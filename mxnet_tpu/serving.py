"""Dynamic-batching inference engine — the serving layer.

The reference served concurrent clients through the dependency
engine's async dispatch (SURVEY §2 layer 2): many small requests in
flight, the engine keeping the device busy.  The TPU-native equivalent
is **dynamic micro-batching over a cache of pre-compiled bucket
executables** — the pattern production TPU serving stacks use to keep
the MXU fed under bursty, variable-size traffic:

* a thread-safe request queue accepts single samples or small batches
  and hands each caller a :class:`~concurrent.futures.Future`;
* a micro-batcher coalesces pending requests until ``max_batch`` fills
  or ``batch_timeout_ms`` expires, then pads the coalesced batch up to
  the nearest size in a bucket ladder (default ``1/8/32/128``);
* each bucket size gets ONE ahead-of-time-compiled jitted forward
  (input buffers donated on accelerators), compiled lazily on first
  use and reused for every later batch of that bucket — the
  ``BucketingModule`` shared-arena pattern applied to inference;
* dispatch and completion run on separate threads, so H2D staging of
  micro-batch k+1 (``io.stage_array`` — the ``PrefetchingIter``
  machinery) overlaps the device compute of micro-batch k.

Counters/histograms (queue depth, batch-fill ratio, request latency,
flush reasons) surface through :mod:`mxnet_tpu.profiler`'s metrics
registry and through :meth:`InferenceEngine.stats`.

Correctness contract: every output row a caller receives is bit-
identical to running its request alone through the same executable —
padding rows ride along in the batch but are sliced off before the
future resolves, and row-wise ops (everything a forward pass does to
the batch axis) do not mix rows.
"""

from __future__ import annotations

import collections
import os
import queue as _queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Deque, Dict, List, Optional, Sequence

import numpy as np

from .base import MXNetError, get_env
from . import profiler
from . import slo as _slo
from .adapters import QuotaExceededError
from .chaos import get_chaos

__all__ = ["InferenceEngine", "DecodeEngine", "EngineClosedError",
           "ReplicaHarness"]

_DEFAULT_BUCKETS = (1, 8, 32, 128)
# prefills a decode engine keeps unfetched at most: as many first tokens
# as one decode step can take from the device (``DecodeEngine._feed_exe``)
_FIRSTS_AHEAD = 4
# why a latent spec's pages neither leave nor arrive (fmt 1 frames)
_NO_LATENT_FRAME = ("a migration frame is a K and a V slab a layer; a "
                    "layer that keeps ONE latent row a token has no wire "
                    "format yet")


def _phase_breakdown(summ: dict, phases: Dict[str, str]) -> dict:
    """Per-phase latency percentiles from a registry summary: the
    ``latency_breakdown`` object the benches attach to their JSON so a
    p99 regression names the phase (queue_wait / prefill / decode /
    ...) instead of reporting one opaque number.  Phases with no
    samples yet are omitted."""
    out = {}
    for phase, hist in phases.items():
        h = summ["histograms"].get(hist)
        if h:
            out[phase] = {"p50_ms": round(h["p50"], 3),
                          "p99_ms": round(h["p99"], 3),
                          "count": h["count"]}
    return out


class EngineClosedError(MXNetError):
    """Named failure for futures outstanding when an engine shuts down
    (or when its serving loop dies): raised AT WAIT by every affected
    future instead of letting callers block forever — the PR-3
    'failure poisoning raises at wait instead of hanging' convention
    applied to the serving tier."""


class _Request:
    __slots__ = ("inputs", "n", "future", "t_submit", "trace")

    def __init__(self, inputs, n, future, t_submit, trace=None):
        self.inputs = inputs      # {name: np.ndarray with leading n}
        self.n = n                # samples in this request
        self.future = future
        self.t_submit = t_submit
        self.trace = trace        # TraceContext | None (observer only)


class _PredictorModel:
    """Adapter: a Predictor's forward closure, re-jittable per bucket."""

    def __init__(self, predictor):
        self._pred = predictor
        self.input_names = list(predictor._input_names)
        # per-sample shapes: the Predictor's bound batch dim is dropped
        self.sample_shapes = {n: tuple(predictor._input_shapes[n][1:])
                              for n in self.input_names}
        self.input_dtypes = {n: np.dtype(predictor._input_dtypes[n])
                             for n in self.input_names}
        self.output_names = list(predictor.output_names)
        self.device = predictor._ctx.jax_device()
        self._forward = predictor.forward_closure()

    def compile(self, bucket: int, donate: bool):
        """AOT-compile the forward at batch size ``bucket``."""
        import jax

        specs = {n: jax.ShapeDtypeStruct((bucket,) + self.sample_shapes[n],
                                         self.input_dtypes[n])
                 for n in self.input_names}
        jitted = jax.jit(self._forward,
                         donate_argnums=(0,) if donate else ())
        return jitted.lower(specs).compile()

    def set_params(self, params):
        """Live weight swap: install new weights on the Predictor and
        re-pull the forward closure (compiled executables baked the OLD
        weights in as constants — the caller must recompile)."""
        self._pred.set_params(params)
        self._forward = self._pred.forward_closure()

    def get_params(self):
        """Host-side snapshot of the served weights (merged weights +
        aux) — the rollback anchor for a failed swap."""
        import numpy as _np

        return {n: _np.asarray(v) for n, v in
                {**self._pred._weights, **self._pred._aux}.items()}


class _ExportedModel:
    """Adapter: a ``predictor.export_model`` artifact.

    Exported StableHLO is shape-frozen, so the ladder collapses to the
    single batch size the artifact was exported at — everything pads to
    it.  Still benefits from coalescing + async completion."""

    def __init__(self, path_or_bytes):
        from .predictor import load_exported

        fn, meta = load_exported(path_or_bytes)
        self._fn = fn
        self.input_names = list(meta["inputs"])
        shapes = meta["input_shapes"]
        self.export_batch = int(shapes[self.input_names[0]][0])
        self.sample_shapes = {n: tuple(shapes[n][1:])
                              for n in self.input_names}
        # dtypes ride the header since the engine was added; artifacts
        # exported before that were float32-only
        dtypes = meta.get("input_dtypes", {})
        self.input_dtypes = {n: np.dtype(dtypes.get(n, "float32"))
                             for n in self.input_names}
        self.output_names = list(meta.get("outputs", []))
        import jax

        self.device = jax.devices()[0]

    def set_params(self, params):
        raise MXNetError(
            "exported artifacts are weight-frozen StableHLO — no live "
            "swap; re-export and restart the replica instead")

    def get_params(self):
        raise MXNetError("exported artifacts embed their weights; "
                         "there is nothing to snapshot")

    def compile(self, bucket: int, donate: bool):
        if bucket != self.export_batch:
            raise MXNetError(
                f"exported artifact is frozen at batch "
                f"{self.export_batch}; cannot compile bucket {bucket}")
        fn = self._fn
        names = self.input_names

        def call(inputs):
            return fn(*[inputs[n] for n in names])

        return call


class InferenceEngine:
    """Dynamic micro-batching over a bucketed executable cache.

    Parameters
    ----------
    model : Predictor
        The loaded model; its bound batch size is irrelevant — the
        engine compiles its own per-bucket executables.
    buckets : sequence of int
        Batch-size ladder.  A coalesced batch of ``n`` real samples
        pads to the smallest bucket ``>= n``.
    max_batch : int, optional
        Coalescing ceiling (default: the largest bucket).  A single
        request may carry at most this many samples.
    batch_timeout_ms : float
        How long the batcher waits for more requests after the first
        one arrives before flushing a partial batch — while the device
        is busy with a previous micro-batch (waiting costs nothing:
        dispatch would queue anyway).
    idle_timeout_ms : float
        The much shorter grace used when the device is IDLE: holding a
        request on an idle device only pays off if more load arrives
        within the window, so the default (0.5 ms) is just enough to
        coalesce a thread-wakeup burst of closed-loop clients.  Set it
        equal to ``batch_timeout_ms`` for strict deadline batching.
    queue_depth : int
        Request-queue bound; ``submit`` blocks when full (backpressure).
    pipeline_depth : int
        In-flight micro-batches between dispatch and completion; 2
        keeps one batch staging while one computes.
    prewarm : bool
        Compile every bucket at construction instead of lazily.
    donate : bool, optional
        Donate input buffers to XLA (default: on for accelerator
        backends, off on CPU where donation is unsupported).
    """

    def __init__(self, model, buckets: Sequence[int] = _DEFAULT_BUCKETS,
                 max_batch: Optional[int] = None,
                 batch_timeout_ms: float = 2.0,
                 idle_timeout_ms: float = 0.5, queue_depth: int = 1024,
                 pipeline_depth: int = 2, prewarm: bool = False,
                 donate: Optional[bool] = None):
        from .predictor import Predictor

        if isinstance(model, Predictor):
            self._model = _PredictorModel(model)
        elif isinstance(model, (_PredictorModel, _ExportedModel)):
            self._model = model
        else:
            raise MXNetError(
                "InferenceEngine wraps a Predictor or an exported "
                f"artifact (use from_exported); got {type(model)}")
        if isinstance(self._model, _ExportedModel):
            buckets = (self._model.export_batch,)
        self._buckets = tuple(sorted({int(b) for b in buckets}))
        if not self._buckets or self._buckets[0] < 1:
            raise MXNetError(f"bad bucket ladder {buckets}")
        self._max_batch = int(max_batch or self._buckets[-1])
        if self._max_batch > self._buckets[-1]:
            raise MXNetError(
                f"max_batch {self._max_batch} exceeds the largest "
                f"bucket {self._buckets[-1]}")
        self._timeout_s = float(batch_timeout_ms) / 1000.0
        self._idle_timeout_s = min(float(idle_timeout_ms) / 1000.0,
                                   self._timeout_s)
        self._inflight_n = 0  # micro-batches dispatched, not yet done
        if donate is None:
            import jax

            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)

        self._queue: _queue.Queue = _queue.Queue(maxsize=queue_depth)
        self._pipeline_depth = int(pipeline_depth)
        self._inflight: _queue.Queue = _queue.Queue(maxsize=pipeline_depth)
        self._carry: Optional[_Request] = None
        self._building: Optional[List[_Request]] = None
        self._cache: Dict[int, Any] = {}
        self._lock = threading.Lock()  # stats
        self._compile_lock = threading.Lock()  # one compile per bucket
        self.compiles: Dict[int, int] = {}  # bucket -> compile count
        # engine-local counters + histograms — same machinery as the
        # global registry, but scoped to this engine; _count() mirrors
        # every engine counter into the global registry too
        self._metrics = profiler.MetricsRegistry()
        # learned cost model: bucket -> EMA of end-to-end batch ms.
        # Decides whether growing a batch across a bucket boundary
        # raises or lowers the projected serving rate (on CPU, batch
        # time ~scales with the bucket; on TPU it's nearly flat until
        # the MXU fills — the engine measures instead of assuming).
        self._bucket_ms: Dict[int, float] = {}
        self._alive = True
        self._accepting = True
        self._reject = None  # drain(): submit's refusal message
        # every accepted-but-unresolved request's future: the
        # inflight() snapshot the fleet router reads — without it the
        # only way to know what died with an engine is to OWN its
        # futures (see ReplicaHarness)
        self._owned: set = set()
        # orders submit's (check, put) against close's (clear, sentinel):
        # an accepted request always lands BEFORE the sentinel, so the
        # drain path serves it instead of stranding its future
        self._accept_lock = threading.Lock()

        if prewarm:
            self.warmup()

        # ops surface: MXNET_METRICS_PORT (no-op when unset) + the
        # /statusz engine section (one engine per serving process in
        # the fleet; a later engine in the same process takes over)
        profiler.maybe_start_metrics_server()
        profiler.register_statusz("engine", self.stats)

        self._batcher = threading.Thread(
            target=self._batch_loop, daemon=True,
            name="mxnet_tpu-serving-batcher")
        self._completer = threading.Thread(
            target=self._complete_loop, daemon=True,
            name="mxnet_tpu-serving-completer")
        self._batcher.start()
        self._completer.start()

    # ------------------------------------------------------------------
    @classmethod
    def from_exported(cls, path_or_bytes, **kwargs):
        """Serve a ``predictor.export_model`` artifact (single-bucket:
        its exported batch size)."""
        kwargs.pop("buckets", None)
        return cls(_ExportedModel(path_or_bytes), **kwargs)

    # -- client surface -------------------------------------------------
    def submit(self, inputs, trace=None) -> Future:
        """Enqueue one request; returns a Future resolving to the list
        of output arrays, each with leading dim = this request's sample
        count.

        ``inputs``: ``{input_name: array}`` (leading batch dim, or a
        bare per-sample shape for n=1), or a single array when the
        model has exactly one input.  ``trace``: optional
        :class:`profiler.TraceContext` — the engine stamps its queue
        and exec spans as children (the fleet wire propagates it).
        """
        if not self._accepting:
            raise MXNetError(self._reject or "InferenceEngine is closed")
        names = self._model.input_names
        if not isinstance(inputs, dict):
            if len(names) != 1:
                raise MXNetError(
                    f"model has inputs {names}; pass a dict")
            inputs = {names[0]: inputs}
        missing = set(names) - set(inputs)
        if missing:
            raise MXNetError(f"inputs not set: {sorted(missing)}")
        batch: Dict[str, np.ndarray] = {}
        n = None
        for name in names:
            sshape = self._model.sample_shapes[name]
            arr = np.asarray(
                getattr(inputs[name], "asnumpy", lambda: inputs[name])(),
                dtype=self._model.input_dtypes[name])
            if arr.shape == sshape:  # bare single sample
                arr = arr[None]
            if arr.shape[1:] != sshape:
                raise MXNetError(
                    f"input {name!r} shape {arr.shape} != (n,) + {sshape}")
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise MXNetError(
                    f"inconsistent sample counts: {name!r} has "
                    f"{arr.shape[0]}, expected {n}")
            batch[name] = arr
        if n == 0:
            raise MXNetError("empty request")
        if n > self._max_batch:
            raise MXNetError(
                f"request of {n} samples exceeds max_batch "
                f"{self._max_batch}; split it client-side")
        fut: Future = Future()
        req = _Request(batch, n, fut, time.perf_counter(), trace=trace)
        # gauge only — exporting the same family as both a histogram
        # and a gauge would make prometheus_text() an invalid exposition
        profiler.set_gauge("serving.queue_depth", self._queue.qsize())
        # backpressure without holding the accept lock through a
        # blocking put: a full queue must stall THIS caller only, not
        # serialize every other submitter (or close()) behind it
        while True:
            with self._accept_lock:
                if not self._accepting:  # close()/drain() raced us
                    raise MXNetError(
                        self._reject or "InferenceEngine is closed")
                try:
                    self._queue.put_nowait(req)
                    break
                except _queue.Full:
                    pass
            time.sleep(0.002)  # wait for the batcher to drain a slot
        # count only after the put: a request rejected by the race
        # above was never accepted and must not skew requests-vs-images
        self._count("requests")
        # membership-first then callback: if the future is ALREADY done
        # the callback runs inline and discards what we just added
        with self._lock:
            self._owned.add(fut)
        fut.add_done_callback(self._disown)
        return fut

    def _disown(self, fut):
        with self._lock:
            self._owned.discard(fut)

    def inflight(self) -> int:
        """Accepted-but-unresolved request count: queued, coalescing,
        or dispatched — everything that would die with this engine.
        Poisoned futures (a dead loop, close()) leave the count the
        moment their exception is set, so after a drain/shutdown this
        reads 0."""
        with self._lock:
            return len(self._owned)

    def drain(self, timeout: float = 30.0) -> int:
        """Stop accepting new requests and wait for the in-flight ones
        to finish.  Returns the number still unresolved at the
        deadline (0 = fully quiesced).  The engine stays alive —
        ``resume()`` re-opens admission (the rolling weight-swap
        choreography: drain → swap_params → warmup → resume)."""
        with self._accept_lock:
            if self._accepting:
                self._reject = ("InferenceEngine is draining — not "
                                "accepting requests (weight swap in "
                                "progress)")
                self._accepting = False
        deadline = time.perf_counter() + float(timeout)
        while self.inflight() and time.perf_counter() < deadline:
            time.sleep(0.002)
        return self.inflight()

    def resume(self):
        """Re-open admission after :meth:`drain`."""
        if not self._alive:
            raise MXNetError("cannot resume a closed InferenceEngine")
        with self._accept_lock:
            self._reject = None
            self._accepting = True

    def swap_params(self, params):
        """Live weight swap: requires a drained engine (compiled bucket
        executables bake the weights in as constants, so they are all
        invalidated).  Call :meth:`warmup` before :meth:`resume` — a
        lazy recompile inside the serving path is exactly the p99 spike
        a rolling update exists to avoid."""
        n = self.inflight()
        if n:
            raise MXNetError(
                f"swap_params with {n} request(s) in flight — drain() "
                "first (their batches would mix weight versions)")
        with self._compile_lock:
            self._model.set_params(params)
            self._cache = {}
            with self._lock:
                self._bucket_ms.clear()  # re-learn: weights changed

    def get_params(self):
        """Host snapshot of the served weights (merged weights + aux)
        — the rollback anchor a failed swap restores from."""
        return self._model.get_params()

    def _count(self, name, value=1.0):
        self._metrics.inc(name, value)
        profiler.inc_counter(f"serving.{name}", value)

    def infer(self, inputs):
        """Synchronous convenience: ``submit(...).result()``."""
        return self.submit(inputs).result()

    def warmup(self):
        """Compile every bucket now (otherwise lazy on first use) and
        run each once on zeros — seeds the per-bucket cost model and
        flushes any first-run autotuning out of the serving path."""
        from .io import stage_array

        for b in self._buckets:
            exe = self._executable(b)
            inputs = {
                n: stage_array(
                    np.zeros((b,) + self._model.sample_shapes[n],
                             dtype=self._model.input_dtypes[n]),
                    self._model.device)
                for n in self._model.input_names}
            t0 = time.perf_counter()
            for o in exe(inputs):
                np.asarray(o)
            with self._lock:
                self._bucket_ms[b] = (time.perf_counter() - t0) * 1e3

    # -- stats ----------------------------------------------------------
    _COUNTERS = ("requests", "images", "slots", "batches", "flush_full",
                 "flush_timeout", "flush_boundary", "cache_hits",
                 "cache_misses")

    def stats(self) -> dict:
        """Engine-local snapshot: counters, per-bucket compile counts,
        slot-weighted batch-fill ratio, latency percentiles."""
        with self._lock:
            compiles = dict(self.compiles)
        summ = self._metrics.summary()
        lat = summ["histograms"].get("latency_ms")
        out = {name: int(summ["counters"].get(name, 0))
               for name in self._COUNTERS}
        out["compiles"] = compiles
        # slot-weighted: real samples / padded slots dispatched — the
        # documented padding-waste metric (an unweighted mean of
        # per-batch fills would overstate utilization whenever bucket
        # sizes are mixed)
        out["batch_fill_ratio"] = (out["images"] / out["slots"]
                                   if out["slots"] else None)
        out["p50_ms"] = lat["p50"] if lat else None
        out["p90_ms"] = lat["p90"] if lat else None
        out["p99_ms"] = lat["p99"] if lat else None
        # rate-since-reset (engine start), from the shared summary schema
        out["requests_per_s"] = summ["rates"].get("requests", 0.0)
        out["images_per_s"] = summ["rates"].get("images", 0.0)
        out["buckets"] = list(self._buckets)
        out["latency_breakdown"] = _phase_breakdown(
            summ, {"queue_wait": "queue_wait_ms",
                   "exec": "batch_ms", "total": "latency_ms"})
        return out

    # -- lifecycle ------------------------------------------------------
    def close(self, timeout: float = 30.0):
        """Stop accepting requests, drain in-flight work, join threads."""
        if not self._alive:
            return
        with self._accept_lock:
            self._accepting = False
            self._queue.put(None)  # batcher drains everything before this
        self._batcher.join(timeout=timeout)
        self._alive = False
        self._completer.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close(timeout=1.0)
        except Exception:
            pass

    # -- bucket cache ---------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]  # unreachable: n <= max_batch <= last

    def _boundary_flush(self, total: int, add: int) -> bool:
        """Would adding ``add`` samples push this batch into a bigger
        bucket whose measured rate is WORSE than shipping now?

        Compares projected img/s: ``total / t(bucket_now)`` against
        ``(total + add + backlog) / t(bucket_next)`` where backlog is
        what's already queued (capped at the next bucket's headroom).
        On TPU ``t`` is nearly flat across buckets, so the batch always
        grows; on CPU ``t`` scales with the bucket and half-empty big
        buckets lose.  With no measurements yet (bucket never run),
        grow — exploring compiles/updates the model."""
        b = self._bucket_for(total)
        nb = self._bucket_for(total + add)
        if nb <= b:
            return False
        t_b = self._bucket_ms.get(b)
        t_nb = self._bucket_ms.get(nb)
        if not t_b or not t_nb:
            return False
        backlog = min(self._queue.qsize(), nb - total - add)
        return total / t_b >= (total + add + backlog) / t_nb

    def _executable(self, bucket: int):
        # lock-free fast path: entries are never replaced, so a hit
        # must not stall behind another bucket's in-progress compile
        exe = self._cache.get(bucket)
        if exe is not None:
            self._count("cache_hits")
            return exe
        # the compile lock serializes a user-thread warmup() racing the
        # batcher: without it both read a cold cache and compile twice
        with self._compile_lock:
            exe = self._cache.get(bucket)
            if exe is not None:
                self._count("cache_hits")
                return exe
            with profiler.scope(f"serving.compile.b{bucket}", "serving",
                                args={"bucket": bucket}):
                exe = self._model.compile(bucket, self._donate)
            self._cache[bucket] = exe
            with self._lock:
                self.compiles[bucket] = self.compiles.get(bucket, 0) + 1
            self._count("cache_misses")
            return exe

    # -- batcher thread: coalesce → pad → stage → dispatch --------------
    def _batch_loop(self):
        try:
            self._batch_loop_inner()
        except BaseException as exc:  # loop died: poison, don't hang
            # every queued request would otherwise wait forever and
            # close() would block on a completer that never gets its
            # sentinel — fail them all with a named error instead
            profiler.dump_flight_record(
                "engine_crash", extra={"error": repr(exc)})
            self._shutdown(EngineClosedError(
                f"InferenceEngine batch loop died: {exc!r}"))
            raise

    def _batch_loop_inner(self):
        while True:
            first = self._carry
            self._carry = None
            if first is None:
                first = self._queue.get()
            if first is None:  # close() sentinel
                self._shutdown()
                return
            batch = [first]
            # visible to _shutdown: a loop death mid-coalesce must fail
            # the requests already popped off the queue too
            self._building = batch
            total = first.n
            reason = "full" if total >= self._max_batch else "timeout"
            closing = False
            t_first = time.perf_counter()
            while reason == "timeout":
                # Three regimes, by how busy the device pipeline is:
                # * pipeline full: dispatching would only block — the
                #   deadline is suspended and the batch keeps growing
                #   until a slot frees (this is what lets a long
                #   device batch accumulate a FULL next batch instead
                #   of fragmenting into deadline-sized slivers);
                # * device busy, slot free: hold up to the full
                #   deadline for stragglers;
                # * device idle: a short grace — holding a request on
                #   an idle device only pays if more load is coming.
                suspended = self._inflight_n >= self._pipeline_depth
                if suspended:
                    remaining = 0.005  # poll: a slot may free any time
                else:
                    window = (self._timeout_s if self._inflight_n > 0
                              else self._idle_timeout_s)
                    remaining = t_first + window - time.perf_counter()
                    if remaining <= 0:
                        break
                try:
                    req = self._queue.get(timeout=remaining)
                except _queue.Empty:
                    if suspended:
                        continue  # deadline suspended; re-check the slot
                    break
                if req is None:  # drain: flush what we have, then exit
                    closing = True
                    break
                if total + req.n > self._max_batch:
                    self._carry = req  # belongs to the next micro-batch
                    reason = "full"
                    break
                if self._boundary_flush(total, req.n):
                    self._carry = req
                    reason = "boundary"
                    break
                batch.append(req)
                total += req.n
                if total >= self._max_batch:
                    reason = "full"
            self._building = None
            try:
                self._dispatch(batch, total, reason)
            except Exception:  # _dispatch already failed the futures
                pass
            if closing:
                self._shutdown()
                return

    def _shutdown(self, exc: Optional[Exception] = None):
        """Fail stragglers that raced close() (or that a dead batch
        loop stranded), then release the completion thread."""
        exc = exc or EngineClosedError("InferenceEngine closed")
        building, self._building = self._building, None
        for req in building or ():
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(exc)
        carry = self._carry
        self._carry = None
        while True:
            if carry is not None:
                req, carry = carry, None
            else:
                try:
                    req = self._queue.get_nowait()
                except _queue.Empty:
                    break
            if req is not None and req.future.set_running_or_notify_cancel():
                req.future.set_exception(exc)
        self._inflight.put(None)

    def _dispatch(self, batch: List[_Request], total: int, reason: str):
        from .io import stage_array

        t0 = time.perf_counter()
        for req in batch:
            # per-request queue/coalesce wait: the first slice of the
            # latency-breakdown (and a child span of the request trace)
            wait_ms = (t0 - req.t_submit) * 1e3
            self._metrics.observe("queue_wait_ms", wait_ms)
            profiler.observe("serving.queue_wait_ms", wait_ms)
            if req.trace is not None:
                profiler.add_trace_event(
                    "serving.queue", req.t_submit, t0 - req.t_submit,
                    req.trace.child(), cat="serving",
                    args={"n": req.n, "reason": reason})
        try:
            bucket = self._bucket_for(total)
            compiled_now = bucket not in self._cache
            exe = self._executable(bucket)
            names = self._model.input_names
            with profiler.scope(f"serving.stage.b{bucket}", "serving",
                                args={"bucket": bucket, "n": total}):
                padded = {}
                for name in names:
                    buf = np.zeros(
                        (bucket,) + self._model.sample_shapes[name],
                        dtype=self._model.input_dtypes[name])
                    off = 0
                    for req in batch:
                        buf[off:off + req.n] = req.inputs[name]
                        off += req.n
                    # async H2D: the PrefetchingIter staging machinery —
                    # this transfer overlaps the previous batch's compute
                    padded[name] = stage_array(buf, self._model.device)
            with profiler.scope(f"serving.enqueue.b{bucket}", "serving",
                                args={"bucket": bucket, "n": total,
                                      "reason": reason}):
                outs = exe(padded)  # async dispatch; completion thread blocks
        except BaseException as exc:
            # BaseException too: a KeyboardInterrupt/MemoryError here
            # kills the batch loop, and by this point the batch is off
            # the queue and out of _building — nothing else can fail
            # these futures, so an Exception-only net would strand them
            for req in batch:
                if not req.future.set_running_or_notify_cancel():
                    continue
                req.future.set_exception(exc)
            raise
        with self._lock:
            self._inflight_n += 1
        self._count("batches")
        self._count("images", total)
        self._count("slots", bucket)  # padded capacity actually dispatched
        self._count(f"flush_{reason}")
        profiler.observe("serving.batch_fill", total / bucket)
        # re-sample post-drain so the gauge doesn't freeze at the
        # backlog the LAST submit happened to see
        profiler.set_gauge("serving.queue_depth", self._queue.qsize())
        self._inflight.put((outs, batch, t0, bucket, compiled_now))

    # -- completion thread: block on device, slice, resolve -------------
    def _complete_loop(self):
        last_done = 0.0
        while True:
            item = self._inflight.get()
            if item is None:
                return
            outs, batch, t0, bucket, compiled_now = item
            try:
                host = [np.asarray(o) for o in outs]  # blocks on device
            except Exception as exc:
                with self._lock:
                    self._inflight_n -= 1
                for req in batch:
                    if req.future.set_running_or_notify_cancel():
                        req.future.set_exception(exc)
                continue
            now = time.perf_counter()
            batch_ms = (now - t0) * 1e3
            # dispatch→completion wall: the per-bucket cost span (the
            # enqueue-side scope only times XLA's async handoff)
            profiler.add_event(f"serving.batch.b{bucket}", t0, now - t0,
                               "serving",
                               args={"bucket": bucket,
                                     "n": sum(r.n for r in batch)})
            # cost-model sample: occupancy, not latency — a pipelined
            # batch dispatched while its predecessor still computed
            # only occupied the device from the predecessor's finish.
            # A batch that triggered its bucket's (lazy) compile is not
            # a sample at all: folding seconds of XLA compile into the
            # EMA would poison _boundary_flush for many batches.
            exec_ms = (now - max(t0, last_done)) * 1e3
            last_done = now
            with self._lock:
                self._inflight_n -= 1
                if not compiled_now:
                    old = self._bucket_ms.get(bucket)
                    self._bucket_ms[bucket] = (
                        exec_ms if old is None
                        else 0.5 * old + 0.5 * exec_ms)
            profiler.observe("serving.batch_ms", batch_ms)
            # an output that reduced over the batch axis cannot be
            # sliced back per-request — failing loudly beats handing
            # one client a value computed over another client's rows
            bad = [i for i, o in enumerate(host)
                   if o.shape[:1] != (bucket,)]
            if bad:
                exc = MXNetError(
                    f"output(s) {bad} have leading dims "
                    f"{[host[i].shape for i in bad]} != bucket "
                    f"{bucket}: the model reduces over the batch "
                    f"axis, so its outputs cannot be served "
                    f"per-request by the batching engine")
                for req in batch:
                    if req.future.set_running_or_notify_cancel():
                        req.future.set_exception(exc)
                continue
            off = 0
            for req in batch:
                # copy, not view: a view would pin the whole padded
                # bucket output (128x the request for a 1-sample request
                # in the top bucket) for as long as the caller holds it
                rows = [np.array(o[off:off + req.n]) for o in host]
                off += req.n
                if req.trace is not None:
                    # the batch's device time, as THIS request's child
                    # span — every rider shares the same bounds
                    profiler.add_trace_event(
                        "serving.exec", t0, now - t0,
                        req.trace.child(), cat="serving",
                        args={"bucket": bucket, "n": req.n})
                if req.future.set_running_or_notify_cancel():
                    req.future.set_result(rows)
                lat_ms = (now - req.t_submit) * 1e3
                self._metrics.observe("latency_ms", lat_ms)
                profiler.observe("serving.latency_ms", lat_ms)


# ---------------------------------------------------------------------------
# Autoregressive serving: continuous batching over a paged KV cache.
# ---------------------------------------------------------------------------


def sample_tokens(base_key, logits, temps, seeds, steps):
    """On-device greedy/temperature sampling, per-stream keyed by
    (engine seed, stream seed, absolute position) — reproducible
    whatever batch the stream happens to ride in.  Module-level so the
    mesh step programs (``serving_mesh``) run the EXACT sampler the
    single-device engine runs: the fleet's decode-retry bit-replay
    holds across tp/pp shapes."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(sd, st, row, tp):
        key = jax.random.fold_in(jax.random.fold_in(base_key, sd), st)
        safe = jnp.where(tp > 0, tp, 1.0)
        return jax.random.categorical(key, row / safe).astype(jnp.int32)

    sampled = jax.vmap(one)(seeds, steps, logits, temps)
    return jnp.where(temps > 0, sampled, greedy)


def _read_env_int(name, lo=1):
    """Loud at-construction validation (the checkpoint env-var
    convention): garbage values raise immediately, naming the
    variable.  The default comes from the config catalog — the one
    place it is declared — so ``mx.config.describe`` never documents
    a default the engine doesn't actually use."""
    from . import config

    raw = get_env(name, None, str)
    if raw is None:
        return config.describe(name).default
    try:
        v = int(raw)
    except ValueError:
        raise MXNetError(f"{name}={raw!r} is not an integer")
    if v < lo:
        raise MXNetError(f"{name}={v} must be >= {lo}")
    return v


def _read_env_str(name, choices=None):
    """String env var resolved through the config catalog, optionally
    validated against a closed vocabulary (loud at construction)."""
    from . import config

    raw = get_env(name, None, str)
    if raw is None:
        raw = config.describe(name).default
    if choices is not None and raw not in choices:
        raise MXNetError(f"{name}={raw!r} must be one of {choices}")
    return raw


def _read_env_buckets(name, default):
    """CSV bucket ladder: strictly increasing positive ints."""
    raw = get_env(name, None, str)
    if raw is None:
        return default
    try:
        vals = [int(x) for x in raw.split(",") if x.strip()]
    except ValueError:
        raise MXNetError(f"{name}={raw!r} is not a comma-separated "
                         f"list of integers")
    if not vals or any(v < 1 for v in vals) \
            or any(b <= a for a, b in zip(vals, vals[1:])):
        raise MXNetError(f"{name}={raw!r} must be a strictly "
                         f"increasing ladder of positive ints")
    return vals


def _prefix_salt(s) -> bytes:
    """Prefix-cache namespace for a stream: adapted K/V is a function
    of (tokens, adapter), so each adapter gets its own radix subtree —
    a prefix prefilled under LoRA adapter X must never satisfy a plain
    stream or one of adapter Y.  Plain streams share the unsalted
    tree, bit-compatible with the pre-adapter cache."""
    return s.adapter.encode("utf-8") if s.adapter else b""


class _Stream:
    """One in-flight generation: host-side state the scheduler owns."""

    __slots__ = ("sid", "prompt", "max_new", "temp", "eos", "future",
                 "seed", "generated", "blocks", "length", "next_token",
                 "resume", "t_submit", "t_admit", "trace", "t_enqueue",
                 "cached_len", "await_first", "t_chunk0", "slo_class",
                 "canary", "cost", "migrate", "tenant", "adapter",
                 "adapter_bucket", "adapter_slot", "slot", "keep_state",
                 "wblocks", "wfirst", "ahead", "feed")

    def __init__(self, sid, prompt, max_new, temp, eos, future, seed,
                 trace=None, slo_class="interactive", canary=False,
                 tenant=None, adapter=None):
        self.sid = sid
        self.prompt = prompt          # np.int32 (P,)
        self.max_new = max_new
        self.temp = temp
        self.eos = eos
        self.future = future
        self.seed = seed
        self.generated: List[int] = []
        self.blocks: List[int] = []   # page ids held (host block table)
        # SCHEDULING state, advanced when a program is dispatched:
        self.length = 0               # tokens cached, or being cached by
        #                               a program already dispatched
        self.ahead = 0                # tokens sampled on the device and
        #                               not fetched yet
        self.feed = None              # (device tokens, row) of the newest
        #                               of them: the next step's input
        # DELIVERY state, advanced when a program's tokens are fetched
        # (``generated`` above, and):
        self.next_token = -1          # sampled, fetched, not yet fed
        self.resume = False           # re-prefill after preemption
        self.t_submit = time.perf_counter()
        self.t_admit = 0.0
        self.t_enqueue = self.t_submit  # (re)joined the pending queue
        self.trace = trace            # TraceContext | None
        self.cached_len = 0           # prefix-cache tokens attached
        self.await_first = False      # full hit: first token pending
        self.t_chunk0 = 0.0           # chunked prefill: first chunk start
        self.slo_class = slo_class    # validated at submit()
        self.canary = canary          # excluded from request counters
        self.migrate = False          # prefill-only: export after TTFT
        self.tenant = tenant          # quota + cost-attribution key
        self.adapter = adapter        # published adapter name | None
        self.adapter_bucket = None    # rank bucket (set on acquire)
        self.adapter_slot = None      # pool slot id (set on acquire)
        self.slot = 0                 # recurrent-state slot held (0: none)
        # windowed layers' pages, by logical block like ``blocks``; the
        # entries before ``wfirst`` lie behind the window: given back,
        # or never taken (0, the scratch page)
        self.wblocks: List[int] = []
        self.wfirst = 0
        self.keep_state = False       # resolve with the slot's state too
        self.cost = _slo.CostRecord(sid, slo_class, canary,
                                    tenant=tenant, adapter_id=adapter)
        self.cost.prompt_tokens = int(prompt.size)

    def prefill_seq(self) -> np.ndarray:
        """Token sequence whose K/V the cache must hold before the
        next decode step: the prompt, plus — after a preemption — all
        sampled tokens except the pending ``next_token``."""
        if not self.resume:
            return self.prompt
        return np.concatenate(
            [self.prompt,
             np.asarray(self.generated[:-1], np.int32)])

    def done(self) -> bool:
        return (len(self.generated) >= self.max_new
                or (self.eos is not None and self.generated
                    and self.generated[-1] == self.eos))

    def last_by_count(self) -> bool:
        """Its tokens in flight are its last: known without reading
        one, so the next batch is composed without it."""
        return len(self.generated) + self.ahead >= self.max_new


class _Flight:
    """One dispatched program whose sampled tokens are still on the
    device: a decode step's (one a row of ``streams``), or a prefill's
    first (``prefill``: what its booking needs; None for a step)."""

    __slots__ = ("toks", "streams", "t0", "bb", "fl", "prefill")

    def __init__(self, toks, streams, t0, bb=1, fl=0.0, prefill=None):
        self.toks = toks
        self.streams = streams
        self.t0 = t0
        self.bb = bb
        self.fl = fl
        self.prefill = prefill


class DecodeEngine:
    """Continuous-batching autoregressive serving over a paged KV cache.

    The iteration-level scheduler (Orca, Yu et al. OSDI '22): sequences
    join and retire at EVERY decode step, not per request —

    * **prefill** runs the full causal forward over a (bucket-padded)
      prompt once, writing each layer's K/V into fixed-size cache
      pages through the stream's block table;
    * **decode** advances ALL active streams one token per step with a
      single program: one query position per stream against the paged
      cache (``QKVPagedAttentionDecode`` — the Pallas
      gather-by-block-table kernel on TPU), greedy/temperature
      sampling on device, one (B,) int32 D2H per step;
    * executables are AOT-compiled per ``(batch bucket, cache-blocks
      bucket)`` and cached — the ``InferenceEngine`` bucketed-cache
      pattern — with the pool buffers donated so the cache updates in
      place on accelerators;
    * **admission control** is keyed to free cache blocks: a pending
      request is admitted only when its prompt's pages (plus one block
      of decode headroom) are free.  When a growing stream finds the
      pool empty, the YOUNGEST stream is preempted — its pages freed,
      its progress re-queued for re-prefill (recompute-style
      preemption; ``serving.preempted`` counts them).

    **The loop runs one program ahead of what it has read**: the chip's
    queue is never empty while a stream is active.  A stream's
    SCHEDULING state (its length, its pages, whether it is in the next
    batch) advances when a program is dispatched; its DELIVERY state
    (``generated``, metrics, retirement, the ``Future``) advances when
    the program's tokens are fetched — after the next program is
    queued.  A turn: admit and dispatch prefills without reading their
    first tokens; dispatch decode step t+1, its token feed gathered ON
    the device from step t's tokens and the new rows' first tokens (one
    small program a batch bucket, ``jit_next_tokens_b<B>``, built by
    ``warmup()``); THEN fetch and book what lies before step t+1, up to
    step t (``serving.d2h_sync``, ``serving.absorb``), while the chip
    runs.  A stream whose tokens in flight are its last by count is out
    of the next batch without a token read; a row it frees is refilled
    one step later than a synchronous loop would.  A stream with ``eos``
    rides ahead too: if step t's token is its ``eos``, its row of step
    t+1 is an *overshoot* — the token is dropped, the K/V it wrote lies
    on a page the stream owned (pages follow the scheduled length), and
    pages and slot go back at once: a page is written again only by a
    program dispatched later, which the device runs later.  Sampling is
    keyed (seed, stream, position), so the served tokens are the
    synchronous loop's.

    Where it may NOT run ahead the loop first fetches and books what is
    in flight — a *drain*, counted by reason
    (``serving.run_ahead.drain.<reason>``) — and goes on as a
    synchronous loop would.  Decided from what is in front of it, no
    option: a verify window (``verify``), a chunked prefill in flight
    (``chunk``: every step between chunks is fetched at once), a page
    growth or copy-on-write about to PREEMPT (``preempt``: a victim is
    rewound to what it has delivered), a page export or import
    (``export``, ``import``), a stream with ``eos`` whose result hands
    back its slot's state (``state_eos``: an overshoot would alter it),
    a step whose batch bucket differs from the one in flight
    (``bucket``), ``swap_params``, ``reset_stats``, ``close`` (tokens
    already sampled are delivered, the rest fails), and the tail, when
    nothing is left to dispatch (``idle``).  A device error surfaces at
    the fetch and fails every stream, those in no list but a program's
    record too.  ``stats()``: ``steps_run_ahead`` (decode programs
    dispatched while an earlier program's tokens were unread),
    ``run_ahead_share`` (of ``steps``), ``run_ahead_drains`` and
    ``run_ahead_drain_reasons``, ``overshoot_row_steps``,
    ``prefill_first_deferred``; ``d2h_syncs`` counts fetches (one a
    program), ``d2h_syncs_saved`` those made with a newer program
    already queued; ``prefill_bucket_tokens`` sums the ROWS of the
    prefill programs that ran (a prompt runs the program of its bucket)
    beside ``prefill_tokens``, the rows that held a token, and
    ``prefill_bucket_fill`` is their ratio: what the bucket ladder's
    padding leaves of a prefill's work; ``prefill_tiles_walked`` /
    ``prefill_tiles_skipped`` count, a layer and head, the key tiles the
    whole-prompt attention kernels walked and the tiles of their buckets
    they left out for knowing the prompt's length
    (``pallas_kernels.prompt_tile_visits`` over the layers
    ``prompt_attention()`` lists), ``prefill_tiles_skipped_share`` the
    share of the buckets' tiles that was left out;
    ``prefill_tiles_masked`` counts the walked tiles that took a masked
    body (an edge of the band crosses them: the others run without a
    mask) and ``prefill_scores_computed_over_needed`` is the score
    elements the kernels' schedule computed (``prefill_scores_computed``)
    over the pairs the prompts' bands hold (``prefill_scores_needed``;
    ``pallas_kernels.prompt_tile_work``).

    Decode numerics: prefill + N decode steps is bit-identical (lax
    path) to the full-sequence causal forward of
    ``transformer_lm(..., block_size=kv_block)`` — the page size IS
    the attention block size (see ops/attention.py).

    Parameters
    ----------
    params : dict
        Parameter arrays by training-symbol name (``Module.get_params``
        arg dict, merged aux, or a ``Predictor``'s weights).
    vocab_size, num_layers, num_heads, d_model, d_ff : int
        Architecture of the served ``transformer_lm``: the keywords
        build ``model=models.transformer.DenseSpec(...)``.
    model : spec, optional
        Any other family.  ALL the engine asks of a served model, and
        it asks by these names only (no class is looked at):

        * ``vocab_size``, ``num_layers``, ``d_model``; ``kv_heads``,
          ``head_dim`` (the geometry of a page's rows; 0 without
          pages: K and V rows of ``kv_heads x head_dim`` — or, for a
          spec whose ``latent_row`` is set, ONE row a token and layer
          shared by all heads, ``kv_heads`` 1 and ``head_dim`` the
          lanes the pool spends on it; ``latent_row`` = (values the row
          needs, lanes it takes), which the ``mla.cache_bytes*`` gauges
          report, and no wire format for its pages);
          ``name`` (how a refusal calls it);
        * ``phases``: which of ``prefill``, ``decode``,
          ``prefix_prefill``, ``verify`` ``symbol(phase, kv_block=,
          kv_dtype=, lora=)`` builds (it refuses the rest).  The first
          two are required; ``prefix_cache`` and ``prefill_chunk`` need
          ``prefix_prefill`` and ``spec_tokens`` needs ``verify``, and
          all three need every layer's state in ordinary pages (no
          slots, no windowed pools);
        * ``feeds``: the symbols' arguments that are no parameter:
          of ``data positions lengths block_table start slots
          window_table``;
        * ``pools(cache_blocks, kv_block, slots, dtype, kv_dtype)``:
          the per-stream state the programs carry, ``(name, shape,
          dtype, fill)`` in the symbols' output order, and
          ``pool_kinds(kv_dtype)``, the kind of each beside it:
          ``pages`` / ``scales`` (rows by page; a migration frame),
          ``window_pages`` (K/V pages of layers that see only the last
          ``window`` keys — the spec's ``window``: a second page-id
          space with its own allocator, ``pools(..., window_blocks=)``
          pages a pool, addressed through the ``window_table`` feed (as
          wide as ``block_table``); a stream holds the pages its window
          still reaches and gives the others back as it grows),
          ``slots`` (rows by the ``slots`` feed, one a stream; what
          ``return_state`` reads) / ``slots_aux`` (the same, read by
          the programs only), ``counters`` (``ops.hybrid.MOE_COUNTERS``,
          read by ``stats()``);
        * ``kv_dtypes``: the K/V storage types it can hold;
          ``lora_width``: the width of the projection that takes LoRA
          epilogues, 0 for none; ``partition_rules``: None, or a
          callable giving the rules table of a ``tp`` x ``pp`` mesh
          (served by ``serving_mesh.MeshPrograms``, which is written for
          the ``transformer_lm`` block and reads ``num_heads``, ``d_ff``);
          ``positions``: the parameter whose rows are the learned
          positions and bound ``max_len``, or None (``max_len``
          required);
        * optionally ``tail_row``: (float32 numbers needed, numbers
          held) a stream, over the layers that keep a convolution's tail
          in a ``slots_aux`` pool BESIDE their pages (a cca mixer): the
          ``cca.cache_bytes_per_token`` (K and V of one layer) and
          ``cca.tail_bytes_per_stream`` gauges report them;
        * optionally ``prompt_attention()``: ``(window, latent)`` a
          layer whose prefill kernel takes the prompt's length, for the
          ``prefill_tiles_*`` counters (absent: none counted).

        A feature the spec cannot carry is refused at construction, by
        name; its catalog default is taken only where it can.  What
        changes a spec's symbols alone is nothing the engine asks: a
        ``models.hybrid_lm.HybridSpec``'s ``post_norm`` and an
        attention layer's ``qk_norm`` serve through the same pools,
        feeds and programs as the specs without them.
    max_len : int, optional
        Longest prompt+generation a stream may reach.  Default: the
        learned positions' row count.
    kv_block : int
        Cache page size in tokens (env ``MXNET_SERVING_KV_BLOCK``,
        default 16).  Also the attention block size.
    max_streams : int
        Concurrent-stream ceiling (env ``MXNET_SERVING_MAX_STREAMS``,
        default 64); the top of the decode batch-bucket ladder.
    cache_blocks : int, optional
        Total pool pages (+1 reserved scratch).  Default sizes the
        pool so every stream can reach ``max_len`` (no preemption);
        pass something smaller to trade memory for preemptions.
    decode_buckets, cache_buckets, prefill_buckets
        Explicit ladders (batch sizes / table widths in blocks /
        prompt tokens); env ``MXNET_SERVING_DECODE_BUCKETS`` /
        ``_CACHE_BUCKETS`` / ``_PREFILL_BUCKETS``.  Defaults: doubling
        ladders.
    temperature : float
        Default sampling temperature; 0 = greedy.  Per-request
        override via ``submit``.
    """

    def __init__(self, params, *, vocab_size=None, num_layers=None,
                 num_heads=None, d_model=None, d_ff=None, model=None,
                 max_len=None, kv_block=None,
                 max_streams=None, cache_blocks=None,
                 decode_buckets=None, cache_buckets=None,
                 prefill_buckets=None, temperature=0.0, seed=0,
                 eos_id=None, ctx=None, donate=None, dtype="float32",
                 kv_dtype=None, prefix_cache=None, evict_policy=None,
                 spec_tokens=None, proposer=None, prefill_chunk=None,
                 tp=None, pp=None, devices=None, prewarm=False,
                 adapters=None, tenant_quota=None):
        import jax

        from .kv_cache import (BlockAllocator, blocks_for_tokens,
                               bucket_ladder, kv_storage_dtype)
        from .executor import build_graph_fn
        from .models.transformer import DenseSpec
        from .prefix_cache import EVICT_POLICIES, PrefixCache
        from .kv_cache import KV_DTYPES, SlotAllocator
        from .speculative import PROPOSERS, make_proposer

        self._blocks_for = blocks_for_tokens

        # -- the model: a spec, or the dense keywords that build one ----
        dense_kw = (vocab_size, num_layers, num_heads, d_model)
        if model is None:
            if None in dense_kw:
                raise MXNetError(
                    "DecodeEngine needs model=<spec> or all of "
                    "vocab_size, num_layers, num_heads, d_model (the "
                    "transformer_lm family)")
            model = DenseSpec(vocab_size, num_layers, num_heads, d_model,
                              d_ff)
        elif any(v is not None for v in dense_kw + (d_ff,)):
            raise MXNetError(
                "give DecodeEngine model=<spec> OR the dense keywords "
                "(vocab_size, num_layers, num_heads, d_model, d_ff), "
                "not both")
        self._spec = model
        vocab_size, num_layers = model.vocab_size, model.num_layers
        # the K/V page geometry (a spec without attention layers has
        # none: 0 heads, and its pools hold no pages)
        num_heads, d_model = model.kv_heads, model.d_model

        # -- prefix cache / KV storage configuration --------------------
        # (loud at-construction validation, the MXNET_CKPT_* pattern)
        self._kv_dtype = kv_dtype if kv_dtype is not None else \
            _read_env_str("MXNET_SERVING_KV_DTYPE", choices=KV_DTYPES)
        if self._kv_dtype not in KV_DTYPES:
            raise MXNetError(
                f"kv_dtype {self._kv_dtype!r} must be one of {KV_DTYPES}")
        kv_store_dtype = kv_storage_dtype(self._kv_dtype)  # may raise
        self._pool_kinds = tuple(model.pool_kinds(self._kv_dtype))
        # a slot a stream: state ``return_state`` reads, or — a layer
        # that holds pages AND a tail — rows the programs alone read
        slots = any(k in ("slots", "slots_aux") for k in self._pool_kinds)
        windowed = "window_pages" in self._pool_kinds
        # a suffix prefill continues from pages alone; a verify step
        # rolls back pages alone: pages that are all still there
        plain = not slots and not windowed
        suffix = "prefix_prefill" in model.phases and plain
        if prefix_cache is None and not suffix \
                and get_env("MXNET_SERVING_PREFIX_CACHE", None, str) is None:
            prefix_cache = 0  # the catalog's default (on): where carried
        if prefix_cache is None:
            prefix_cache = _read_env_int("MXNET_SERVING_PREFIX_CACHE",
                                         lo=0)
        if int(prefix_cache) not in (0, 1):
            raise MXNetError(
                f"MXNET_SERVING_PREFIX_CACHE={prefix_cache!r} must be "
                f"0 or 1")
        self._prefix_on = bool(int(prefix_cache))
        self._evict_policy = evict_policy if evict_policy is not None \
            else _read_env_str("MXNET_SERVING_EVICT",
                               choices=EVICT_POLICIES)
        if self._evict_policy not in EVICT_POLICIES:
            raise MXNetError(
                f"MXNET_SERVING_EVICT={self._evict_policy!r} must be "
                f"one of {EVICT_POLICIES}")
        # -- speculative decoding + chunked prefill ---------------------
        self._spec_k = spec_tokens if spec_tokens is not None else \
            _read_env_int("MXNET_SERVING_SPEC_TOKENS", lo=0)
        self._spec_k = int(self._spec_k)
        if self._spec_k < 0:
            raise MXNetError(
                f"spec_tokens {self._spec_k} must be >= 0")
        if proposer is None or isinstance(proposer, str):
            name = proposer if proposer is not None else \
                _read_env_str("MXNET_SERVING_PROPOSER",
                              choices=PROPOSERS)
            self._proposer_name = name
            self._proposer = make_proposer(name) if self._spec_k \
                else None
        else:  # a draft-LM / custom proposer instance slots in here
            if not callable(getattr(proposer, "propose", None)):
                raise MXNetError(
                    f"proposer {proposer!r} must expose "
                    f"propose(context, k) -> np.int32 tokens")
            self._proposer_name = type(proposer).__name__
            self._proposer = proposer
        self._chunk = prefill_chunk if prefill_chunk is not None else \
            _read_env_int("MXNET_SERVING_PREFILL_CHUNK", lo=0)
        self._chunk = int(self._chunk)
        if self._chunk < 0:
            raise MXNetError(
                f"prefill_chunk {self._chunk} must be >= 0")
        self._vocab = int(vocab_size)
        self._L = int(num_layers)
        self._H = int(num_heads)
        self._D = int(model.head_dim)

        self._kv_block = kv_block if kv_block is not None else \
            _read_env_int("MXNET_SERVING_KV_BLOCK")
        if int(self._kv_block) < 1:
            raise MXNetError(f"kv_block {self._kv_block} must be >= 1")
        self._kv_block = int(self._kv_block)
        if self._chunk and self._chunk % self._kv_block:
            raise MXNetError(
                f"MXNET_SERVING_PREFILL_CHUNK={self._chunk} must be a "
                f"multiple of kv_block {self._kv_block} — every chunk "
                f"after the first must start block-aligned for the "
                f"suffix-prefill continuation to be bit-identical to "
                f"monolithic prefill")
        self._max_streams = max_streams if max_streams is not None else \
            _read_env_int("MXNET_SERVING_MAX_STREAMS")
        if int(self._max_streams) < 1:
            raise MXNetError(
                f"max_streams {self._max_streams} must be >= 1")
        self._max_streams = int(self._max_streams)

        # -- model-parallel mesh (tp x pp) ------------------------------
        # loud at-construction validation, the MXNET_CKPT_* pattern:
        # a bad MXNET_SERVING_TP / MXNET_SERVING_PP / MXNET_SERVING_
        # DEVICES raises HERE, not three minutes into a warmup
        self._tp = int(tp) if tp is not None else \
            _read_env_int("MXNET_SERVING_TP")
        self._pp = int(pp) if pp is not None else \
            _read_env_int("MXNET_SERVING_PP")
        if self._tp < 1:
            raise MXNetError(
                f"MXNET_SERVING_TP={self._tp} must be >= 1")
        if self._pp < 1:
            raise MXNetError(
                f"MXNET_SERVING_PP={self._pp} must be >= 1")
        n_mesh = self._tp * self._pp
        # a feature needs a symbol, a pool or a state transfer of the
        # spec: what it does not list is refused here, by name, none
        # built and none failing silently
        if adapters is None and not model.lora_width:
            adapters = False  # the env's default: where carried
        for feature, asked, carried in (
                ("prefix_cache", self._prefix_on, suffix),
                ("prefill_chunk", self._chunk, suffix),
                ("spec_tokens", self._spec_k,
                 "verify" in model.phases and plain),
                (f"kv_dtype={self._kv_dtype!r}", True,
                 self._kv_dtype in model.kv_dtypes),
                (f"tp={self._tp}", self._tp > 1, model.partition_rules),
                (f"pp={self._pp}", self._pp > 1, model.partition_rules),
                ("adapters", adapters, model.lora_width)):
            if asked and not carried:
                raise MXNetError(
                    f"{feature} is not built for {model.name}"
                    + (": a layer's per-stream state lives in a slot, "
                       "which this feature would have to share, cut, "
                       "roll back, quantize or shard" if slots else
                       ": a windowed layer's pool gives back the pages "
                       "behind its window, which this feature would "
                       "have to share, continue from, roll back, "
                       "quantize or shard" if windowed else ""))
        if devices is None:
            devices = os.environ.get("MXNET_SERVING_DEVICES") or None
        if isinstance(devices, str):
            try:
                devices = [int(t) for t in devices.split(",")
                           if t.strip()]
            except ValueError:
                raise MXNetError(
                    f"MXNET_SERVING_DEVICES={devices!r} must be a "
                    f"comma-separated list of device ordinals")
        mesh_devs = None
        if devices is not None:
            ords = [int(d) for d in devices]
            all_devs = jax.devices()
            if len(ords) != n_mesh:
                raise MXNetError(
                    f"MXNET_SERVING_DEVICES lists {len(ords)} devices "
                    f"but the tp={self._tp} x pp={self._pp} mesh "
                    f"needs {n_mesh}")
            if len(set(ords)) != len(ords):
                raise MXNetError(
                    f"MXNET_SERVING_DEVICES={ords} repeats a device — "
                    f"each mesh slot needs its own chip")
            bad = [o for o in ords if o < 0 or o >= len(all_devs)]
            if bad:
                raise MXNetError(
                    f"MXNET_SERVING_DEVICES ordinals {bad} out of "
                    f"range — jax reports {len(all_devs)} devices")
            mesh_devs = [all_devs[o] for o in ords]
        elif n_mesh > 1:
            all_devs = jax.devices()
            if len(all_devs) < n_mesh:
                raise MXNetError(
                    f"tp={self._tp} x pp={self._pp} needs {n_mesh} "
                    f"devices; jax reports {len(all_devs)}")
            mesh_devs = list(all_devs[:n_mesh])

        # -- parameters onto the device / mesh --------------------------
        if ctx is None:
            from .context import current_context
            ctx = current_context()
        self._ctx = ctx
        # pool STORAGE dtype: the legacy ``dtype`` arg for fp32 (it
        # always meant the pool dtype), the kv_dtype mapping otherwise
        self._np_dtype = np.dtype(dtype) if self._kv_dtype == "fp32" \
            else kv_store_dtype
        self._mesh = None
        if n_mesh > 1:
            from .parallel import MeshPlan
            from .serving_mesh import MeshPrograms
            self._mesh = MeshPrograms(
                MeshPlan(mesh_devs, dp=1, tp=self._tp, pp=self._pp,
                         rules=model.partition_rules()),
                model, kv_block=self._kv_block, kv_dtype=self._kv_dtype,
                pool_dtype=self._np_dtype, seed=int(seed))
            # every feed lands replicated; pools/params carry their
            # own NamedShardings
            dev = self._mesh.replicated
        elif mesh_devs is not None:
            dev = mesh_devs[0]
        else:
            dev = ctx.jax_device()
        self._device = dev

        def to_dev(v):
            if isinstance(v, jax.Array):
                # already on a device: moved there (or kept, where it
                # is) without a trip through the host and, on the same
                # device, without a second copy of the weights
                return jax.device_put(v, dev)
            arr = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
            return jax.device_put(arr, dev)

        host_params = {k: v for k, v in params.items()}
        if model.positions is None:
            if max_len is None:
                raise MXNetError(
                    "a model spec without learned positions needs "
                    "max_len")
            self._max_len = int(max_len)
        else:
            if model.positions not in host_params:
                raise MXNetError(
                    f"params has no {model.positions!r} — the dense "
                    "keywords serve the transformer_lm family "
                    "(models/transformer.py); another family comes as "
                    "model=<spec>")
            pos_rows = int(host_params[model.positions].shape[0])
            self._max_len = int(max_len) if max_len is not None \
                else pos_rows
            if self._max_len > pos_rows:
                raise MXNetError(
                    f"max_len {self._max_len} exceeds the model's "
                    f"learned positions ({pos_rows} {model.positions} "
                    f"rows)")

        self._max_blocks_seq = blocks_for_tokens(self._max_len,
                                                 self._kv_block)
        if cache_blocks is None:
            cache_blocks = 1 + self._max_streams * self._max_blocks_seq
        if int(cache_blocks) < 2:
            raise MXNetError(f"cache_blocks {cache_blocks} must be >= 2")
        self._alloc = BlockAllocator(int(cache_blocks), self._kv_block)
        # the second kind of pages: a windowed layer's pools, their own
        # page ids.  A stream holds the blocks its window reaches — at
        # most the window's blocks and one (a window that starts inside
        # a block) — and one more while a step crosses a page boundary
        # before the block behind the window is given back (gauges
        # ``serving.window.cache_util`` and the family beside it)
        self._window = int(model.window) if windowed else 0
        self._walloc = BlockAllocator(
            1 + self._max_streams * (
                blocks_for_tokens(self._window, self._kv_block) + 2),
            self._kv_block, gauge_prefix="serving.window") \
            if windowed else None
        self._prefix = PrefixCache(self._alloc,
                                   policy=self._evict_policy) \
            if self._prefix_on else None
        self._prefix_dirty: List[bytes] = []  # queued salt drops

        # -- bucket ladders ---------------------------------------------
        self._decode_buckets = tuple(
            decode_buckets if decode_buckets is not None else
            _read_env_buckets("MXNET_SERVING_DECODE_BUCKETS",
                              bucket_ladder(self._max_streams)))
        self._cache_buckets = tuple(
            cache_buckets if cache_buckets is not None else
            _read_env_buckets("MXNET_SERVING_CACHE_BUCKETS",
                              bucket_ladder(self._max_blocks_seq)))
        pre_default = [b * self._kv_block
                       for b in bucket_ladder(self._max_blocks_seq)]
        self._prefill_buckets = tuple(
            prefill_buckets if prefill_buckets is not None else
            _read_env_buckets("MXNET_SERVING_PREFILL_BUCKETS",
                              pre_default))
        for pb in self._prefill_buckets:
            if pb % self._kv_block:
                raise MXNetError(
                    f"prefill bucket {pb} is not a multiple of "
                    f"kv_block {self._kv_block} (page-aligned prefill "
                    f"keeps ONE block table width per bucket)")
        for lad, nm in ((self._decode_buckets, "decode_buckets"),
                        (self._cache_buckets, "cache_buckets"),
                        (self._prefill_buckets, "prefill_buckets")):
            if any(b <= a for a, b in zip(lad, lad[1:])) or lad[0] < 1:
                raise MXNetError(f"bad {nm} ladder {lad}")
        # A ladder that doesn't cover the configured maxima would kill
        # the serving loop mid-flight (a _bucket miss poisons EVERY
        # outstanding future) — reject it here instead.
        if self._decode_buckets[-1] < self._max_streams:
            raise MXNetError(
                f"decode_buckets {self._decode_buckets} does not cover "
                f"max_streams {self._max_streams}")
        if self._cache_buckets[-1] < self._max_blocks_seq:
            raise MXNetError(
                f"cache_buckets {self._cache_buckets} does not cover "
                f"the {self._max_blocks_seq} pages a max_len "
                f"({self._max_len}) stream holds")
        if self._chunk and self._chunk > self._prefill_buckets[-1]:
            raise MXNetError(
                f"prefill_chunk {self._chunk} exceeds the largest "
                f"prefill bucket {self._prefill_buckets[-1]} — chunks "
                f"are bucketed through the prefill ladder")

        # -- paged LoRA adapters + per-tenant quotas ---------------------
        # (the multi-tenancy layer; mxnet_tpu/adapters.py)
        from . import adapters as _adapters
        if adapters is None:
            adapters = _adapters.adapters_enabled()
        if adapters is True:
            adapters = _adapters.pool_from_env(self._L, int(d_model),
                                               model.lora_width)
        elif adapters is False:
            adapters = None
        if adapters is not None \
                and not isinstance(adapters, _adapters.AdapterPool):
            raise MXNetError(
                f"adapters must be an AdapterPool, True (build from "
                f"MXNET_ADAPTER_* env), or None; got {adapters!r}")
        self._adapter_pool = adapters
        if self._adapter_pool is not None:
            if self._mesh is not None:
                raise MXNetError(
                    "paged LoRA adapters on a tp/pp-meshed engine are "
                    "not supported yet — the adapter slabs would need "
                    "the rules-table sharding the base weights get")
            pl = self._adapter_pool
            if pl.num_layers != self._L or pl.d_model != int(d_model) \
                    or pl.d_out != model.lora_width:
                raise MXNetError(
                    f"AdapterPool geometry (layers={pl.num_layers}, "
                    f"d_model={pl.d_model}, d_out={pl.d_out}) does not "
                    f"match the engine (layers={self._L}, d_model="
                    f"{int(d_model)}, d_out={model.lora_width})")
        self._lora = tuple(self._adapter_pool.rank_buckets) \
            if self._adapter_pool is not None else None
        if tenant_quota is None:
            tenant_quota = _adapters.quota_from_env()
        self._quota = tenant_quota
        # per-tenant fairness ledger (requests/tokens/shed), kept at
        # the same sites as the global counters
        self._tenants: Dict[str, Dict[str, float]] = {}
        # draft-LM proposers know their vocab; a draft that tokenizes
        # differently from the target would propose out-of-range ids
        if self._proposer is not None \
                and hasattr(self._proposer, "vocab_size") \
                and int(self._proposer.vocab_size) != int(vocab_size):
            raise MXNetError(
                f"draft_lm proposer vocab {self._proposer.vocab_size} "
                f"!= target vocab {int(vocab_size)} — draft and "
                f"target must share a tokenizer")

        # -- graphs + pools ---------------------------------------------
        kw = dict(kv_block=self._kv_block, kv_dtype=self._kv_dtype,
                  lora=self._lora)
        # a chunk is a suffix-prefill continuation, so chunked prefill
        # needs that graph even with the prefix cache off
        phases = ["decode", "prefill"]
        if self._prefix_on or self._chunk:
            phases.append("prefix_prefill")
        if self._spec_k:
            phases.append("verify")
        syms = {ph: model.symbol(ph, **kw) for ph in phases}
        self._gfn = {ph: build_graph_fn(s) for ph, s in syms.items()}
        # per-stream state the programs carry, by graph-argument name,
        # in the order the symbols hand it back, and each pool's kind
        # beside it: K/V pages ([k, v] or, quantized, [k, v, k_scale,
        # v_scale] a layer), slots, counters
        n_slots = 1 + self._max_streams
        layout = model.pools(
            int(cache_blocks), self._kv_block, n_slots, self._np_dtype,
            self._kv_dtype, **({"window_blocks": self._walloc.num_blocks}
                               if windowed else {}))
        self._pool_names = tuple(n for n, _, _, _ in layout)
        self._slot_alloc = SlotAllocator(self._max_streams) \
            if slots else None
        self._counters_at = self._pool_kinds.index("counters") \
            if "counters" in self._pool_kinds else None
        # the pools are donated to every program; stats() reads the
        # counters among them from another thread, under this lock
        self._pools_lock = threading.Lock()
        # the runtime tail of every program, after the pools: the slot
        # ids (a spec with slots), the windowed pools' table (a spec
        # with such pools), then per rank bucket the adapter
        # slabs + slot vector — RUNTIME args (like the pools), never
        # baked params: publish stays drain-free
        self._runtime_names = (("slots",) if slots else ()) + (
            ("window_table",) if windowed else ()) + tuple(
            f"adapter_{t}_r{rb}" for rb in self._lora or ()
            for t in ("a", "b", "slots"))
        feed = set(model.feeds) | set(self._pool_names) \
            | set(self._runtime_names)
        self._param_names = [n for n in syms["decode"].list_arguments()
                             if n not in feed]
        missing = [n for n in self._param_names if n not in host_params]
        if missing:
            raise MXNetError(f"params missing {missing} for the "
                             f"decode graph")
        if self._mesh is not None:
            # rules-resolved placement (tp output-dim shards, qkv rows
            # head-permuted, replicated sampler base_key rides along)
            self._params = self._mesh.shard_params(host_params)
        else:
            self._params = {n: to_dev(host_params[n])
                            for n in self._param_names}
        # pools a layer in self._pools (what a migration frame holds);
        # on a mesh the pools are STACKED (L, pages, ...) slabs
        # instead, sharded pp x tp
        self._pool_stride = sum(
            k != "counters" for k in self._pool_kinds) // self._L
        if self._mesh is not None:
            self._pools = self._mesh.init_pools(int(cache_blocks))
        else:
            filled = {}  # one host array per (shape, dtype, fill)
            pools = []
            for _, shape, dt, fill in layout:
                key = (tuple(shape), np.dtype(dt).name, fill)
                if key not in filled:
                    filled[key] = np.full(shape, fill, np.dtype(dt)) \
                        if fill else np.zeros(shape, np.dtype(dt))
                pools.append(jax.device_put(filled[key], dev))
            del filled
            self._pools = tuple(pools)

        sizes = [int(np.prod(np.shape(p))) * np.dtype(p.dtype).itemsize
                 for p in self._pools]
        # slot state apart from the K/V pages
        self._state_pool_bytes = sum(
            b for k, b in zip(self._pool_kinds, sizes)
            if k in ("slots", "slots_aux"))
        self._pool_bytes = sum(sizes) - self._state_pool_bytes
        profiler.set_gauge("serving.kv_pool_bytes", self._pool_bytes)
        profiler.set_gauge("serving.state_pool_bytes",
                           self._state_pool_bytes)
        # a latent spec: what its pool spends a token and layer (the
        # row in whole lane tiles) beside what the row needs
        self._latent_row = getattr(model, "latent_row", None)
        self._latent_bytes = {}
        if self._latent_row:
            need, lanes = self._latent_row
            item = np.dtype(self._np_dtype).itemsize
            self._latent_bytes = {
                "cache_bytes_per_token": lanes * item,
                "cache_bytes_needed_per_token": need * item}
            for k, v in self._latent_bytes.items():
                profiler.set_gauge(f"mla.{k}", v)
        # a spec whose layers keep a tail beside their pages (cca): what
        # a token leaves in a layer's pages, what a stream's slots hold
        tail_row = getattr(model, "tail_row", None)
        if tail_row:
            profiler.set_gauge(
                "cca.cache_bytes_per_token",
                2 * self._H * self._D * np.dtype(self._np_dtype).itemsize)
            profiler.set_gauge("cca.tail_bytes_per_stream", 4 * tail_row[1])
        # the layers whose prompt kernels stop at the prompt's last row,
        # how many of each (window, latent); none for a family without
        self._prompt_layers = collections.Counter(
            getattr(model, "prompt_attention", tuple)())
        self._cow_fn = None  # lazily-jitted copy-on-write page copy

        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        self._base_key = jax.random.PRNGKey(int(seed))
        self._graph_key = jax.random.PRNGKey(0)
        self._temperature = float(temperature)
        self._eos = eos_id

        self._exe_cache: Dict[tuple, Any] = {}
        profiler.hold_programs(self)  # weakly: for program_scopes()
        self._compile_lock = threading.Lock()
        self.compiles: Dict[tuple, int] = {}
        # per-executable FLOPs (XLA cost analysis, cached at compile)
        # feeding each stream's cost record's flops_est
        self._exe_flops: Dict[tuple, float] = {}
        self._metrics = profiler.MetricsRegistry()
        self._cost_agg = _slo.CostAggregator()
        self._slo = _slo.get_tracker()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: List[_Stream] = []
        self._active: List[_Stream] = []
        # queued KV-page imports (meta, slabs, future): spliced into
        # the pool ON the scheduler thread (pools are donated jax
        # buffers — only the loop may touch them)
        self._imports: List[tuple] = []
        self._admitting: Optional[_Stream] = None
        self._prefilling: Optional[_Stream] = None  # mid-chunked-prefill
        # programs dispatched and not fetched, oldest first (the loop
        # thread's alone); other threads' requests to have them fetched
        # and booked before they go on: (reason, Event)
        self._inflight: Deque[_Flight] = collections.deque()
        self._asks: List[tuple] = []
        self._booking = False  # inside _fetch_one (callbacks run there)
        self._t_booked = 0.0   # when the last decode step was booked
        # fills the first-token arguments of ``_feed_exe`` no row reads
        self._no_first = jax.device_put(np.zeros(1, np.int32), dev)
        self._accepting = True
        self._reject = None  # drain(): submit's refusal message
        self._alive = True
        self._next_sid = 0
        # accepted-but-unresolved futures — the inflight() snapshot
        # the fleet router reads (see InferenceEngine.inflight)
        self._owned: set = set()

        if prewarm:
            self.warmup()

        # ops surface (MXNET_METRICS_PORT-gated) + /statusz section
        profiler.maybe_start_metrics_server()
        profiler.register_statusz("engine", self.stats)

        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name="mxnet_tpu-serving-decode")
        self._thread.start()

        # synthetic canary prober (MXNET_CANARY_INTERVAL-gated): a
        # known-cost probe through the full admission→prefill→decode
        # path, excluded from serving.requests, feeding slo.canary_*
        self._canary = None
        interval = _slo.canary_interval_s()
        if interval > 0:
            probe_prompt = _slo.canary_prompt(int(vocab_size))
            probe_new = min(_slo.canary_tokens(),
                            self._max_len - probe_prompt.size)

            def _probe(trace):
                self.submit(probe_prompt, max_new_tokens=probe_new,
                            trace=trace, canary=True).result(timeout=60)

            self._canary = _slo.CanaryProber(
                _probe, interval, tracker=self._slo, name="engine",
                book_latency=False)  # the engine path books real
            # TTFT/TPT for canary streams; the prober adds avail only

    # ------------------------------------------------------------------
    # client surface
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, temperature=None,
               eos_id=None, seed=None, trace=None,
               slo_class="interactive", canary=False,
               prefill_only=False, tenant=None,
               adapter=None, return_state=False) -> Future:
        """Enqueue one generation; the Future resolves to the np.int32
        array of generated token ids (eos, when hit, is included).

        ``prefill_only=True`` is the disaggregated-serving prefill
        phase: the stream runs admission + (chunked/prefix-shared)
        prefill, samples its FIRST token, and then — instead of
        joining the decode batch — its KV pages are gathered off the
        pool and the Future resolves to a migration payload dict
        (``meta`` + ``kv_arrays``) for :meth:`import_stream` on a
        decode-role replica.  Sampling stays keyed by (engine seed,
        stream seed, position), so the handoff is bit-invisible.

        ``slo_class`` ("interactive"/"batch", loudly validated) keys
        the request's SLO objectives and its cost-record aggregation;
        ``canary=True`` marks a synthetic probe — it rides the normal
        path but is EXCLUDED from the ``requests`` counter.

        ``seed`` overrides the stream's sampling seed (default: the
        engine-local stream id).  Sampling is keyed by (engine seed,
        stream seed, position), so two engines constructed with the
        same weights and engine ``seed`` produce BIT-IDENTICAL tokens
        for the same (prompt, seed) — the property the fleet router's
        exactly-once retry of a dead replica's requests rests on.

        ``trace``: optional :class:`profiler.TraceContext` — the
        stream's queue wait, prefill, and every decode-step batch it
        rides in become child spans of it (propagated over the fleet
        wire; purely an observer).

        ``return_state=True`` (a model with slot state only): the
        Future resolves to ``{"tokens": ..., "state": {pool name:
        array}}`` — beside the tokens, what the stream's slot holds in
        every ``layer<i>_state`` pool when it retires, read off the
        device before the slot is freed: per kda layer the (heads, d_v,
        d_k) float32 state after prompt + all generated tokens but the
        last (sampled, never fed).  For holding the engine to a
        reference on the state itself, which logits hardly show."""
        _slo.check_class(slo_class)
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise MXNetError(
                f"prompt must be a non-empty 1-D token array; got "
                f"shape {prompt.shape}")
        prompt = prompt.astype(np.int32)
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise MXNetError(f"max_new_tokens {max_new} must be >= 1")
        total = prompt.size + max_new
        if total > self._max_len:
            raise MXNetError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"= {total} exceeds max_len {self._max_len}")
        if prompt.size > self._prefill_buckets[-1] and not self._chunk:
            raise MXNetError(
                f"prompt of {prompt.size} tokens exceeds the largest "
                f"prefill bucket {self._prefill_buckets[-1]} (enable "
                f"MXNET_SERVING_PREFILL_CHUNK to prefill it in "
                f"chunks)")
        need = self._blocks_for(total, self._kv_block)
        if need > self._alloc.capacity:
            raise MXNetError(
                f"request needs {need} cache blocks but the pool only "
                f"has {self._alloc.capacity}")
        if prefill_only and (self._slot_alloc is not None
                             or self._walloc is not None):
            raise MXNetError(
                f"prefill_only page export is not built for "
                f"{self._spec.name}: a stream's state is pages AND a "
                f"slot or a windowed pool's pages, and only whole "
                f"tables of ordinary pages have a wire format")
        if prefill_only and self._latent_row:
            raise MXNetError(
                f"prefill_only page export is not built for "
                f"{self._spec.name}: {_NO_LATENT_FRAME}")
        if return_state and (self._slot_alloc is None or prefill_only):
            raise MXNetError(
                "return_state reads a stream's slot at retirement: the "
                "model must have layers with slot state (kda), and the "
                "stream must decode here (not prefill_only)")
        if prefill_only and self._mesh is not None:
            raise MXNetError(
                "prefill_only export from a tp/pp-meshed engine is "
                "not supported yet (page slabs are per-shard)")
        # -- tenancy: quota admission + adapter reference ----------------
        # (typed, per-tenant, BEFORE the stream takes any engine state)
        if adapter is not None and self._adapter_pool is None:
            raise MXNetError(
                f"request names adapter {adapter!r} but the engine "
                f"has no adapter pool (MXNET_ADAPTER_ENABLE=1 or "
                f"adapters=AdapterPool(...))")
        tenant = str(tenant) if tenant is not None else None
        if self._quota is not None and tenant is not None \
                and not canary:
            try:
                self._quota.charge(tenant, prompt.size + max_new)
            except QuotaExceededError:
                self._count("shed")
                self._count("shed_tenant_quota")
                self._tenant_count(tenant, "shed")
                raise
        ad_bucket = ad_slot = None
        if adapter is not None:
            ad_bucket, ad_slot = self._adapter_pool.acquire(adapter)
        temp = self._temperature if temperature is None \
            else float(temperature)
        eos = self._eos if eos_id is None else eos_id
        fut: Future = Future()
        try:
            with self._cond:
                if not self._accepting:
                    raise EngineClosedError(
                        self._reject or "DecodeEngine is closed")
                s = _Stream(self._next_sid, prompt, max_new, temp, eos,
                            fut,
                            seed=(self._next_sid + 1 if seed is None
                                  else int(seed)), trace=trace,
                            slo_class=slo_class, canary=canary,
                            tenant=tenant, adapter=adapter)
                s.adapter_bucket, s.adapter_slot = ad_bucket, ad_slot
                s.migrate = bool(prefill_only)
                s.keep_state = bool(return_state)
                self._next_sid += 1
                self._pending.append(s)
                self._owned.add(fut)
                self._cond.notify_all()
        except BaseException:
            if adapter is not None:  # refused: hand the ref back
                self._adapter_pool.release(adapter)
            if self._quota is not None and tenant is not None \
                    and not canary:
                self._quota.refund(tenant, prompt.size + max_new)
            raise
        fut.add_done_callback(self._disown)
        if not canary:  # probes keep request counters honest
            self._count("requests")
            if tenant is not None:
                self._tenant_count(tenant, "requests")
        return fut

    def _disown(self, fut):
        with self._lock:
            self._owned.discard(fut)

    def inflight(self) -> int:
        """Accepted-but-unresolved generation count (pending + admitted
        + mid-prefill).  Poisoned futures leave the count when their
        exception lands, so a drained/dead engine reads 0."""
        with self._lock:
            return len(self._owned)

    def drain(self, timeout: float = 30.0) -> int:
        """Stop accepting new generations and wait for active streams
        to retire.  Returns the unresolved count at the deadline (0 =
        quiesced).  ``resume()`` re-opens admission."""
        with self._cond:
            if self._accepting:
                self._reject = ("DecodeEngine is draining — not "
                                "accepting requests (weight swap in "
                                "progress)")
                self._accepting = False
        deadline = time.perf_counter() + float(timeout)
        while self.inflight() and time.perf_counter() < deadline:
            time.sleep(0.002)
        return self.inflight()

    def resume(self):
        """Re-open admission after :meth:`drain`."""
        with self._cond:
            if not self._alive:
                raise MXNetError("cannot resume a closed DecodeEngine")
            self._reject = None
            self._accepting = True
            self._cond.notify_all()

    def swap_params(self, params):
        """Live weight swap.  Decode executables take the parameters as
        RUNTIME arguments (nothing is baked in), so installing new
        weights is one atomic reference swap — no recompile, and the
        bucketed executable cache stays warm.  Takes effect at the next
        prefill/decode step; the fleet drains first anyway so no stream
        straddles two weight versions mid-generation."""
        import jax

        host = {k: v for k, v in params.items()}
        missing = [n for n in self._param_names if n not in host]
        if missing:
            raise MXNetError(f"swap_params: params missing {missing}")
        self._settle("swap_params")  # no token in flight across a swap
        if self._mesh is not None:
            clean = {}
            for n in self._param_names:
                v = host[n]
                arr = np.asarray(v.asnumpy() if hasattr(v, "asnumpy")
                                 else v)
                want = self._mesh.host_shape(n)
                if want is not None and tuple(arr.shape) != want:
                    raise MXNetError(
                        f"swap_params: param {n!r} shape {arr.shape} "
                        f"!= serving shape {want}")
                clean[n] = arr
            # re-shards through the rules table (qkv head permutation
            # included) — still one atomic reference swap
            self._params = self._mesh.shard_params(clean)
            return
        new = {}
        for n in self._param_names:
            v = host[n]
            arr = np.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v)
            old = self._params[n]
            if tuple(arr.shape) != tuple(old.shape):
                raise MXNetError(
                    f"swap_params: param {n!r} shape {arr.shape} != "
                    f"serving shape {tuple(old.shape)}")
            new[n] = jax.device_put(arr.astype(old.dtype, copy=False),
                                    self._device)
        self._params = new

    def get_params(self):
        """Host snapshot of the served weights — the rollback anchor a
        failed swap restores from."""
        if self._mesh is not None:
            # checkpoint layout (qkv rows un-permuted, shards gathered)
            return self._mesh.unshard_params(self._params)
        return {n: np.asarray(v) for n, v in self._params.items()}

    def publish_adapter(self, name, a, b, alpha=None) -> int:
        """Install a LoRA adapter under ``name`` — HOT.  The slabs are
        runtime executable arguments (like the base weights), so the
        publish is a functional slab update plus one atomic reference
        swap inside the pool: no drain, no recompile, and in-flight
        streams keep reading the rows their slot ids pin (eviction
        only ever touches refcount-0 slots).  Returns the slot."""
        if self._adapter_pool is None:
            raise MXNetError(
                "publish_adapter: this engine has no adapter pool "
                "(construct with adapters=..., or set "
                "MXNET_ADAPTER_ENABLE=1)")
        slot = self._adapter_pool.publish(name, a, b, alpha=alpha)
        # a retire-then-republish binds NEW weights to the name: prefix
        # chains prefilled under the old ones (the name is the cache
        # salt) must stop being matchable.  Queued: only the scheduler
        # thread may touch the radix tree (it attaches unlocked).
        self._queue_prefix_invalidate(name)
        self._count("adapter_publishes")
        return slot

    def retire_adapter(self, name) -> bool:
        """Retire an adapter by name — also hot.  If streams still
        hold references the retire is DEFERRED: the name stops being
        acquirable immediately, and the slot frees when the last
        holder retires.  Returns True if the slot freed now."""
        if self._adapter_pool is None:
            raise MXNetError(
                "retire_adapter: this engine has no adapter pool")
        freed = self._adapter_pool.retire(name)
        # reclaim the retiring adapter's parked prefix chains (nothing
        # can match them again: acquire-by-name is gone)
        self._queue_prefix_invalidate(name)
        self._count("adapter_retires")
        return freed

    def _queue_prefix_invalidate(self, name) -> None:
        """Queue an adapter-salt prefix invalidation for the scheduler
        thread (which owns the radix tree).  Applied at the next
        admission pass — before any request submitted after this call
        can be admitted, so a post-(re)publish stream never matches a
        chain prefilled under the name's old weights."""
        if self._prefix is None:
            return
        with self._cond:
            self._prefix_dirty.append(str(name).encode("utf-8"))
            self._cond.notify_all()

    def generate(self, prompt, max_new_tokens=32, **kw) -> np.ndarray:
        """Synchronous convenience: ``submit(...).result()``."""
        return self.submit(prompt, max_new_tokens, **kw).result()

    def warmup(self):
        """Compile EVERY prefill bucket and every (batch, cache)
        decode combination now — a lazily-compiled executable inside
        the serving loop stalls every active stream for the compile
        (seconds), which is exactly the p99 a decode tier cares
        about."""
        for tp in self._prefill_buckets:
            self._exe("prefill", tp)
        for bb in self._decode_buckets:
            self._feed_exe(bb)
            for mb in self._cache_buckets:
                self._exe("decode", bb, mb)
                if self._spec_k:
                    self._exe("verify", bb, mb, self._spec_k + 1)
        if "prefix_prefill" in self._gfn:
            # suffix-prefill matrix (prefix-cache hits AND prefill
            # chunks): a table bucket narrower than the suffix itself
            # can never occur (the table covers prefix + suffix
            # pages), so those combinations are skipped
            for tp in self._prefill_buckets:
                for mb in self._cache_buckets:
                    if mb * self._kv_block >= tp:
                        self._exe("prefix_prefill", tp, mb)

    def _count(self, name, value=1.0):
        self._metrics.inc(name, value)
        profiler.inc_counter(f"serving.{name}", value)

    def _tenant_count(self, tenant, name, value=1):
        """Per-tenant fairness counters (requests/tokens/shed) — same
        increment sites as the engine-global counters so the sums
        reconcile."""
        if tenant is None:
            return
        with self._lock:
            d = self._tenants.setdefault(tenant, {})
            d[name] = d.get(name, 0) + value

    # ------------------------------------------------------------------
    def reset_stats(self):
        """Zero the engine-local counters/histograms so the next
        :meth:`stats` covers only work from this point on (benchmarks
        isolate sweep points; lifetime percentiles blend loads).  What
        is in flight is fetched and booked first: a step counts whole
        on one side of the reset."""
        self._settle("reset_stats")
        self._metrics.reset()
        self._cost_agg.reset()
        with self._lock:
            self._tenants.clear()
        if self._prefix is not None:
            self._prefix.reset_counters()
        if self._counters_at is not None:
            import jax

            at = self._counters_at
            with self._pools_lock:
                old = self._pools[at]
                zero = jax.device_put(np.zeros(old.shape, old.dtype),
                                      self._device)
                self._pools = self._pools[:at] + (zero,) \
                    + self._pools[at + 1:]

    def _state_stats(self, c) -> dict:
        """Slots and routing: the second kind of per-stream state, and
        what the expert layers did with the decode steps' tokens (``c``:
        the engine's counters).  The
        routing counters live in a device array the decode step carries
        and are read HERE only (under the lock the dispatch holds: the
        array is donated to the next step)."""
        from .ops.hybrid import MOE_COUNTERS

        num, live = (0, 0) if self._slot_alloc is None else \
            (self._slot_alloc.num_slots, self._slot_alloc.live)
        out = {"state_slots": num, "state_slots_live": live,
               "state_pool_bytes": self._state_pool_bytes}
        out.update({f"mla_{k}": v for k, v in self._latent_bytes.items()})
        # the windowed pools: pages there are, pages held now, and over
        # the decode steps the pages their rows held beside the pages
        # the same rows hold in the ordinary pools (what a windowed
        # layer would hold with no window): 1.0 = nothing given back
        wa = self._walloc
        out.update(
            window_pages=wa.capacity if wa else 0,
            window_pages_live=wa.used_blocks if wa else 0,
            window_pages_held_share=(
                c.get("window_page_steps", 0) / c["page_steps"]
                if wa and c.get("page_steps") else None),
            **{k: int(c.get(k, 0)) for k in (
                "window_pages_released", "window_context_tokens",
                "window_prefill_pairs")})
        if self._counters_at is None:
            out.update({k: 0 for k in MOE_COUNTERS})
        else:
            with self._pools_lock:
                c = np.asarray(self._pools[self._counters_at])
            out.update({k: int(v) for k, v in zip(MOE_COUNTERS, c)})
        return out

    def stats(self) -> dict:
        summ = self._metrics.summary()
        c = summ["counters"]
        out = {k: int(c.get(k, 0)) for k in
               ("requests", "generations", "tokens", "prefill_tokens",
                "preempted", "prefills", "steps", "stream_steps",
                "prefill_chunks", "spec_steps", "spec_proposed",
                "spec_accepted", "spec_pages_rolled_back", "d2h_syncs",
                "d2h_syncs_saved", "context_tokens", "prefill_pairs",
                "prefill_bucket_tokens", "prefill_tiles_walked",
                "prefill_tiles_skipped", "prefill_tiles_masked",
                "prefill_scores_computed", "prefill_scores_needed",
                "steps_run_ahead",
                "run_ahead_drains", "overshoot_row_steps",
                "prefill_first_deferred")}
        # the share of the prefill programs' rows that held a token:
        # what the ladder's padding leaves of them
        out["prefill_bucket_fill"] = round(
            out["prefill_tokens"] / out["prefill_bucket_tokens"], 4) \
            if out["prefill_bucket_tokens"] else 0.0
        profiler.set_gauge("serving.prefill_bucket_fill",
                           out["prefill_bucket_fill"])
        # the share of the prompt kernels' bucket tiles they did not
        # walk for knowing the prompt's length
        tiles = out["prefill_tiles_walked"] + out["prefill_tiles_skipped"]
        out["prefill_tiles_skipped_share"] = round(
            out["prefill_tiles_skipped"] / tiles, 4) if tiles else 0.0
        profiler.set_gauge("serving.prefill_tiles_skipped_share",
                           out["prefill_tiles_skipped_share"])
        # the score elements those kernels' schedule computed over the
        # pairs the prompts' bands hold: 1.0 = nothing but the need
        out["prefill_scores_computed_over_needed"] = round(
            out["prefill_scores_computed"] / out["prefill_scores_needed"],
            4) if out["prefill_scores_needed"] else 0.0
        profiler.set_gauge("serving.prefill_scores_computed_over_needed",
                           out["prefill_scores_computed_over_needed"])
        # how the loop ran: the share of decode programs dispatched
        # while an earlier program's tokens were still unread, and why
        # it fetched everything before going on, when it did
        out["run_ahead_share"] = round(
            out["steps_run_ahead"] / out["steps"], 4) \
            if out["steps"] else 0.0
        profiler.set_gauge("serving.run_ahead_share",
                           out["run_ahead_share"])
        out["run_ahead_drain_reasons"] = {
            k[len("run_ahead.drain."):]: int(v) for k, v in c.items()
            if k.startswith("run_ahead.drain.")}
        # speculative-decoding headline ratios: how much of what the
        # proposer offered the target model verified, and how many
        # tokens ONE target-model evaluation of one stream commits
        # (1.0 = no speculation; up to spec_tokens + 1)
        out["accepted_token_rate"] = round(
            out["spec_accepted"] / out["spec_proposed"], 4) \
            if out["spec_proposed"] else 0.0
        out["tokens_per_step"] = round(
            out["tokens"] / out["stream_steps"], 4) \
            if out["stream_steps"] else 0.0
        out["spec_tokens"] = self._spec_k
        out["proposer"] = self._proposer_name if self._spec_k else None
        out["prefill_chunk"] = self._chunk
        tpt = summ["histograms"].get("time_per_token_ms")
        out["p50_ms"] = tpt["p50"] if tpt else None
        out["p90_ms"] = tpt["p90"] if tpt else None
        out["p99_ms"] = tpt["p99"] if tpt else None
        ttft = summ["histograms"].get("ttft_ms")
        out["ttft_p50_ms"] = ttft["p50"] if ttft else None
        for split in ("ttft_hit_ms", "ttft_miss_ms"):
            h = summ["histograms"].get(split)
            out[split.replace("_ms", "_p50_ms")] = h["p50"] if h \
                else None
        out["tokens_per_s"] = summ["rates"].get("tokens", 0.0)
        out["cache_util"] = self._alloc.utilization()
        out["cache_blocks_free"] = self._alloc.free_blocks
        out["cache_blocks_cached"] = self._alloc.parked_blocks
        out["shared_blocks"] = self._alloc.shared_blocks
        out["kv_dtype"] = self._kv_dtype
        out.update(self._state_stats(c))
        out["prefix_cache"] = int(self._prefix_on)
        if self._prefix is not None:
            out.update(self._prefix.stats())
            admissions = out["prefills"] + self._prefix.full_hits
            out["prefix_hit_rate"] = round(
                self._prefix.hits / admissions, 4) if admissions \
                else 0.0
        with self._lock:
            out["active_streams"] = len(self._active)
            out["pending"] = len(self._pending)
        out["compiles"] = {str(k): v for k, v in self.compiles.items()}
        # mesh shape + per-device pool bytes: what fleet_top / statusz
        # show for a sharded replica (tp=pp=1 reads honestly too)
        out["mesh"] = self._mesh.describe() if self._mesh is not None \
            else {"tp": 1, "pp": 1, "devices": [str(self._device)],
                  "sharded": {}}
        out["pool_bytes_per_device"] = \
            self._mesh.pool_bytes_per_device(self._pools) \
            if self._mesh is not None else self._pool_bytes
        out["decode_buckets"] = list(self._decode_buckets)
        out["cache_buckets"] = list(self._cache_buckets)
        out["prefill_buckets"] = list(self._prefill_buckets)
        out["kv_block"] = self._kv_block
        out["latency_breakdown"] = _phase_breakdown(
            summ, {"queue_wait": "queue_wait_ms",
                   "prefill": "prefill_ms",
                   "decode": "time_per_token_ms",
                   "ttft": "ttft_ms",
                   "ttft_hit": "ttft_hit_ms",
                   "ttft_miss": "ttft_miss_ms"})
        # per-class cost attribution (retired streams only) + the
        # FLOP rate the tenant-quota layer will meter against
        out["cost_by_class"] = self._cost_agg.by_class()
        out["cost_flops_per_s"] = round(
            summ["rates"].get("cost_flops", 0.0), 3)
        # disaggregated serving: KV-page migration traffic.  The _out
        # counters and their cost-record mirrors increment at the same
        # site, so sum(records) == these — same conservation contract
        # as tokens/cow_copies.
        out["migrations_out"] = int(c.get("migrations_out", 0))
        out["migrations_in"] = int(c.get("migrations_in", 0))
        out["migration_bytes"] = int(c.get("migration_bytes", 0))
        out["migration_ms"] = round(c.get("migration_ms", 0.0), 6)
        out["migrations_per_s"] = round(
            summ["rates"].get("migrations_out", 0.0)
            + summ["rates"].get("migrations_in", 0.0), 4)
        # multi-tenancy: fairness counters per tenant (requests /
        # tokens / shed at the same sites as the globals), quota
        # balances, and retired-stream cost attribution by tenant
        out["shed"] = int(c.get("shed", 0))
        out["shed_tenant_quota"] = int(c.get("shed_tenant_quota", 0))
        with self._lock:
            out["tenants"] = {t: dict(d)
                              for t, d in self._tenants.items()}
        if self._quota is not None:
            for t, q in self._quota.stats().items():
                out["tenants"].setdefault(t, {}).update(q)
        out["cost_by_tenant"] = self._cost_agg.by_tenant()
        if self._adapter_pool is not None:
            out["adapters"] = self._adapter_pool.stats()
            out["adapter_rank_buckets"] = list(self._lora or ())
        return out

    def cost_records(self) -> List[dict]:
        """The retained tail of per-stream cost records (newest last):
        one dict per retired stream, keyed by ``slo.COST_FIELDS`` plus
        sid/slo_class/canary/wall_s — what the conservation test sums
        against the engine counters."""
        return list(self._cost_agg.records)

    def executable_text(self, key) -> str:
        """Compiled HLO text of one cached executable — ``key`` as in
        :attr:`compiles`, e.g. ``("decode", 8, 32)``.  What
        chip_smoke.py reads to prove the paged kernel (a
        ``tpu_custom_call``) is in the decode step on the chip — the
        engine's twin of ``Module.fused_hlo_text``."""
        return self._exe_cache[key].as_text()

    def _programs_held(self) -> dict:
        return dict(self._exe_cache)

    def program_scopes(self) -> dict:
        """{program name as a device trace's ``XLA Modules`` carry it
        (``jit_prefill_t1024``, ``jit_step_decode_b48x64``,
        ``jit_next_tokens_b48``): {instruction: record}} — every
        operation of every executable this engine holds under the name
        the model's symbol gave it (``hlo.scope_table`` lists the
        record's fields).  Each executable's text is read and parsed
        when this is first asked, once; an engine nobody asks reads
        none.  ``profiler.program_scopes()`` is the same over every
        holder in the process."""
        return profiler.holder_scopes(self)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def close(self, timeout: float = 30.0):
        """Stop accepting work and fail every outstanding generation
        with :class:`EngineClosedError` at the next step boundary —
        in-flight decodes never strand their futures."""
        canary = getattr(self, "_canary", None)
        if canary is not None:  # stop probing BEFORE the door shuts
            canary.stop()
            self._canary = None
        with self._cond:
            if not self._alive:
                return
            self._accepting = False
            self._alive = False
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            # Join timed out mid-step (e.g. a lazy compile): the loop
            # thread still owns _active and the allocator — failing
            # outstanding futures here would race it.  Its finally
            # clause poisons them at the step boundary instead.
            return
        self._fail_outstanding(EngineClosedError("DecodeEngine closed"))
        # the /statusz section held the engine — weights and pools —
        # alive after close; a closed engine has nothing to report
        profiler.unregister_statusz("engine", self.stats)
        # what it ran stays nameable in a trace taken while it did
        profiler.retire_programs(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close(timeout=1.0)
        except Exception:
            pass

    def _fail_outstanding(self, exc):
        with self._lock:
            streams = self._pending + self._active
            # a stream popped for admission but not yet active (its
            # prefill raised) must not strand its caller
            if self._admitting is not None:
                if self._admitting not in streams:
                    streams.append(self._admitting)
                self._admitting = None
            # a stream mid-chunked-prefill is in neither list either
            if self._prefilling is not None:
                if self._prefilling not in streams:
                    streams.append(self._prefilling)
                self._prefilling = None
            # a stream whose last tokens are in flight is in no list
            # but its program's record
            for rec in self._inflight:
                streams.extend(s for s in rec.streams if s not in streams
                               and not s.future.done())
            self._inflight.clear()
            self._pending, self._active = [], []
            imports, self._imports = self._imports, []
            asks, self._asks = self._asks, []
        for _, settled in asks:  # nothing is in flight any more
            settled.set()
        for item in imports:  # queued page imports never spliced
            fut = item[2]
            if fut.set_running_or_notify_cancel():
                fut.set_exception(exc)
        for s in streams:
            if s.blocks:
                self._release_pages(s.blocks)
                s.blocks = []
            self._release_window(s)
            self._release_slot(s)
            self._release_adapter(s)
            if s.future.set_running_or_notify_cancel():
                s.future.set_exception(exc)

    # ------------------------------------------------------------------
    # executables
    # ------------------------------------------------------------------
    def _bucket(self, ladder, n, what):
        for b in ladder:
            if b >= n:
                return b
        raise MXNetError(f"{what} {n} exceeds ladder {ladder}")

    def _spec_of(self, tree):
        """AOT input specs for a params/pools pytree — on a mesh the
        spec carries each leaf's NamedSharding so the lowered
        executable bakes the shard_map placement in."""
        import jax

        def one(a):
            if self._mesh is not None:
                return jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                            sharding=a.sharding)
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype)

        return jax.tree_util.tree_map(one, tree)

    def _arg_spec(self, shape, dtype):
        """Spec of one scheduler feed (tokens/table/temps/...): small
        host arrays, replicated across the mesh when one exists."""
        import jax

        if self._mesh is not None:
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=self._device)
        return jax.ShapeDtypeStruct(shape, dtype)

    # phase -> (program name, compile span's suffix, what the key's
    # tail counts — the span's args —, the arguments before the runtime
    # tail, by the names they carry in the program's text)
    _PROGRAMS = {
        "decode": ("step_decode_b{}x{}", "decode.b{}x{}",
                   ("batch", "blocks"),
                   "params tokens positions lengths table temps seeds "
                   "steps pools"),
        "verify": ("step_verify_b{}x{}w{}", "verify.b{}x{}w{}",
                   ("batch", "blocks", "window"),
                   "params tokens positions start lengths table temps "
                   "seeds steps0 pools"),
        "prefill": ("prefill_t{}", "prefill.t{}", ("tokens",),
                    "params tokens positions lengths table temps seeds "
                    "steps pools"),
        "prefix_prefill": ("prefill_suffix_t{}x{}", "prefix_prefill.t{}x{}",
                           ("tokens", "blocks"),
                           "params tokens positions start lengths table "
                           "temps seeds steps pools"),
    }

    def _exe(self, phase: str, *dims):
        """The compiled program of one phase at one bucket, built once:
        ``(params, tokens, positions, [start,] lengths, table, temps,
        seeds, steps, pools, *runtime) -> (sampled, pools)`` with the
        pools donated.  ``("decode", bb, mb)`` advances ``bb`` streams a
        token; ``("verify", bb, mb, W)`` scores W = 1 + spec_tokens
        queries a stream (keyed apart from the plain step, which stays
        the zero-draft fast path); ``("prefill", tp)`` runs a prompt
        padded to ``tp``; ``("prefix_prefill", tp, mb)`` a suffix padded
        to ``tp`` over a table of ``mb`` pages (prefix + suffix)."""
        key = (phase,) + dims
        exe = self._exe_cache.get(key)
        if exe is not None:
            return exe
        with self._compile_lock:
            exe = self._exe_cache.get(key)
            if exe is not None:
                return exe
            import inspect

            import jax
            import jax.numpy as jnp

            from .speculative import verify_sample

            name, span, counted, named = self._PROGRAMS[phase]
            named = named.split()
            dim = dict(zip(counted, dims))
            # feeds are (rows, tokens) over a table of mb pages: a
            # prompt is one row, its table as wide as its bucket
            rows = dim.get("batch", 1)
            toks = dim.get("tokens") or dim.get("window", 1)
            mb = dim.get("blocks") or toks // self._kv_block
            gfn, gkey, base = self._gfn[phase], self._graph_key, \
                self._base_key

            def program(*flat):
                (params, tokens, positions, *start, lengths, table, temps,
                 seeds, steps, pools) = flat[:len(named)]
                args = dict(params)
                args.update(data=tokens, positions=positions,
                            lengths=lengths, block_table=table)
                if start:
                    args["start"], = start
                args.update(zip(self._pool_names, pools))
                args.update(zip(self._runtime_names, flat[len(named):]))
                outs, _ = gfn(args, {}, gkey, False)
                logits = outs[0]
                if phase == "verify":
                    return verify_sample(base, logits, tokens,
                                         lengths - start[0], temps, seeds,
                                         steps), tuple(outs[1:])
                if logits.shape[1] == 1:
                    # a decode step's row; a prefill that computed the
                    # head at each prompt's last row alone
                    last = logits[:, 0, :]
                else:  # the last real row of the prompt (of the suffix)
                    last = logits[
                        jnp.arange(logits.shape[0]),
                        (lengths - start[0] if start else lengths) - 1]
                return sample_tokens(base, last, temps, seeds, steps), \
                    tuple(outs[1:])

            # jax names a program's parameters by this signature
            P = inspect.Parameter
            program.__signature__ = inspect.Signature(
                [P(n, P.POSITIONAL_OR_KEYWORD) for n in named]
                + [P("extra", P.VAR_POSITIONAL)])
            if self._mesh is not None:
                program = getattr(self._mesh, f"{phase}_step")()

            i32 = np.dtype(np.int32)
            mat = self._arg_spec((rows, toks), i32)
            shaped = {"params": self._spec_of(self._params),
                      "tokens": mat, "positions": mat,
                      "table": self._arg_spec((rows, mb), i32),
                      "temps": self._arg_spec((rows,),
                                              np.dtype(np.float32)),
                      "pools": self._spec_of(self._pools)}
            row = self._arg_spec((rows,), i32)  # every other: one a row
            specs = tuple(shaped.get(n, row) for n in named) \
                + self._runtime_specs(rows, mb)
            # the name the program carries in a device trace
            # (``jit_step_decode_b48x64``, ``jit_prefill_t1024``)
            program.__name__ = program.__qualname__ = name.format(*dims)
            with profiler.scope(f"serving.compile.{span.format(*dims)}",
                                "serving", args=dim):
                jitted = jax.jit(
                    program, donate_argnums=(len(named) - 1,)
                    if self._donate else ())
                exe = jitted.lower(*specs).compile()
            self._exe_cache[key] = exe
            self._exe_flops[key] = _slo.executable_flops(exe)
            self.compiles[key] = self.compiles.get(key, 0) + 1
            return exe

    def _runtime_specs(self, bb: int, mb: int) -> tuple:
        """AOT specs of the runtime tail (``self._runtime_names``) at
        batch bucket ``bb`` over tables of ``mb`` pages — slab shapes
        are fixed by the adapter pool, so the executable matrix gains NO
        new dimension from multi-tenancy."""
        vec = self._arg_spec((bb,), np.dtype(np.int32))
        out = [vec] if self._slot_alloc is not None else []
        if self._walloc is not None:
            out.append(self._arg_spec((bb, mb), np.dtype(np.int32)))
        if self._lora:
            slabs = self._adapter_pool.slabs()
            for j in range(len(self._lora)):
                out += [self._spec_of(slabs[2 * j]),
                        self._spec_of(slabs[2 * j + 1]), vec]
        return tuple(out)

    def _runtime_args(self, streams, bb: int, mb: int) -> tuple:
        """Call-time runtime tail for one step.  The slot each row's
        stream holds (pad rows: 0, the scratch slot), staged like the
        other feeds; the windowed pools' table, ``mb`` wide (pad rows
        and blocks behind a row's window: 0, the scratch page); then,
        per rank bucket, the adapter pool's CURRENT
        slabs (fetched once — an atomic snapshot, so a concurrent
        publish lands next step, never mid-step) and the slot vector
        gathered from the batch: rows without an adapter — pad rows
        included — carry slot 0, the exact no-op."""
        from .io import stage_array

        out = []
        if self._slot_alloc is not None:
            vec = np.zeros(bb, np.int32)
            for i, s in enumerate(streams):
                vec[i] = s.slot
            out.append(stage_array(vec, self._device))
        if self._walloc is not None:
            table = np.zeros((bb, mb), np.int32)
            for i, s in enumerate(streams):
                table[i, :len(s.wblocks)] = s.wblocks
            out.append(stage_array(table, self._device))
        if self._lora:
            import jax

            slabs = self._adapter_pool.slabs()
            for j, rb in enumerate(self._lora):
                vec = np.zeros(bb, np.int32)
                for i, s in enumerate(streams):
                    if s is not None and s.adapter_slot is not None \
                            and s.adapter_bucket == rb:
                        vec[i] = s.adapter_slot
                out.extend((slabs[2 * j], slabs[2 * j + 1],
                            jax.device_put(vec, self._device)))
        return tuple(out)

    def _feed_exe(self, bb: int):
        """The program that builds a decode step's ``(bb, 1)`` token
        feed ON the device, from tokens no one has read yet: row ``i``
        is ``concat(prev, *firsts, host)[index[i]]`` — ``prev`` the
        last step's ``(bb,)`` sampled tokens, ``firsts`` the
        ``_FIRSTS_AHEAD`` newest prefills' ``(1,)`` first tokens,
        ``host`` the tokens the scheduler knows.  One a batch bucket,
        built by ``warmup()``."""
        key = ("feed", bb)
        exe = self._exe_cache.get(key)
        if exe is not None:
            return exe
        with self._compile_lock:
            exe = self._exe_cache.get(key)
            if exe is not None:
                return exe
            import jax
            import jax.numpy as jnp

            def next_tokens(prev, firsts, host, index):
                return jnp.concatenate(
                    (prev,) + tuple(firsts) + (host,))[index][:, None]

            next_tokens.__name__ = next_tokens.__qualname__ = \
                f"next_tokens_b{bb}"
            i32 = np.dtype(np.int32)
            row = self._arg_spec((bb,), i32)
            with profiler.scope(f"serving.compile.feed.b{bb}", "serving",
                                args={"batch": bb}):
                exe = jax.jit(
                    next_tokens,
                    out_shardings=self._device if self._mesh is not None
                    else None).lower(
                        row, (self._arg_spec((1,), i32),) * _FIRSTS_AHEAD,
                        row, row).compile()
            self._exe_cache[key] = exe
            self.compiles[key] = self.compiles.get(key, 0) + 1
            return exe

    def _cow_exe(self):
        """One jitted page copy for copy-on-write: every pool (values
        and scales) copies row ``src`` into row ``dst``; src/dst are
        traced scalars, so this compiles exactly once."""
        if self._cow_fn is None:
            import jax

            if self._mesh is not None:
                # stacked pools: page axis is 1 (behind the layer dim)
                copy = self._mesh.cow_fn()
            else:
                def copy(pools, src, dst):
                    return tuple(p.at[dst].set(p[src]) for p in pools)

            jitted = jax.jit(
                copy, donate_argnums=(0,) if self._donate else ())
            self._cow_fn = jitted
        return self._cow_fn

    # ------------------------------------------------------------------
    # page accounting: the alloc/release funnel (prefix-aware)
    # ------------------------------------------------------------------
    def _palloc(self, n: int, owner=None):
        """Allocate pages; with the prefix cache on, parked (cached)
        pages are evicted LRU when the free list runs dry."""
        if self._prefix is not None:
            return self._prefix.alloc(n, owner=owner)
        return self._alloc.alloc(n, owner=owner)

    def _release_pages(self, pages):
        """Detach a stream from its pages.  Exclusive pages free;
        shared pages drop one reference; indexed pages park for future
        prefix hits."""
        if not pages:
            return
        if self._prefix is not None:
            self._prefix.release(pages)
        else:
            self._alloc.free(pages)

    # ------------------------------------------------------------------
    # running ahead: programs dispatched whose tokens are not read yet
    # ------------------------------------------------------------------
    def _hold_back(self, streams) -> Optional[str]:
        """Why the loop may not dispatch past these streams' unread
        tokens — the name of the drain — or None: it may.  Read from
        what is in front of it: a chunked prefill in flight runs as it
        always did, between synchronous steps; a stream that stops at
        ``eos`` AND hands back its slot's state would have that state
        altered by the step dispatched past its last token."""
        if self._prefilling is not None:
            return "chunk"
        if any(s.keep_state and s.eos is not None for s in streams):
            return "state_eos"
        return None

    @staticmethod
    def _read(toks) -> np.ndarray:
        """The device's tokens on the host: where the loop waits for
        the device, and where a device error surfaces."""
        return np.asarray(toks)

    def _fetch_one(self) -> _Flight:
        """Fetch the oldest program's tokens and book them: a step's
        under ``serving.absorb``, a prefill's first token as
        ``_deliver_first`` does."""
        rec = self._inflight[0]
        with profiler.scope("serving.d2h_sync", "serving",
                            args={"active": len(rec.streams)}):
            toks = self._read(rec.toks)
        self._inflight.popleft()
        self._count("d2h_syncs")
        if self._inflight:  # a newer program is queued behind it
            self._count("d2h_syncs_saved")
        t_done = time.perf_counter()
        self._booking = True
        try:
            if rec.prefill is not None:
                self._deliver_first(rec, int(toks[0]), t_done)
            else:
                span_args = {"active": len(rec.streams), "retired": 0}
                with profiler.scope("serving.absorb", "serving",
                                    args=span_args):
                    span_args["retired"] = self._book_step(rec, toks,
                                                           t_done)
        finally:
            self._booking = False
        return rec

    def _fetch_all(self):
        while self._inflight:
            self._fetch_one()

    def _drain(self, reason: str):
        """Fetch and book everything in flight before something that
        needs the streams' delivery state whole; counted by reason
        (``serving.run_ahead.drain.<reason>``)."""
        if not self._inflight:
            return
        self._count("run_ahead_drains")
        self._count(f"run_ahead.drain.{reason}")
        with profiler.scope("serving.drain", "serving",
                            args={"reason": reason,
                                  "programs": len(self._inflight)}):
            self._fetch_all()

    def _settle(self, reason: str, timeout: float = 30.0):
        """From any thread: have the loop drain, and wait until it
        has."""
        if threading.current_thread() is self._thread:
            if not self._booking:  # (a caller's callback, mid-fetch)
                self._drain(reason)
            return
        settled = threading.Event()
        with self._cond:
            if not self._alive:
                return
            self._asks.append((reason, settled))
            self._cond.notify_all()
        settled.wait(timeout)

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------
    def _loop(self):
        profiler.name_thread("mx-decode-loop")  # its line in a trace
        try:
            while True:
                with self._cond:
                    while self._alive and not self._pending \
                            and not self._active \
                            and not self._imports \
                            and not self._inflight \
                            and not self._asks \
                            and self._prefilling is None:
                        with profiler.scope("serving.idle", "serving"):
                            self._cond.wait(timeout=0.5)
                    alive = self._alive
                    asks, self._asks = self._asks, []
                if not alive:
                    # tokens already sampled are delivered; what they
                    # do not finish fails in the ``finally`` below
                    self._drain("close")
                    for _, settled in asks:
                        settled.set()
                    return
                for reason, settled in asks:
                    self._drain(reason)
                    settled.set()
                if self._imports:
                    # splice migrated-in KV pages FIRST: an imported
                    # stream is past its prefill, so it joins the very
                    # next decode batch (migration adds no queue wait)
                    self._drain("import")
                    self._absorb_imports()
                if self._pending:
                    with profiler.scope(
                            "serving.admit", "serving",
                            args={"pending": len(self._pending),
                                  "active": len(self._active)}):
                        self._admit()
                else:
                    self._admit()  # (queued prefix invalidations)
                if self._prefilling is not None:
                    # ONE chunk per iteration: the decode step below
                    # runs between chunks, so a long admission can no
                    # longer stall every active stream's cadence
                    self._prefill_chunk()
                if self._active:
                    self._decode_step()
                elif self._inflight:
                    # nothing to dispatch behind them: the last tokens
                    # of streams that end by count, or first tokens
                    self._drain("idle")
                elif self._pending and self._prefilling is None:
                    # head-of-line request can't be admitted and no
                    # stream is decoding (transient: submit racing the
                    # loop) — don't busy-spin on the allocator
                    with self._cond, \
                            profiler.scope("serving.idle", "serving"):
                        self._cond.wait(timeout=0.05)
                profiler.set_gauge("serving.active_streams",
                                   len(self._active))
        except BaseException as exc:
            profiler.dump_flight_record(
                "engine_crash", extra={"error": repr(exc)})
            self._shut_door()  # before poisoning: submit() must not
            self._fail_outstanding(EngineClosedError(  # re-queue work
                f"DecodeEngine serving loop died: {exc!r}"))
            raise
        finally:
            # door first, drain second: a submit that won the race and
            # appended to _pending is caught by this drain; one that
            # lost sees _accepting False and raises EngineClosedError
            self._shut_door()
            self._fail_outstanding(
                EngineClosedError("DecodeEngine closed"))

    def _shut_door(self):
        with self._cond:
            self._accepting = False
            self._alive = False
            self._cond.notify_all()

    def _admit(self):
        """Join pending requests: admission is keyed to free cache
        blocks — the prompt's pages plus one block of decode headroom,
        capped at the stream's LIFETIME page need (a request whose
        prefill already holds every page it will ever touch needs no
        headroom, and one sized exactly to the pool must still be
        admittable).

        With the prefix cache on, the longest cached block-aligned
        prefix of the prompt is ATTACHED (block-table splice — pages
        shared by refcount, parked pages revived) and only the suffix
        needs new pages + prefill.  A fully-cached prompt skips
        prefill entirely: the stream enters decode replaying its last
        prompt token (whose page write COWs at the first step).
        Matched-but-parked pages are about to be revived, so they do
        NOT count as spare capacity for the admission check."""
        if self._prefix is not None and self._prefix_dirty:
            # adapter (re)publish/retire queued salt invalidations:
            # apply them HERE, on the tree-owning thread, before any
            # post-publish request can match a stale chain
            with self._cond:
                dirty, self._prefix_dirty = self._prefix_dirty, []
            for salt in dirty:
                self._prefix.invalidate_salt(salt)
        while True:
            with self._lock:
                if not self._pending \
                        or len(self._active) >= self._max_streams \
                        or self._prefilling is not None:
                    return
                # SLO-tiered admission: the first interactive stream
                # jumps the batch queue (within a class, FIFO order
                # holds — preempted re-queues sit at the front and
                # are interactive-or-original-class anyway)
                pick = 0
                for i, cand in enumerate(self._pending):
                    if cand.slo_class == "interactive":
                        pick = i
                        break
                s = self._pending[pick]
                seq = s.prefill_seq()
                if self._prefix is not None:
                    cached, parked_matched = self._prefix.peek(
                        seq, salt=_prefix_salt(s))
                else:
                    cached, parked_matched = 0, 0
                # cached is block-aligned, so the suffix page count is
                # exactly the total minus the attached chain — the
                # fully-cached prompt is the 0-token path:
                # blocks_for_tokens(0) == 0 new pages
                chunked = bool(self._chunk) \
                    and len(seq) - cached > self._chunk
                if chunked:
                    # admission charges pages incrementally per chunk:
                    # the gate covers only the FIRST chunk (later
                    # chunks allocate as they run; decode retirements
                    # keep refilling the pool between them)
                    need = self._blocks_for(self._chunk,
                                            self._kv_block)
                elif cached:
                    need = self._blocks_for(len(seq) - cached,
                                            self._kv_block)
                else:
                    need = self._blocks_for(max(len(seq), 1),
                                            self._kv_block)
                lifetime = self._blocks_for(
                    len(s.prompt) + s.max_new, self._kv_block)
                lifetime_new = max(
                    lifetime - cached // self._kv_block, 0)
                avail = self._alloc.free_blocks - parked_matched
                if avail < min(need + 1, max(lifetime_new, 1)):
                    return  # not enough cache: hold the FIFO line
                if self._walloc is not None \
                        and self._walloc.free_blocks < min(
                            self._window_need(len(seq)) + 1,
                            max(self._window_need(
                                len(s.prompt) + s.max_new), 1)):
                    return  # the windowed pools are short: the same hold
                if self._slot_alloc is not None and self._slot_alloc.live \
                        >= self._slot_alloc.num_slots:
                    # every slot is held: streams whose last tokens are
                    # in flight keep theirs until those are fetched
                    return
                self._pending.pop(pick)
                self._admitting = s  # visible to _fail_outstanding
            # On failure _admitting must STAY set until the loop's
            # poison handler runs — clearing it first would strand the
            # caller's future between pop and activation.
            if self._prefix is not None:
                cached, pages = self._prefix.attach(
                    seq, owner=s.sid, salt=_prefix_salt(s))
            else:
                cached, pages = 0, []
            s.cost.book_pages(0)  # page-second clock starts at attach
            s.blocks = pages  # attach now: a dying prefill must not leak
            s.cached_len = cached
            if chunked:
                # hand off to the chunk state machine: s.length tracks
                # tokens cached so far; chunks run at iteration
                # boundaries, interleaved with decode steps
                s.length = cached
                self._prefilling = s
                self._admitting = None
                return
            new_pages = self._palloc(need, owner=s.sid)
            if new_pages is None:  # pragma: no cover - defensive
                raise MXNetError(
                    f"admission raced the allocator: {need} pages "
                    f"unavailable after the capacity check")
            s.cost.book_pages(len(s.blocks))
            s.blocks = pages + new_pages
            if self._walloc is not None:
                # the windowed pools' pages of the prompt: none for the
                # blocks the first decode step's window no longer
                # reaches (their K/V goes to the scratch page)
                with profiler.scope("serving.window_alloc", "serving",
                                    args={"sids": s.sid}):
                    held = self._window_need(len(seq))
                    wpages = self._walloc.alloc(held, owner=s.sid)
                    if wpages is None:  # pragma: no cover - defensive
                        raise MXNetError(
                            f"admission raced the windowed pools' "
                            f"allocator: {held} pages unavailable after "
                            f"the capacity check")
                    s.wfirst = len(s.blocks) - held
                    s.wblocks = [0] * s.wfirst + wpages
                self._count("window_prefill_pairs",
                            self._band_pairs(len(seq)))
            if cached == len(seq) and cached > 0:
                self._full_hit(s, seq)
                if s.migrate:
                    # ship the cached pages as-is: the importer enters
                    # in full-hit state (replaying the last prompt
                    # token), so its first decode step samples the
                    # first token with the same (seed, position) key
                    with self._lock:
                        self._active.remove(s)
                    self._drain("export")
                    self._export_stream(s)
            else:
                self._prefill(s, seq, s.blocks)
            self._admitting = None

    def _full_hit(self, s: _Stream, seq: np.ndarray):
        """Admission of a fully-cached prompt: NO prefill runs.  A
        fresh stream re-enters decode at its last prompt token — the
        step recomputes that token's K/V (the write COWs the shared
        tail page) and samples the first new token, so TTFT is one
        decode step.  A resumed stream's pending next_token survives,
        so it continues exactly where preemption cut it."""
        n = len(seq)
        if self._prefix is not None:
            self._prefix.full_hits += 1
        if s.resume:
            s.length = n          # cache holds all of seq
            s.resume = False      # next_token survives preemption
        else:
            s.length = n - 1      # replay the last prompt token
            s.next_token = int(seq[-1])
            s.await_first = True  # first token (and TTFT) at step 1
        now = time.perf_counter()
        wait_ms = (now - s.t_enqueue) * 1e3
        self._metrics.observe("queue_wait_ms", wait_ms)
        profiler.observe("serving.queue_wait_ms", wait_ms)
        if s.trace is not None:
            profiler.add_trace_event(
                "serving.queue", s.t_enqueue, now - s.t_enqueue,
                s.trace.child(), cat="serving",
                args={"sid": s.sid, "full_hit": True})
        s.t_admit = now
        with self._lock:
            self._active.append(s)

    def _suffix_prefill_call(self, s: _Stream, seq: np.ndarray,
                             done: int, end: int, label: str,
                             kind: str, extra: dict):
        """Launch the suffix-prefill executable over
        ``seq[done:end]`` (absolute token offsets, ``done``
        block-aligned): the one launch behind both a prefix-cache
        hit's one-shot suffix and every chunk of a chunked prefill
        (both bit-identity contracts are pinned against the same
        monolithic prefill).  Returns the sampled-token DEVICE array
        (meaningful only when ``end`` covers the full sequence — the
        caller decides whether to fetch it) and the prefill bucket
        used."""
        csize = end - done
        tp = self._bucket(self._prefill_buckets, csize, label)
        mb = self._bucket(self._cache_buckets, len(s.blocks),
                          "cache blocks")
        exe = self._exe("prefix_prefill", tp, mb)
        with profiler.scope(f"serving.prefill.{kind}.t{tp}",
                            "serving",
                            args=dict(extra, tokens=csize, bucket=tp)):
            toks, self._pools = exe(
                self._params,
                *self._prompt_feeds(s, seq, done, end, tp, mb, s.blocks,
                                    True),
                self._pools, *self._runtime_args([s], 1, mb))
        s.cost.flops_est += self._exe_flops.get(
            ("prefix_prefill", tp, mb), 0.0)
        self._count("prefill_bucket_tokens", tp)
        return toks, tp

    def _prompt_feeds(self, s: _Stream, seq: np.ndarray, done: int,
                      end: int, tp: int, mb: int, pages,
                      suffix: bool) -> tuple:
        """The staged feeds of one prompt row over ``seq[done:end]``
        padded to ``tp``, its table ``pages`` padded to ``mb``: tokens,
        positions, ``start`` (the ``suffix`` program's), lengths, table,
        temps, seeds, steps — the ONE feed builder behind monolithic
        prefill, a prefix hit's suffix and every chunk, so the three
        cannot drift apart."""
        from .io import stage_array

        tokens = np.zeros((1, tp), np.int32)
        tokens[0, :end - done] = seq[done:end]
        table = np.zeros((1, mb), np.int32)
        table[0, :len(pages)] = pages
        feeds = [tokens, (done + np.arange(tp, dtype=np.int32))[None],
                 np.asarray([end], np.int32), table,
                 np.asarray([s.temp], np.float32),
                 np.asarray([s.seed], np.int32),
                 np.asarray([len(seq) - 1], np.int32)]  # sampling position
        if suffix:
            feeds.insert(2, np.asarray([done], np.int32))
        return tuple(stage_array(a, self._device) for a in feeds)

    def _prefill(self, s: _Stream, seq: np.ndarray, pages: List[int]):
        """Dispatch the prompt's program and go on: the sampled first
        token stays on the device (``s.feed``) until the loop fetches
        it behind a later decode step.  Where it may not run ahead
        (``_hold_back``; a stream to export), what is in flight is
        fetched first and this prefill's token right after."""
        n = len(seq)
        c = s.cached_len  # block-aligned prefix already in the cache
        why = "export" if s.migrate else self._hold_back([s])
        if why:
            self._drain(why)
        # a decode step takes _FIRSTS_AHEAD first tokens from the
        # device: the oldest programs are fetched before one more
        while sum(r.prefill is not None for r in self._inflight) \
                >= _FIRSTS_AHEAD:
            self._fetch_one()
        t_pre0 = time.perf_counter()
        if c:
            # prefix hit: prefill ONLY the uncached suffix, attending
            # the shared prefix through the block table
            ns = n - c
            s.blocks = pages
            toks, tp = self._suffix_prefill_call(
                s, seq, c, n, "suffix length", "suffix",
                {"cached": c, "resume": s.resume})
        else:
            ns = n
            tp = self._bucket(self._prefill_buckets, n, "prompt length")
            mb = tp // self._kv_block
            exe = self._exe("prefill", tp)
            if self._slot_alloc is not None:
                # one slot from admission to retirement; the prefill
                # below writes it anew (a re-prefill after preemption
                # too), so a reused slot never shows its last owner
                with profiler.scope("serving.slot_alloc", "serving",
                                    args={"sids": s.sid}):
                    s.slot = self._slot_alloc.alloc(owner=s.sid)
                    profiler.set_gauge("serving.state_slots_live",
                                       self._slot_alloc.live)
                if s.slot is None:  # pragma: no cover - defensive
                    s.slot = 0
                    raise MXNetError(
                        "admission raced the slot allocator: no state "
                        "slot for an admitted stream")
            with profiler.scope(f"serving.prefill.t{tp}", "serving",
                                args={"sids": s.sid, "tokens": n,
                                      "bucket": tp,
                                      "resume": s.resume}):
                with self._pools_lock:
                    toks, self._pools = exe(
                        self._params,
                        *self._prompt_feeds(s, seq, 0, n, tp, mb, pages,
                                            False),
                        self._pools, *self._runtime_args([s], 1, mb))
            s.cost.flops_est += self._exe_flops.get(("prefill", tp),
                                                    0.0)
            # the rows the program ran, beside the rows that were real
            # (prefill_tokens): the bucket ladder's padding is work
            self._count("prefill_bucket_tokens", tp)
            self._count_prompt_tiles(n, tp)
        s.blocks = pages
        s.length = n
        self._launch_prefill(s, toks, n, ns, c, tp, t_pre0)
        if why:
            self._fetch_all()
        else:
            self._count("prefill_first_deferred")

    def _launch_prefill(self, s: _Stream, toks, n: int, ns: int, c: int,
                        tp: int, t_pre0: float):
        """The scheduling half of a prefill's completion (monolithic,
        suffix, final chunk), at its DISPATCH: the prompt's pages are
        registered, the stream joins the next decode batch — its first
        token fed from the device — and the program's record joins the
        ones in flight.  ``_deliver_first`` is the other half."""
        if self._prefix is not None and not s.migrate:
            # the prompt's full pages become shareable; blocks already
            # indexed keep the incumbent page (ours stays private) — a
            # migrating stream's pages are about to LEAVE this pool,
            # so they never enter the index
            self._prefix.register(s.prompt, s.blocks,
                                  salt=_prefix_salt(s))
        self._count("prefills")
        self._count("prefill_tokens", ns)  # uncached tokens only
        # the query-key pairs those tokens' causal attention holds (row
        # i sees i + 1 keys): what a prefill kernel's need is counted
        # from
        self._count("prefill_pairs", (n * (n + 1) - c * (c + 1)) // 2)
        s.cost.prefill_tokens += ns
        s.t_admit = time.perf_counter()
        self._inflight.append(_Flight(
            toks, [s], t_pre0, prefill=(n, c, tp, s.resume)))
        if s.resume:
            s.resume = False  # next_token survives preemption
        else:
            s.ahead, s.feed = 1, (toks, 0)
            s.await_first = False  # first token delivered via prefill
        if not s.migrate and not s.last_by_count():
            with self._lock:
                self._active.append(s)

    def _deliver_first(self, rec: _Flight, first: int, t_done: float):
        """The delivery half, when the prefill's token is fetched: the
        timing and TTFT metrics, the first token into ``generated``,
        and retirement or export where the stream ends there."""
        s, = rec.streams
        n, c, tp, resume = rec.prefill
        t_pre0 = rec.t0
        s.cost.d2h_syncs += 1
        prefill_ms = (t_done - t_pre0) * 1e3
        self._metrics.observe("prefill_ms", prefill_ms)
        profiler.observe("serving.prefill_ms", prefill_ms)
        if s.trace is not None:
            # queue wait (enqueue → prefill start) and the prefill
            # itself, as child spans of the request's trace — a resume
            # prefill's queue span covers only the post-preemption
            # wait, not the service time already rendered; a chunked
            # prefill's earlier chunks emitted their own spans
            profiler.add_trace_event(
                "serving.queue", s.t_enqueue, t_pre0 - s.t_enqueue,
                s.trace.child(), cat="serving",
                args={"sid": s.sid, "resume": resume})
            profiler.add_trace_event(
                "serving.prefill", t_pre0, t_done - t_pre0,
                s.trace.child(), cat="serving",
                args={"sid": s.sid, "tokens": n, "bucket": tp,
                      "resume": resume})
        wait_ms = (t_pre0 - s.t_enqueue) * 1e3
        self._metrics.observe("queue_wait_ms", wait_ms)
        profiler.observe("serving.queue_wait_ms", wait_ms)
        if not resume:  # (a resumed stream's token was its pending one)
            s.ahead -= 1
            if s.feed[0] is rec.toks:
                s.feed = None
            s.next_token = first
            s.generated.append(first)
            ttft = (t_done - s.t_submit) * 1e3
            self._metrics.observe("ttft_ms", ttft)
            profiler.observe("serving.ttft_ms", ttft)
            # hit/miss TTFT split: a hit's first token cost only the
            # suffix prefill — the headline prefix-cache latency win
            split = "ttft_hit_ms" if c else "ttft_miss_ms"
            self._metrics.observe(split, ttft)
            profiler.observe(f"serving.{split}", ttft)
            self._slo.observe_ttft(s.slo_class, ttft)
            self._count("tokens")
            s.cost.tokens += 1  # same site as the engine counter
        if s.migrate:
            self._export_stream(s)
        elif s.done():  # max_new == 1 or instant eos
            self._end(s)

    def _end(self, s: _Stream):
        """Retire a stream its fetched tokens finished: one that ended
        by count left the batch when its last program was dispatched,
        one that read ``eos`` leaves it now."""
        with self._lock:
            try:
                self._active.remove(s)
            except ValueError:
                pass
        self._retire(s)

    def _prefill_chunk(self):
        """Advance the in-flight chunked prefill by ONE fixed-size
        slice — a suffix-prefill continuation (the PR-13 executable
        already takes an offset): the chunk's K/V is written at
        absolute offset ``s.length`` and its queries attend the pages
        already cached plus the chunk causally, bit-identical (lax
        path, fp32 pools) to the matching rows of monolithic prefill.
        A chunk that cannot get its pages simply waits for the next
        iteration (decode retirements refill the pool); only the FINAL
        chunk samples the first token and activates the stream."""
        self._drain("chunk")  # chunks run between synchronous steps
        s = self._prefilling
        seq = s.prefill_seq()
        n = len(seq)
        done = s.length       # tokens cached so far (block-aligned)
        end = min(done + self._chunk, n)
        need = self._blocks_for(end, self._kv_block) - len(s.blocks)
        if need > 0:
            pages = self._palloc(need, owner=s.sid)
            if pages is None:
                return  # pool dry: retry after the next decode step
            s.cost.book_pages(len(s.blocks))
            s.blocks.extend(pages)
        t0 = time.perf_counter()
        if done == s.cached_len:
            s.t_chunk0 = t0  # first chunk: queue wait ends here
        toks, tp = self._suffix_prefill_call(
            s, seq, done, end, "chunk length", "chunk",
            {"sid": s.sid, "offset": done, "of": n})
        # the sampled token only means anything on the final chunk —
        # fetching it on every chunk would serialize the scheduler
        # with each chunk's full device wall, the exact stall chunking
        # exists to bound.  Non-final chunks stay async: the
        # interleaved decode step queues behind them on the device (so
        # chunk_ms here times the launch, not the compute, for those).
        s.length = end
        if end >= n:
            self._prefilling = None
            self._launch_prefill(s, toks, n, n - s.cached_len,
                                 s.cached_len, tp, s.t_chunk0)
            self._fetch_all()  # the final chunk's token fetch
        t_done = time.perf_counter()
        self._count("prefill_chunks")
        self._metrics.observe("prefill_chunk_ms", (t_done - t0) * 1e3)
        profiler.observe("serving.prefill_chunk_ms",
                         (t_done - t0) * 1e3)

    def _reclaimable(self, v: _Stream) -> int:
        """Pages preempting ``v`` would actually return to the pool:
        the ones ``v`` holds exclusively (a shared page only loses one
        reference — its co-holders keep it resident)."""
        if self._prefix is None:
            return len(v.blocks)
        return sum(1 for p in v.blocks
                   if self._alloc.refcount(p) == 1)

    def _alloc_with_preempt(self, s: _Stream, n: int,
                            alloc=None) -> Optional[List[int]]:
        """Pages for active stream ``s``, preempting the youngest
        other stream when the pool (including evictable cached pages)
        is exhausted.  None: ``s`` itself was failed and removed.
        ``alloc``: another pool's allocation (the windowed pools');
        a preemption frees a victim's pages in every pool."""
        while True:
            pages = (alloc or self._palloc)(n, owner=s.sid)
            if pages is not None:
                return pages
            if self._inflight:
                # a victim is rewound to what it has DELIVERED: its
                # tokens in flight are booked first (which may free
                # pages, or end ``s`` itself), then the pool is asked
                # again
                self._drain("preempt")
                if s not in self._active:
                    return None
                continue
            # a victim must be able to COME BACK: its resume
            # re-prefill (prompt + progress = its cached tokens) has
            # to fit the prefill ladder — unless chunked prefill is
            # on, which re-prefills ANY length in ladder-sized slices,
            # making every stream preemptable
            victims = [v for v in self._active if v is not s
                       and (self._chunk
                            or v.length <= self._prefill_buckets[-1])]
            if not victims:
                with self._lock:
                    self._active.remove(s)
                s.cost.book_pages(len(s.blocks))
                self._release_pages(s.blocks)
                s.blocks = []
                self._release_window(s)
                self._release_slot(s)
                if not s.canary:
                    self._slo.observe_avail(s.slo_class, False)
                if s.future.set_running_or_notify_cancel():
                    s.future.set_exception(MXNetError(
                        f"KV cache exhausted: stream {s.sid} needs a "
                        f"page and no preemptable stream remains "
                        f"(pool: {self._alloc.capacity} blocks, "
                        f"largest resumable prefill: "
                        f"{self._prefill_buckets[-1]} tokens); size "
                        f"cache_blocks / the prefill ladder for the "
                        f"workload"))
                return None
            # prefer victims whose preemption actually frees pages: a
            # pure sharer only drops refcounts, so evicting it first
            # is N-1 pointless re-prefills before anything returns to
            # the pool.  When EVERY victim is a pure sharer, fall back
            # to the youngest anyway — successive preemptions drain
            # the chain's refcount to zero, park it, and the eviction
            # path reclaims it (liveness preserved).
            productive = [v for v in victims
                          if self._reclaimable(v) > 0]
            # SLO tiering extends the pressure ladder: among equally
            # productive victims, a batch-class stream is preempted
            # before any interactive one, youngest first within a tier
            victim = max(productive or victims,
                         key=lambda v: (v.slo_class == "batch",
                                        v.t_admit))
            self._preempt(victim)

    def _ensure_capacity(self, s: _Stream, ahead: int = 1) -> bool:
        """Grow ``s`` to hold ``ahead`` more tokens' pages if needed
        (1 = the next token's page, counted from the SCHEDULED length:
        a step dispatched past an unread ``eos`` still writes a page
        the stream owns; a verify window needs more); preempt the
        youngest other stream when the pool is exhausted.  False when
        ``s`` itself could not be kept resident."""
        need = self._blocks_for(s.length + ahead, self._kv_block) \
            - len(s.blocks)
        if need <= 0:
            return True
        pages = self._alloc_with_preempt(s, need)
        if pages is None:
            return False
        s.cost.book_pages(len(s.blocks))
        s.blocks.extend(pages)
        if self._walloc is not None:
            # the same logical blocks in the windowed pools
            wpages = self._alloc_with_preempt(s, need, self._walloc.alloc)
            if wpages is None:
                return False
            s.wblocks.extend(wpages)
        return True

    # -- the windowed pools' pages --------------------------------------
    def _window_first(self, length: int) -> int:
        """The first logical block the query at position ``length``
        (the next decode step's) still sees under the window: the
        blocks before it are wholly behind ``length - window + 1``."""
        return max(length - self._window + 1, 0) // self._kv_block

    def _window_need(self, tokens: int) -> int:
        """Windowed-pool pages a stream holds once ``tokens`` are
        cached."""
        return self._blocks_for(max(tokens, 1), self._kv_block) \
            - self._window_first(tokens)

    def _band_pairs(self, n: int) -> int:
        """Query-key pairs a windowed layer's prefill of ``n`` tokens
        needs: row i sees min(i + 1, window) keys."""
        w = min(n, self._window)
        return w * (w + 1) // 2 + (n - w) * self._window

    def _release_window(self, s: _Stream, upto: Optional[int] = None):
        """Give back the stream's windowed-pool pages of the logical
        blocks before ``upto`` (all of them at retirement, preemption
        and shutdown); returns how many."""
        if self._walloc is None:
            return 0
        end = len(s.wblocks) if upto is None else min(upto, len(s.wblocks))
        pages = [p for p in s.wblocks[s.wfirst:end] if p]
        if pages:
            self._walloc.free(pages)
        if upto is None:
            s.wblocks, s.wfirst = [], 0
        elif end > s.wfirst:
            s.wblocks[s.wfirst:end] = [0] * (end - s.wfirst)
            s.wfirst = end
        return len(pages)

    def _maybe_cow(self, s: _Stream) -> bool:
        """Copy-on-write probe before this step's cache write: if the
        page about to receive position ``s.length``'s K/V is shared
        (another stream holds it, or the prefix index still maps its
        bytes), copy it to a private page on device and splice the
        block table.  The only route here in practice is a fully-
        cached prompt replaying its last token — every other write
        lands on a page that is private by construction (the index
        holds only FULL pages, so a partial tail is never shared).
        False when ``s`` could not get its private copy."""
        j = s.length // self._kv_block
        if j >= len(s.blocks):  # pragma: no cover - ensured upstream
            return True
        page = s.blocks[j]
        if not self._prefix.needs_cow(page):
            return True
        pages = self._alloc_with_preempt(s, 1)
        if pages is None:
            return False
        new = pages[0]
        with profiler.scope("serving.cow_copy", "serving",
                            args={"sid": s.sid, "src": page,
                                  "dst": new}):
            self._pools = self._cow_exe()(
                self._pools, np.int32(page), np.int32(new))
        s.blocks[j] = new
        self._prefix.release([page])  # drop OUR ref; sharers keep it
        self._prefix.note_cow()
        s.cost.cow_copies += 1  # same site as the cache's counter
        return True

    def _preempt(self, victim: _Stream):
        """Recompute-style preemption: drop the victim's pages, requeue
        it (front of the line) for re-prefill of prompt + progress.
        Shared pages lose only the victim's reference — sharers keep
        reading them, and the victim's re-admission will usually
        re-attach them as a prefix hit."""
        victim.cost.book_pages(len(victim.blocks))
        self._release_pages(victim.blocks)
        victim.blocks = []
        self._release_window(victim)
        self._release_slot(victim)  # recompute: re-prefill writes anew
        victim.length = 0
        victim.cached_len = 0
        victim.ahead, victim.feed = 0, None  # (drained: nothing unread)
        # a full-hit stream preempted BEFORE its first sampled token
        # re-admits as a fresh request (there is no pending progress
        # to resume; prefill_seq would otherwise drop the last token)
        victim.resume = bool(victim.generated)
        victim.t_enqueue = time.perf_counter()  # re-queued from NOW
        with self._lock:
            self._active.remove(victim)
            self._pending.insert(0, victim)
        self._count("preempted")

    def _release_slot(self, s: _Stream):
        """Hand back the stream's state slot (retirement, preemption,
        shutdown); its contents stay until the next owner's prefill
        overwrites them."""
        if not s.slot:
            return
        with profiler.scope("serving.slot_free", "serving",
                            args={"sids": s.sid}):
            self._slot_alloc.free(s.slot)
            s.slot = 0
            profiler.set_gauge("serving.state_slots_live",
                               self._slot_alloc.live)

    def _release_adapter(self, s: _Stream):
        """Drop the stream's adapter-pool reference exactly once (the
        slot id in the stream doubles as the not-yet-released flag).
        Preemption does NOT come through here — a preempted stream
        keeps its reference so the slot cannot be evicted while it
        waits for re-admission."""
        if s.adapter is None or s.adapter_slot is None:
            return
        s.adapter_slot = None
        try:
            self._adapter_pool.release(s.adapter)
        except MXNetError:
            pass  # pool already torn down (close during shutdown)

    def _retire(self, s: _Stream):
        s.cost.book_pages(len(s.blocks))
        if s.blocks:
            self._release_pages(s.blocks)
            s.blocks = []
        self._release_window(s)
        result = np.asarray(s.generated, np.int32)
        if s.keep_state:
            with self._pools_lock:  # the pools are donated step by step
                result = {"tokens": result, "state": {
                    n: np.asarray(p[s.slot])
                    for n, k, p in zip(self._pool_names,
                                       self._pool_kinds, self._pools)
                    if k == "slots"}}
        self._release_slot(s)
        self._release_adapter(s)
        if s.tenant is not None and not s.canary:
            self._tenant_count(s.tenant, "tokens",
                               len(s.generated) + len(s.prompt))
            self._tenant_count(s.tenant, "generations")
        if s.future.set_running_or_notify_cancel():
            s.future.set_result(result)
        self._count("generations")
        self._cost_agg.add(s.cost)
        if s.cost.flops_est:
            self._count("cost_flops", s.cost.flops_est)
        if not s.canary:
            # canary delivery outcomes are the PROBER's to book (it
            # also sees the failures this path never reaches)
            self._slo.observe_avail(s.slo_class, True)

    # ------------------------------------------------------------------
    # live KV page migration (disaggregated prefill/decode roles)
    # ------------------------------------------------------------------
    def _frame_shape(self, i: int, n: int) -> tuple:
        """What a migration frame declares for ``n`` pages of pool
        ``i``.  Value pages travel as (n, KVB, H, D) — the same bytes
        as the pool's (n, KVB, H·D) rows, so frames read as they did
        when the pools were 4-D; a quantized pool's scales travel as
        they lie, (n, KVB, H)."""
        if self._pool_kinds[i] == "pages":
            return (n, self._kv_block, self._H, self._D)
        return (n, self._kv_block, self._H)

    def _export_stream(self, s: _Stream):
        """Gather a prefill-only stream's KV pages off the pool and
        resolve its Future with a migration payload: ``meta`` (stream
        state — seed, lengths, pending token, generated so far) plus
        ``kv_arrays`` (prompt, generated, then one page slab per pool,
        scale slabs included for quantized dtypes).  Pages this stream
        holds exclusively leave the allocator through
        ``export_pages``; pages still shared with other streams only
        drop this stream's reference (their bytes were copied out).
        Runs ON the scheduler thread — the pools are donated jax
        buffers only the loop may touch."""
        t0 = time.perf_counter()
        done = s.done()  # max_new == 1 or instant eos: state-only frame
        if s.blocks and not done:
            idx = np.asarray(s.blocks, np.int32)
            self._count("d2h_syncs")
            s.cost.d2h_syncs += 1
        else:
            idx = np.zeros((0,), np.int32)
        slabs = [np.asarray(p[idx]).reshape(self._frame_shape(i, len(idx)))
                 for i, p in enumerate(self._pools)]
        nbytes = sum(a.nbytes for a in slabs)
        meta = {
            "fmt": 1,
            "sid": s.sid,
            "seed": int(s.seed),
            "temp": float(s.temp),
            "eos": None if s.eos is None else int(s.eos),
            "max_new": int(s.max_new),
            "length": int(s.length),
            "next_token": int(s.next_token),
            "await_first": bool(s.await_first),
            "slo_class": s.slo_class,
            "canary": bool(s.canary),
            "tenant": s.tenant,
            "adapter": s.adapter,
            "done": done,
            "n_pages": 0 if done else len(s.blocks),
            "kv_dtype": self._kv_dtype,
            "kv_block": self._kv_block,
            "num_layers": self._L,
            "pool_stride": self._pool_stride,
            "migration_bytes": int(nbytes),
        }
        arrays = [np.asarray(s.prompt, np.int32),
                  np.asarray(s.generated, np.int32)] + slabs
        # detach exported pages from the radix index FIRST (a chain
        # whose pages leave this pool must stop being matchable), then
        # export exclusive pages / release shared ones
        s.cost.book_pages(len(s.blocks))
        if self._prefix is not None:
            self._prefix.detach(s.blocks)
        for p in s.blocks:
            if self._alloc.refcount(p) > 1:
                self._release_pages([p])
            else:
                self._alloc.export_pages([p])
        s.blocks = []
        # the decode replica re-acquires the adapter by name on import
        self._release_adapter(s)
        t_done = time.perf_counter()
        ms = (t_done - t0) * 1e3
        # the migration counter and the cost-record mirror increment
        # at THIS site together — the sum(records) == stats()
        # conservation contract extends to migration_bytes/_ms
        self._count("migrations_out")
        self._count("migration_bytes", nbytes)
        self._count("migration_ms", ms)
        s.cost.migration_bytes += nbytes
        s.cost.migration_ms += ms
        # the router folds the engine-side export cost into its
        # end-to-end migration_ms histogram — ship it in the meta
        meta["export_ms"] = round(ms, 6)
        self._metrics.observe("migration_export_ms", ms)
        profiler.observe("serving.migration_export_ms", ms)
        if s.trace is not None:
            profiler.add_trace_event(
                "serving.migrate_out", t0, t_done - t0,
                s.trace.child(), cat="serving",
                args={"sid": s.sid, "pages": int(meta["n_pages"]),
                      "bytes": int(nbytes)})
        self._cost_agg.add(s.cost)
        if s.cost.flops_est:
            self._count("cost_flops", s.cost.flops_est)
        if s.future.set_running_or_notify_cancel():
            s.future.set_result({"meta": meta, "kv_arrays": arrays})

    def import_stream(self, meta: dict, arrays, trace=None) -> Future:
        """Splice a migrated stream into this engine: allocate pages
        (``BlockAllocator.import_pages``), scatter the shipped slabs
        into the pools, and continue decode from the exporter's exact
        state.  Sampling is keyed by (engine seed, stream seed,
        position) and the importer reuses the exporter's stream seed,
        so the tokens are BIT-IDENTICAL to a never-migrated run.
        Thread-safe; the splice itself runs on the scheduler thread.
        The Future resolves to the FULL generated token array
        (including tokens the exporter's prefill already emitted)."""
        if self._slot_alloc is not None or self._walloc is not None:
            raise MXNetError(
                f"page import is not built for {self._spec.name}: an "
                f"imported stream would arrive without the state its "
                f"slot or its windowed pools hold")
        if self._latent_row:
            raise MXNetError(
                f"page import is not built for {self._spec.name}: "
                f"{_NO_LATENT_FRAME}")
        if self._mesh is not None:
            raise MXNetError(
                "KV page migration onto a tp/pp-meshed engine is not "
                "supported yet (page slabs are per-shard)")
        if int(meta.get("fmt", -1)) != 1:
            raise MXNetError(
                f"migration payload fmt {meta.get('fmt')!r} unknown")
        if meta["kv_dtype"] != self._kv_dtype:
            raise MXNetError(
                f"migration kv_dtype {meta['kv_dtype']!r} != this "
                f"engine's {self._kv_dtype!r} — roles must serve "
                f"identical pool dtypes")
        if int(meta["kv_block"]) != self._kv_block:
            raise MXNetError(
                f"migration page size {meta['kv_block']} != this "
                f"engine's kv_block {self._kv_block} — pages only "
                f"splice across an identical page grid")
        if int(meta["num_layers"]) != self._L \
                or int(meta["pool_stride"]) != self._pool_stride:
            raise MXNetError(
                "migration layer/pool layout mismatch: "
                f"{meta['num_layers']}x{meta['pool_stride']} vs "
                f"{self._L}x{self._pool_stride}")
        if len(arrays) != 2 + len(self._pools):
            raise MXNetError(
                f"migration payload has {len(arrays)} arrays; "
                f"expected prompt + generated + {len(self._pools)} "
                f"page slabs")
        n_pages = int(meta["n_pages"])
        for i, (p, slab) in enumerate(zip(self._pools, arrays[2:])):
            want = self._frame_shape(i, n_pages)
            if tuple(np.shape(slab)) != want \
                    or np.dtype(slab.dtype) != np.dtype(p.dtype):
                raise MXNetError(
                    f"migration slab {np.shape(slab)}/{slab.dtype} "
                    f"does not match pool row {want}/{p.dtype}")
        if n_pages > self._alloc.capacity:
            raise MXNetError(
                f"migrated stream holds {n_pages} pages but this "
                f"pool only has {self._alloc.capacity}")
        fut: Future = Future()
        with self._cond:
            if not self._accepting:
                raise EngineClosedError(
                    self._reject or "DecodeEngine is closed")
            self._imports.append((dict(meta), list(arrays), fut,
                                  trace, time.perf_counter()))
            self._owned.add(fut)
            self._cond.notify_all()
        fut.add_done_callback(self._disown)
        return fut

    def _import_alloc(self, n: int, owner) -> Optional[List[int]]:
        """Pages for an incoming migration: evict parked prefix pages
        first, then preempt the youngest resumable stream — the same
        pressure ladder admission uses."""
        while True:
            if self._prefix is not None:
                short = n - self._alloc.free_list_blocks
                if short > 0:
                    self._prefix.evict(short)
            pages = self._alloc.import_pages(n, owner=owner)
            if pages is not None:
                return pages
            victims = [v for v in self._active
                       if self._chunk
                       or v.length <= self._prefill_buckets[-1]]
            if not victims:
                return None
            productive = [v for v in victims
                          if self._reclaimable(v) > 0]
            victim = max(productive or victims,
                         key=lambda v: (v.slo_class == "batch",
                                        v.t_admit))
            self._preempt(victim)

    def _absorb_imports(self):
        """Drain the queued migrations (scheduler thread): allocate,
        scatter each payload's slabs into the pools, and activate the
        stream exactly where the exporter cut it."""
        with self._lock:
            items, self._imports = self._imports, []
        for meta, arrays, fut, trace, t_recv in items:
            t0 = time.perf_counter()
            n_pages = int(meta["n_pages"])
            sid = self._next_sid
            self._next_sid += 1
            pages = self._import_alloc(n_pages, owner=sid)
            if pages is None:
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(MXNetError(
                        f"cannot import migrated stream: {n_pages} "
                        f"pages unavailable (pool: "
                        f"{self._alloc.capacity} blocks) and no "
                        f"preemptable stream remains"))
                continue
            if n_pages:
                idx = np.asarray(pages, np.int32)
                pools = list(self._pools)
                for i, slab in enumerate(arrays[2:]):
                    pools[i] = pools[i].at[idx].set(np.reshape(
                        slab, (n_pages,) + pools[i].shape[1:]))
                self._pools = tuple(pools)
            prompt = np.asarray(arrays[0], np.int32)
            tenant = meta.get("tenant")
            adapter = meta.get("adapter")
            if adapter is not None:
                # the importer re-acquires the adapter BY NAME — both
                # roles must have published it (fleet broadcast does)
                if self._adapter_pool is None:
                    if fut.set_running_or_notify_cancel():
                        fut.set_exception(MXNetError(
                            f"migrated stream uses adapter "
                            f"{adapter!r} but this engine has no "
                            f"adapter pool"))
                    self._release_pages(pages)
                    continue
                try:
                    ad_bucket, ad_slot = \
                        self._adapter_pool.acquire(adapter)
                except MXNetError as e:
                    if fut.set_running_or_notify_cancel():
                        fut.set_exception(e)
                    self._release_pages(pages)
                    continue
            s = _Stream(sid, prompt, int(meta["max_new"]),
                        float(meta["temp"]),
                        None if meta["eos"] is None
                        else int(meta["eos"]),
                        fut, seed=int(meta["seed"]), trace=trace,
                        slo_class=meta.get("slo_class",
                                           "interactive"),
                        canary=bool(meta.get("canary", False)),
                        tenant=tenant, adapter=adapter)
            if adapter is not None:
                s.adapter_bucket, s.adapter_slot = ad_bucket, ad_slot
            s.generated = [int(t) for t in np.asarray(arrays[1])]
            s.blocks = pages
            s.length = int(meta["length"])
            s.next_token = int(meta["next_token"])
            s.await_first = bool(meta.get("await_first", False))
            s.cost.book_pages(0)  # page-second clock starts at splice
            t_done = time.perf_counter()
            ms = (t_done - t0) * 1e3
            self._count("migrations_in")
            self._metrics.observe("migration_import_ms", ms)
            profiler.observe("serving.migration_import_ms", ms)
            if trace is not None:
                profiler.add_trace_event(
                    "serving.migrate_in", t0, t_done - t0,
                    trace.child(), cat="serving",
                    args={"sid": sid, "pages": n_pages,
                          "bytes": int(meta.get("migration_bytes",
                                                0))})
            if s.done():  # exporter shipped a finished stream
                self._retire(s)
            else:
                with self._lock:
                    self._active.append(s)

    def _propose(self, s: _Stream) -> np.ndarray:
        """Draft tokens for one stream, capped by the step's usable
        budget: emissions left before max_new, positions left before
        max_len, and the engine's draft depth."""
        room = min(s.max_new - len(s.generated) - 1,
                   self._max_len - s.length - 1, self._spec_k)
        if room < 1:
            return np.empty(0, np.int32)
        ctx = np.concatenate(
            [s.prompt, np.asarray(s.generated, np.int32)]) \
            if s.generated else s.prompt
        d = np.asarray(self._proposer.propose(ctx, room), np.int32)
        return d[:room]

    def _decode_step(self):
        # chaos injection point: MXNET_CHAOS_SLOW_RANK stretches every
        # step while the heartbeat stays fresh — the straggler the SLO
        # fast-window burn alert must catch before conviction would
        get_chaos().on_decode_step()
        if self._spec_k:
            # a draft continues the tokens a stream has DELIVERED
            self._drain("verify")
            with self._lock:
                streams = list(self._active)
            if not streams:
                return
            drafts = {s.sid: self._propose(s) for s in streams}
            if any(d.size for d in drafts.values()):
                with profiler.scope("serving.step", "serving",
                                    args={"active": len(streams),
                                          "verify": True}):
                    return self._verify_step(drafts)
            # nothing proposed anywhere: the plain one-token step IS
            # the zero-draft verify step (bit-identically, greedy and
            # temperature alike) at a fraction of the compute
        with profiler.scope("serving.step", "serving",
                            args={"active": len(self._active)}):
            self._plain_step()

    @staticmethod
    def _sids(streams):
        """Span arg: the batch's stream ids, or their count where the
        batch is wide."""
        if len(streams) > 8:
            return len(streams)
        return " ".join(str(s.sid) for s in streams)

    def _verify_step(self, drafts: Dict[int, np.ndarray]):
        """One speculative scheduling step: feed every active stream
        its pending token plus its draft window, score all positions
        in ONE multi-query program, commit the longest verified prefix
        (plus the bonus emission at the first mismatch) and roll back
        pages that held only rejected tokens."""
        from .io import stage_array
        from .kv_cache import trim_blocks

        t0 = time.perf_counter()
        for s in list(self._active):
            if s in self._active:
                w = 1 + len(drafts.get(s.sid, ()))
                self._ensure_capacity(s, ahead=w)
        if self._prefix is not None:
            for s in list(self._active):
                if s in self._active:
                    self._maybe_cow(s)
        with self._lock:
            streams = list(self._active)
        if not streams:
            return
        n = len(streams)
        W = self._spec_k + 1
        bb = self._bucket(self._decode_buckets, n, "active streams")
        mb = self._bucket(self._cache_buckets,
                          max(len(s.blocks) for s in streams),
                          "cache blocks")
        exe = self._exe("verify", bb, mb, W)
        with profiler.scope("serving.stage", "serving",
                            args={"sids": self._sids(streams),
                                  "active": n}):
            tokens = np.zeros((bb, W), np.int32)
            positions = np.zeros((bb, W), np.int32)
            start = np.zeros((bb,), np.int32)
            lengths = np.zeros((bb,), np.int32)
            table = np.zeros((bb, mb), np.int32)
            temps = np.zeros((bb,), np.float32)
            seeds = np.zeros((bb,), np.int32)
            steps0 = np.zeros((bb,), np.int32)
            fed: List[np.ndarray] = []
            proposed = 0
            for i, s in enumerate(streams):
                d = drafts.get(s.sid)
                if d is None:  # admitted after the propose pass
                    d = np.empty(0, np.int32)
                w = 1 + len(d)
                row = np.concatenate(
                    [np.asarray([s.next_token], np.int32), d])
                fed.append(row)
                proposed += len(d)
                tokens[i, :w] = row
                # pad rows keep in-range positions (their pos-embed
                # rows are garbage anyway); their K/V writes route to
                # the scratch page because lengths[i] stops at the
                # live window
                positions[i] = np.minimum(s.length + np.arange(W),
                                          self._max_len - 1)
                start[i] = s.length
                lengths[i] = s.length + w
                table[i, :len(s.blocks)] = s.blocks
                temps[i] = s.temp
                seeds[i] = s.seed
                steps0[i] = s.length  # row j keys position length + j
            dev = self._device
            feeds = (stage_array(tokens, dev),
                     stage_array(positions, dev),
                     stage_array(start, dev), stage_array(lengths, dev),
                     stage_array(table, dev), stage_array(temps, dev),
                     stage_array(seeds, dev), stage_array(steps0, dev))
            extra = self._runtime_args(streams, bb, mb)
        self._count("context_tokens", int(lengths.sum()))
        with profiler.scope(f"serving.verify_step.b{bb}x{mb}",
                            "serving",
                            args={"active": n, "batch": bb,
                                  "blocks": mb, "window": W}):
            emit, self._pools = exe(self._params, *feeds, self._pools,
                                    *extra)
        # the staged inputs die here, as call temporaries would: freeing
        # device arrays lets other threads run, and WHERE that happens
        # decides whether a caller's next request makes the next
        # admission (kept to the function's end, ttft halved)
        del feeds, extra
        with profiler.scope("serving.d2h_sync", "serving",
                            args={"active": n}):
            emit = np.asarray(emit)  # ONE (B, W) D2H for k+1 tokens
        self._count("d2h_syncs")
        t_done = time.perf_counter()
        span_args = {"active": n, "retired": 0}
        with profiler.scope("serving.absorb", "serving", args=span_args):
            step_ms = (t_done - t0) * 1e3
            self._count("steps")
            self._count("stream_steps", n)
            self._count("spec_steps")
            self._count("spec_proposed", proposed)
            self._metrics.observe("step_ms", step_ms)
            profiler.observe("serving.decode_step_ms", step_ms)
            # the batch program's FLOPs, split evenly across the riders
            fl = self._exe_flops.get(("verify", bb, mb, W), 0.0) / n
            retired = []
            for i, s in enumerate(streams):
                d = fed[i][1:]
                t = 0
                for j in range(len(fed[i])):
                    tok = int(emit[i, j])
                    # every emission up to and including the first
                    # mismatch is an exact sample for its own slot
                    s.generated.append(tok)
                    t += 1
                    if len(s.generated) >= s.max_new or \
                            (s.eos is not None and tok == s.eos):
                        break
                    if j < len(d) and tok != int(d[j]):
                        break
                s.length += t
                s.next_token = s.generated[-1]
                self._count("tokens", t)
                self._count("spec_accepted", t - 1)
                s.cost.tokens += t  # same sites as the engine counters
                s.cost.spec_accepted += t - 1
                s.cost.decode_steps += 1
                s.cost.d2h_syncs += 1
                s.cost.flops_est += fl
                if s.await_first:
                    s.await_first = False
                    ttft = (t_done - s.t_submit) * 1e3
                    self._metrics.observe("ttft_ms", ttft)
                    profiler.observe("serving.ttft_ms", ttft)
                    self._metrics.observe("ttft_hit_ms", ttft)
                    profiler.observe("serving.ttft_hit_ms", ttft)
                    self._slo.observe_ttft(s.slo_class, ttft)
                per_tok = step_ms / t
                for _ in range(t):
                    self._metrics.observe("time_per_token_ms", per_tok)
                    profiler.observe("serving.time_per_token_ms", per_tok)
                    self._slo.observe_tpt(s.slo_class, per_tok)
                # rejected-token rollback: pages past the committed tail
                # (+ the pending token's slot) held only rejected writes
                keep, surplus = trim_blocks(s.blocks, s.length + 1,
                                            self._kv_block)
                if surplus:
                    s.cost.book_pages(len(s.blocks))
                    s.blocks = keep
                    self._release_pages(surplus)
                    self._count("spec_pages_rolled_back", len(surplus))
                if s.trace is not None:
                    profiler.add_trace_event(
                        "serving.verify_step", t0, t_done - t0,
                        s.trace.child(), cat="serving",
                        args={"sid": s.sid, "position": s.length,
                              "batch": bb, "active": n,
                              "drafts": int(len(d)), "accepted": t - 1})
                if s.done():
                    retired.append(s)
            if retired:
                with self._lock:
                    for s in retired:
                        self._active.remove(s)
                for s in retired:
                    self._retire(s)
            span_args["retired"] = len(retired)

    def _plain_step(self):
        """Dispatch one decode step and THEN fetch the one before it.

        The batch is composed from the scheduling state: every active
        stream at its scheduled length, a row's token taken from the
        device where no one has read it yet (``_feed_exe``).  A stream
        whose tokens in flight are its last by count leaves the batch
        here, without a token read.  Then the oldest programs' tokens
        are fetched and booked, up to the previous decode step's —
        while the chip runs the step just queued.  Where the loop may
        not run ahead (``_hold_back``) it drains first and fetches this
        step's tokens at once: the synchronous loop."""
        from .io import stage_array

        t0 = time.perf_counter()
        why = self._hold_back(self._active)
        if why:
            self._drain(why)
        for s in list(self._active):
            if s in self._active:
                self._ensure_capacity(s)
        if self._prefix is not None:
            for s in list(self._active):
                if s in self._active:
                    self._maybe_cow(s)
        with self._lock:
            streams = list(self._active)
        # the newest step in flight: the rows that rode it feed from
        # its (bb,) tokens, which the feed program takes at ITS bucket
        prev = next((r for r in reversed(self._inflight)
                     if r.prefill is None), None)
        if prev is not None and streams and prev.bb != self._bucket(
                self._decode_buckets, len(streams), "active streams"):
            self._drain("bucket")  # (may end streams that read eos)
            prev = None
            with self._lock:
                streams = list(self._active)
        if not streams:
            return
        bb = self._bucket(self._decode_buckets, len(streams),
                          "active streams")
        n = len(streams)
        mb = self._bucket(self._cache_buckets,
                          max(len(s.blocks) for s in streams),
                          "cache blocks")
        exe = self._exe("decode", bb, mb)
        # the batch program's FLOPs, split evenly across the riders
        fl = self._exe_flops.get(("decode", bb, mb), 0.0) / n
        extra = self._runtime_args(streams, bb, mb)
        ahead = bool(self._inflight)
        span_args = {"sids": self._sids(streams), "active": n,
                     "ahead": ahead}
        dev = self._device
        with profiler.scope("serving.stage", "serving", args=span_args):
            positions = np.zeros((bb, 1), np.int32)
            lengths = np.zeros((bb,), np.int32)
            table = np.zeros((bb, mb), np.int32)
            temps = np.zeros((bb,), np.float32)
            seeds = np.zeros((bb,), np.int32)
            steps = np.zeros((bb,), np.int32)
            for i, s in enumerate(streams):
                positions[i, 0] = s.length
                lengths[i] = s.length + 1
                table[i, :len(s.blocks)] = s.blocks
                temps[i] = s.temp
                seeds[i] = s.seed
                steps[i] = s.length  # the position being sampled FROM
            feeds = (self._token_feed(streams, bb, prev),
                     stage_array(positions, dev),
                     stage_array(lengths, dev), stage_array(table, dev),
                     stage_array(temps, dev), stage_array(seeds, dev),
                     stage_array(steps, dev))
        self._count("steps")
        self._count("stream_steps", n)
        if ahead:
            self._count("steps_run_ahead")
        # the paged kernel's need: the live context this step attends
        self._count("context_tokens", int(lengths.sum()))
        if self._walloc is not None:
            self._count_window_step(streams, lengths)
        with profiler.scope(f"serving.decode_step.b{bb}x{mb}",
                            "serving",
                            args={"active": n, "batch": bb,
                                  "blocks": mb, "ahead": ahead}):
            with self._pools_lock:
                toks, self._pools = exe(self._params, *feeds,
                                        self._pools, *extra)
        # the staged inputs die here, as call temporaries would: freeing
        # device arrays lets other threads run, and WHERE that happens
        # decides whether a caller's next request makes the next
        # admission (kept to the function's end, ttft halved)
        del feeds
        # the scheduling state moves NOW: the next batch is composed
        # from it, whether or not this step's tokens have been read
        rec = _Flight(toks, streams, t0, bb, fl)
        self._inflight.append(rec)
        done = []
        for i, s in enumerate(streams):
            s.length += 1
            s.ahead += 1
            s.feed = (toks, i)
            if s.last_by_count():
                done.append(s)
        if done:
            with self._lock:
                for s in done:
                    self._active.remove(s)
        if why:
            self._fetch_all()
            return
        while self._inflight[0] is not rec:
            if self._fetch_one().prefill is None:
                break

    def _token_feed(self, streams, bb: int, prev: Optional[_Flight]):
        """The staged ``(bb, 1)`` token feed of a decode step.  A row
        whose token the scheduler knows takes it from the host; one
        that rode ``prev`` (the step in flight) takes its row of that
        step's tokens, one admitted since its prefill's first token —
        both still on the device, gathered there by ``_feed_exe``."""
        from .io import stage_array

        tokens = np.zeros((bb,), np.int32)
        # where row i's token lies in concat(prev, firsts, host)
        index = np.arange(bb + _FIRSTS_AHEAD, 2 * bb + _FIRSTS_AHEAD,
                          dtype=np.int32)
        firsts = []
        for i, s in enumerate(streams):
            if s.feed is None:
                tokens[i] = s.next_token
            elif prev is not None and s.feed[0] is prev.toks:
                index[i] = s.feed[1]
            else:
                index[i] = bb + len(firsts)
                firsts.append(s.feed[0])
        if prev is None and not firsts:
            return stage_array(tokens[:, None], self._device)
        host = stage_array(tokens, self._device)
        firsts += [self._no_first] * (_FIRSTS_AHEAD - len(firsts))
        return self._feed_exe(bb)(
            host if prev is None else prev.toks, tuple(firsts), host,
            stage_array(index, self._device))

    def _count_prompt_tiles(self, n: int, tp: int):
        """The key tiles, a layer and head, that a whole prompt's
        attention kernels walked over its ``n`` rows and left out of
        its bucket of ``tp`` for knowing its length; of the walked
        ones, those that took a masked body; and the scores the
        schedule computed beside the pairs the bands hold."""
        from .ops import pallas_kernels as pk

        if not (self._prompt_layers and pk.enabled()):
            return
        walked = skipped = masked = computed = needed = 0
        for (window, latent), layers in self._prompt_layers.items():
            w, sk = pk.prompt_tile_visits(n, tp, window, latent)
            m, c, nd = pk.prompt_tile_work(n, tp, window, latent)
            walked += layers * w
            skipped += layers * sk
            masked += layers * m
            computed += layers * c
            needed += layers * nd
        self._count("prefill_tiles_walked", walked)
        self._count("prefill_tiles_skipped", skipped)
        self._count("prefill_tiles_masked", masked)
        self._count("prefill_scores_computed", computed)
        self._count("prefill_scores_needed", needed)

    def _count_window_step(self, streams, lengths):
        """A decode step's need in the windowed layers (the context
        each row's window holds), and the pages its rows hold in the
        windowed pools beside those they hold in the ordinary ones."""
        self._count("window_context_tokens",
                    int(np.minimum(lengths, self._window).sum()))
        self._count("window_page_steps",
                    sum(len(s.wblocks) - s.wfirst for s in streams))
        self._count("page_steps", sum(len(s.blocks) for s in streams))

    def _book_step(self, rec: _Flight, toks: np.ndarray,
                   t_done: float) -> int:
        """The delivery half of one decode step, when its tokens are
        fetched (under ``serving.absorb``): per-stream token append,
        full-hit TTFT, trace spans, retirement with the futures'
        callbacks.  A row whose stream had already read its ``eos``
        when this step was dispatched past it is an overshoot: the
        token is dropped.  Returns how many retired."""
        streams, bb, fl, n = rec.streams, rec.bb, rec.fl, len(rec.streams)
        # the step's wall is the time since the last booking, where one
        # lies behind its dispatch: a token's cadence, not the life of
        # a program that queued behind another
        t0 = max(rec.t0, self._t_booked)
        self._t_booked = t_done
        step_ms = (t_done - t0) * 1e3
        self._metrics.observe("step_ms", step_ms)
        profiler.observe("serving.decode_step_ms", step_ms)
        retired = []
        overshoot = 0
        by_class: Dict[str, int] = {}
        for i, s in enumerate(streams):
            if s.done():
                overshoot += 1
                continue
            tok = int(toks[i])
            s.generated.append(tok)
            s.next_token = tok
            s.ahead -= 1
            if s.feed[0] is rec.toks:
                s.feed = None  # the newest of its tokens: read now
            s.cost.tokens += 1  # same site as the engine counter
            s.cost.decode_steps += 1
            s.cost.d2h_syncs += 1
            s.cost.flops_est += fl
            if s.await_first:
                # fully-cached prompt: the first token came from this
                # decode step — TTFT collapsed to one step's wall
                s.await_first = False
                ttft = (t_done - s.t_submit) * 1e3
                self._metrics.observe("ttft_ms", ttft)
                profiler.observe("serving.ttft_ms", ttft)
                self._metrics.observe("ttft_hit_ms", ttft)
                profiler.observe("serving.ttft_hit_ms", ttft)
                self._slo.observe_ttft(s.slo_class, ttft)
            by_class[s.slo_class] = by_class.get(s.slo_class, 0) + 1
            if s.trace is not None:
                # every decode-step batch this stream rode in becomes
                # one child span — a request's flame graph shows its
                # whole token cadence, including steps it shared
                profiler.add_trace_event(
                    "serving.decode_step", t0, t_done - t0,
                    s.trace.child(), cat="serving",
                    args={"sid": s.sid, "position": s.length - s.ahead,
                          "batch": bb, "active": n})
            if s.done():
                retired.append(s)
        # every row's cadence is the step's: one locked insert a
        # registry (a class, for the SLO windows), not three a row
        if n > overshoot:
            self._metrics.observe("time_per_token_ms", step_ms,
                                  n - overshoot)
            profiler.observe("serving.time_per_token_ms", step_ms,
                             n - overshoot)
            for slo_class, rows in by_class.items():
                self._slo.observe_tpt(slo_class, step_ms, n=rows)
        self._count("tokens", n - overshoot)
        if overshoot:
            self._count("overshoot_row_steps", overshoot)
        if self._walloc is not None:
            # a step that carried a row's window past a page boundary
            # leaves a page wholly behind it: back to the windowed pool
            # (by the SCHEDULED length: a step in flight reads the page
            # ids it was staged with, and a page given back is written
            # again only by a program dispatched after it)
            behind = [(s, self._window_first(s.length)) for s in streams
                      if s.wblocks]
            if any(first > s.wfirst for s, first in behind):
                with profiler.scope("serving.window_release", "serving"):
                    self._count("window_pages_released", sum(
                        self._release_window(s, first)
                        for s, first in behind))
        for s in retired:
            self._end(s)
        return len(retired)


# ---------------------------------------------------------------------------
# fleet duty: the replica harness
# ---------------------------------------------------------------------------


class ReplicaHarness:
    """One engine dressed for fleet duty (see ``mxnet_tpu.fleet``).

    A :class:`fleet.Router` replica needs four things from whatever
    engine it wraps, and this adapter is the one place they are wired:

    * a **uniform submit surface** — :meth:`submit_infer` for
      :class:`InferenceEngine`, :meth:`submit_decode` for
      :class:`DecodeEngine` (the wrong kind refuses loudly);
    * the **inflight() snapshot** — what would die with this engine;
    * the **drain/resume hooks** the rolling weight swap drives;
    * :meth:`swap` — load the newest committed, checksum-verified
      weights from a checkpoint root (``checkpoint.load_latest_params``
      — a training run's ``MXNET_CKPT_DIR`` or a
      ``checkpoint.publish_params`` output), install them through the
      engine's ``swap_params``, re-warm every executable, re-admit.
      On ANY failure the engine resumes with its OLD weights — a swap
      never leaves a replica refusing traffic.
    """

    #: replica roles a disaggregated fleet may assign (``mixed`` is
    #: the classic do-everything replica and the default)
    ROLES = ("prefill", "decode", "mixed")

    def __init__(self, engine):
        if not isinstance(engine, (InferenceEngine, DecodeEngine)):
            raise MXNetError(
                f"ReplicaHarness wraps an InferenceEngine or a "
                f"DecodeEngine; got {type(engine)}")
        self.engine = engine
        self.kind = "decode" if isinstance(engine, DecodeEngine) \
            else "infer"
        self.weights_step = -1  # last swap's checkpoint step
        self.role = None  # disagg role; None = roles never enabled
        # /statusz: the harness view supersedes the bare engine's —
        # same stats plus kind/inflight/weights_step (what fleet_top
        # renders per replica)
        profiler.register_statusz("engine", self.stats)

    # -- uniform submit -------------------------------------------------
    def submit_infer(self, inputs, trace=None) -> Future:
        if self.kind != "infer":
            raise MXNetError("replica serves decode requests; "
                             "an inference request cannot ride it")
        return self.engine.submit(inputs, trace=trace)

    def submit_decode(self, prompt, max_new_tokens=32, temperature=None,
                      eos_id=None, seed=None, trace=None,
                      slo_class="interactive", tenant=None,
                      adapter=None) -> Future:
        if self.kind != "decode":
            raise MXNetError("replica serves inference requests; "
                             "a decode request cannot ride it")
        return self.engine.submit(prompt, max_new_tokens,
                                  temperature=temperature, eos_id=eos_id,
                                  seed=seed, trace=trace,
                                  slo_class=slo_class, tenant=tenant,
                                  adapter=adapter)

    # -- disaggregated prefill/decode -----------------------------------
    def set_role(self, role: str):
        """Assign this replica's disaggregated-serving role.  The
        router flips roles only through its drain machinery (quiesce →
        flip → warm), so by the time this runs the engine is idle; the
        flip itself is just bookkeeping plus a warmup so the first
        request in the new role never pays a compile."""
        if role not in self.ROLES:
            raise MXNetError(
                f"replica role {role!r} must be one of {self.ROLES}")
        if self.kind != "decode":
            raise MXNetError(
                "replica roles apply to decode replicas only; an "
                "InferenceEngine replica has no prefill/decode split")
        self.role = role
        profiler.inc_counter("serving.role_flips")
        self.engine.warmup()

    def submit_prefill_export(self, prompt, max_new_tokens=32,
                              temperature=None, eos_id=None, seed=None,
                              trace=None, slo_class="interactive",
                              tenant=None, adapter=None) -> Future:
        """Disagg phase 1: admission + prefill + first token, then the
        KV pages leave the pool as a migration payload (the Future's
        result — see :meth:`DecodeEngine.submit` ``prefill_only``)."""
        if self.kind != "decode":
            raise MXNetError("replica serves inference requests; "
                             "a prefill-export request cannot ride it")
        if self.role == "decode":
            raise MXNetError(
                "replica role is 'decode' — prefill-export requests "
                "must route to a prefill-role replica")
        return self.engine.submit(prompt, max_new_tokens,
                                  temperature=temperature, eos_id=eos_id,
                                  seed=seed, trace=trace,
                                  slo_class=slo_class, tenant=tenant,
                                  adapter=adapter, prefill_only=True)

    def submit_import(self, meta: dict, arrays, trace=None) -> Future:
        """Disagg phase 2: splice a migrated stream's KV pages into
        this replica's pool and continue its decode (see
        :meth:`DecodeEngine.import_stream`)."""
        if self.kind != "decode":
            raise MXNetError("replica serves inference requests; "
                             "a KV-page import cannot ride it")
        if self.role == "prefill":
            raise MXNetError(
                "replica role is 'prefill' — migrated streams must "
                "land on a decode-role replica")
        return self.engine.import_stream(meta, arrays, trace=trace)

    # -- multi-tenant adapters -------------------------------------------
    def publish_adapter(self, name, a, b, alpha=None) -> int:
        """Hot LoRA publish (no drain) — see
        :meth:`DecodeEngine.publish_adapter`."""
        if self.kind != "decode":
            raise MXNetError(
                "adapters ride the decode engine; an InferenceEngine "
                "replica has no adapter pool")
        return self.engine.publish_adapter(name, a, b, alpha=alpha)

    def retire_adapter(self, name) -> bool:
        if self.kind != "decode":
            raise MXNetError(
                "adapters ride the decode engine; an InferenceEngine "
                "replica has no adapter pool")
        return self.engine.retire_adapter(name)

    # -- router-facing state --------------------------------------------
    def inflight(self) -> int:
        return self.engine.inflight()

    def drain(self, timeout: float = 30.0) -> int:
        return self.engine.drain(timeout=timeout)

    def resume(self):
        self.engine.resume()

    def stats(self) -> dict:
        out = self.engine.stats()
        out["kind"] = self.kind
        out["inflight"] = self.inflight()
        out["weights_step"] = self.weights_step
        if self.role is not None:  # roles never enabled → not exported
            out["role"] = self.role
        return out

    # -- rolling weight swap --------------------------------------------
    def swap(self, ckpt_dir: str, drain_timeout: float = 60.0) -> dict:
        """drain → load committed manifest (checksum-verified) → install
        → warmup → re-admit.  Returns the timing/step report the router
        aggregates.  Raises (with the engine RESUMED on old weights)
        when the drain deadline passes with requests still in flight or
        the checkpoint refuses verification."""
        from .checkpoint import load_latest_params

        report = {"kind": self.kind}
        t0 = time.perf_counter()
        left = self.drain(timeout=drain_timeout)
        report["drain_ms"] = (time.perf_counter() - t0) * 1e3
        try:
            if left:
                raise MXNetError(
                    f"weight swap aborted: {left} request(s) still in "
                    f"flight after the {drain_timeout:.0f}s drain "
                    "deadline (router should have quiesced this "
                    "replica first)")
            t1 = time.perf_counter()
            params, step, path = load_latest_params(ckpt_dir)
            report["load_ms"] = (time.perf_counter() - t1) * 1e3
            t2 = time.perf_counter()
            old = self.engine.get_params()  # rollback anchor
            installed = False
            try:
                self.engine.swap_params(params)
                installed = True
                self.engine.warmup()
            except BaseException:
                if installed:
                    # warmup died AFTER the install: restore the old
                    # weights before resuming, or re-admitted traffic
                    # would silently serve the new version (and lazily
                    # recompile in the serving path) while the router
                    # believes the swap never happened
                    self.engine.swap_params(old)
                    self.engine.warmup()
                raise
            report["warmup_ms"] = (time.perf_counter() - t2) * 1e3
            report["step"] = self.weights_step = step
            report["path"] = path
            profiler.inc_counter("serving.weight_swaps")
            profiler.set_gauge("serving.weights_step", float(step))
        finally:
            self.resume()
        report["total_ms"] = (time.perf_counter() - t0) * 1e3
        return report

    def close(self, timeout: float = 30.0):
        self.engine.close(timeout=timeout)
