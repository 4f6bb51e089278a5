"""Gradient communication scheduler — bucketed, overlapped push/pull.

The reference's dependency engine existed so parameter push/pull could
proceed asynchronously while compute continued (SURVEY §2 engine
layer; ps-lite pushes keys independently with priorities).  This
module restores that capability on the TPU-native stack: instead of
one blocking collective / TCP round-trip per key in key order,
gradients are

* **bucketed** — many small keys coalesce into one flat fixed-size
  bucket (``MXNET_KVSTORE_BUCKET_BYTES``, default 4 MiB) so ONE
  collective / wire frame moves many keys.  The pack/unpack layout is
  a deterministic function of the submission order (offset = running
  sum of flat sizes), so ``pack → elementwise sum → unpack`` is
  bitwise-identical to the per-key sum — buckets change the transport,
  never the numerics;
* **asynchronous** — a background comm thread consumes sealed buckets
  and returns :class:`CommHandle`\\ s, so the collective / PS
  round-trip (and the D2H staging it needs) overlaps the remaining
  backward/optimizer work on the main thread.  Consumers wait only at
  the true dependency point (``wait(key)`` / ``drain()``);
* **priority-ordered** — sealed buckets are consumed from a priority
  heap (the kvstore ``priority=`` argument finally means something).
  Backends whose transport is a *collective* must instead launch in
  strict submission order (``strict_order=True``): every rank's comm
  thread has to issue the same collective sequence, and a timing-
  dependent heap pop could reorder ranks against each other.  There
  the priority ordering is the caller's push order (model.py pushes in
  reverse-layer priority already);
* optionally **compressed on the wire** — ``MXNET_KVSTORE_GRAD_DTYPE``
  = ``bf16``/``fp16`` sends float32 buckets as 2-byte floats and
  accumulates in float32 on the receiving side (DDP-style gradient
  compression; see README "Gradient communication" for when this is
  safe).

Instrumented with the PR 2 observability layer: every launched bucket
emits a ``kvstore.bucket`` span (bytes, keys, seq, priority, wire
dtype) on the comm thread, the ``kvstore.inflight`` gauge tracks
queued+in-flight buckets, and ``kvstore.wire_bytes`` counts payload
bytes handed to the transport — so a merged 2-rank trace visibly shows
comm running under compute.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import profiler as _prof
from .base import MXNetError, get_env

__all__ = ["bucket_bytes", "wire_dtype", "overlap_enabled",
           "inflight_window", "pack_bucket", "unpack_bucket",
           "BucketEntry", "CommBucket", "CommHandle", "CommScheduler",
           "finish_all", "make_ps_launch", "MAX_BUCKET_KEYS"]

# hard cap on keys per bucket: one bucket becomes at most one wire
# frame per shard, and the frame's key count is a u16 — cap with wide
# margin (big-key splits add a handful of extra items per frame)
MAX_BUCKET_KEYS = 8192


# -- env knobs (registered in mxnet_tpu.config) -------------------------
def bucket_bytes() -> int:
    """Bucket capacity in bytes (MXNET_KVSTORE_BUCKET_BYTES, 4 MiB)."""
    return int(get_env("MXNET_KVSTORE_BUCKET_BYTES", 4 << 20, int))


def wire_dtype() -> Optional[np.dtype]:
    """Wire dtype for float32 gradient payloads, or None for native.

    MXNET_KVSTORE_GRAD_DTYPE: 'fp32' (default, no compression),
    'bf16'/'bfloat16', 'fp16'/'float16'.  Read per bucket launch so
    tests and long-running jobs can flip it at runtime."""
    name = str(get_env("MXNET_KVSTORE_GRAD_DTYPE", "fp32", str)).lower()
    if name in ("fp32", "float32", "f32", ""):
        return None
    if name in ("bf16", "bfloat16"):
        import ml_dtypes  # jax dependency — always present

        return np.dtype(ml_dtypes.bfloat16)
    if name in ("fp16", "float16", "f16"):
        return np.dtype(np.float16)
    raise MXNetError(
        f"MXNET_KVSTORE_GRAD_DTYPE={name!r} — want fp32, bf16 or fp16")


def overlap_enabled() -> bool:
    """MXNET_KVSTORE_OVERLAP: 1 (default) = async bucketed comm; 0 =
    the pre-scheduler blocking per-key path (debugging)."""
    return int(get_env("MXNET_KVSTORE_OVERLAP", 1, int)) != 0


def inflight_window() -> int:
    """Max buckets in flight per transport connection
    (MXNET_KVSTORE_INFLIGHT, default 4)."""
    return max(1, int(get_env("MXNET_KVSTORE_INFLIGHT", 4, int)))


# -- deterministic flat pack/unpack -------------------------------------
class BucketEntry:
    """One key's slot in a bucket: flat [offset, offset+size) slice."""

    __slots__ = ("key", "shape", "dtype", "size", "offset", "priority")

    def __init__(self, key, shape, dtype, size, offset, priority):
        self.key = key
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.size = int(size)
        self.offset = int(offset)
        self.priority = priority


def pack_bucket(arrays):
    """Flatten + concatenate same-dtype device arrays into ONE flat
    array (a jax array if any input is one).  The layout is purely the
    submission order, so it is bitwise-deterministic across runs and
    identical on every rank that submits the same sequence."""
    import jax.numpy as jnp

    if len(arrays) == 1:
        return jnp.ravel(arrays[0])
    return jnp.concatenate([jnp.ravel(a) for a in arrays])


def unpack_bucket(flat, entries: List[BucketEntry]):
    """Slice a flat (summed) bucket back into per-key arrays in the
    entry dtype/shape.  Inverse of :func:`pack_bucket` given the same
    layout; with a native-dtype wire the round trip is bitwise exact."""
    out = []
    for e in entries:
        out.append(flat[e.offset:e.offset + e.size]
                   .reshape(e.shape).astype(e.dtype))
    return out


def make_ps_launch(client, sync: bool = False):
    """Parameter-server bucket transport for :class:`CommScheduler`:
    ONE D2H of the (optionally wire-compressed) packed bucket, then one
    multi-key frame per shard through the windowed connection pipeline;
    returns the collect-later finisher.  The ONE implementation shared
    by DistKVStore and the tests, so they all
    exercise the code path the kvstore actually runs."""
    def launch(bucket):
        flat = pack_bucket(bucket.arrays)
        wdt = bucket.wire  # latched at seal time — see _seal_locked
        if wdt is not None and np.dtype(flat.dtype) == np.float32:
            flat = flat.astype(wdt)
        host = np.asarray(flat)  # one D2H for the whole bucket
        entries = [(e.key, host[e.offset:e.offset + e.size]
                    .reshape(e.shape)) for e in bucket.entries]
        fins = client.begin_push_multi(entries, sync=sync)
        return lambda: finish_all(fins)

    return launch


def finish_all(finishers):
    """Run EVERY finisher, then raise the first error (abandoning a
    finisher would leave its connection lock held / response undrained
    — same contract as ShardedPSClient._fan_out)."""
    first_err = None
    for fin in finishers:
        try:
            fin()
        except Exception as e:  # noqa: BLE001 — drain them all
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err


# -- scheduler ----------------------------------------------------------
class CommHandle:
    """Completion handle for one bucket; shared by all its keys."""

    __slots__ = ("_done", "_exc")

    def __init__(self):
        self._done = threading.Event()
        self._exc: Optional[BaseException] = None

    def _set(self, exc=None):
        self._exc = exc
        self._done.set()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: float = 630.0):
        """Block until the bucket's transport completed; re-raise any
        comm-thread failure at the caller (the true dependency point)."""
        if not self._done.wait(timeout):
            raise MXNetError(
                f"gradient comm bucket not completed within {timeout}s "
                "(dead peer or stuck parameter server?)")
        if self._exc is not None:
            raise self._exc


class CommBucket:
    """One sealed unit of communication: layout + device arrays.

    ``wire`` is the wire dtype LATCHED at seal time on the submitting
    thread: every rank seals the same bucket sequence, so a runtime
    flip of MXNET_KVSTORE_GRAD_DTYPE lands on the same bucket boundary
    everywhere — reading the env on the comm thread instead would let
    rank A launch collective N compressed while rank B still had
    fp32-era buckets queued."""

    __slots__ = ("entries", "arrays", "nbytes", "priority", "seq",
                 "handle", "wire", "t_launch")

    def __init__(self, entries, arrays, nbytes, priority, seq, handle,
                 wire=None):
        self.entries = entries
        self.arrays = arrays
        self.nbytes = nbytes
        self.priority = priority
        self.seq = seq
        self.handle = handle
        self.wire = wire
        self.t_launch = 0.0


class _OpenBucket:
    __slots__ = ("entries", "arrays", "nbytes", "priority", "handle")

    def __init__(self):
        self.entries: List[BucketEntry] = []
        self.arrays: List[Any] = []
        self.nbytes = 0
        self.priority = 0
        self.handle = CommHandle()


class CommScheduler:
    """Background comm thread over a transport ``launch`` callable.

    ``launch(bucket)`` runs on the comm thread; it either completes
    the transport and returns None, or returns a zero-arg *finisher*
    (collect-later half of a pipelined send) which the scheduler
    drains under the in-flight window — up to ``window`` buckets ride
    the wire concurrently, and the depth is exported as the
    ``kvstore.inflight`` gauge.

    ``strict_order=True`` forces launches in submission order —
    REQUIRED when the transport is a collective: every rank must issue
    the identical collective sequence, and a priority pop whose heap
    contents differ by thread timing would deadlock/cross-sum ranks.
    With ``strict_order=False`` (point-to-point parameter-server
    transport) sealed buckets launch in (-priority, seq) order.
    """

    def __init__(self, launch: Callable[[CommBucket], Optional[Callable]],
                 *, strict_order: bool = False,
                 max_bucket_bytes: Optional[int] = None,
                 window: Optional[int] = None,
                 name: str = "mxnet_tpu-kvstore-comm"):
        self._launch = launch
        self._strict = strict_order
        # read once: an env lookup+parse per pushed key would sit on
        # the exact hot path this scheduler exists to speed up (and a
        # runtime bucket-size flip is not rank-safe anyway, unlike the
        # per-seal wire_dtype latch)
        self._max_bytes = (bucket_bytes() if max_bucket_bytes is None
                           else max_bucket_bytes)
        self._window = window
        self._cond = threading.Condition()
        self._heap: List[Tuple[Any, int, CommBucket]] = []
        self._open: Dict[str, _OpenBucket] = {}  # dtype-name → open
        self._handles: Dict[Any, CommHandle] = {}  # key → latest handle
        self._outstanding: List[CommHandle] = []
        self._inflight: deque = deque()  # (bucket, finisher)
        self._seq = 0
        self._stop = False
        self._failed: Optional[BaseException] = None
        # telemetry: comm-thread busy seconds vs main-
        # thread blocked-waiting seconds → overlap ratio
        self.busy_s = 0.0
        self.blocked_s = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._thread.start()
        # best-effort flush at interpreter exit: without it the daemon
        # comm thread can be killed mid-frame and a job's final pushes
        # silently never land (flows that end in barrier()/pull() have
        # already drained; this covers push-and-exit ones).  close()
        # unregisters, so a closed scheduler is fully collectable.
        import atexit

        atexit.register(self._atexit_close)

    def _atexit_close(self):
        try:
            self.drain(timeout=10.0)
        except Exception:  # noqa: BLE001 — exiting anyway; a dead peer
            pass           # must not wedge interpreter shutdown
        with self._cond:
            self._stop = True
            self._cond.notify_all()

    # -- producer side --------------------------------------------------
    def submit(self, key, array, priority: int = 0) -> CommHandle:
        """Add one key's (locally-merged, already-rescaled) gradient to
        the open bucket of its dtype; seal + enqueue when full.  Seal
        points are a pure function of the submission sequence, so every
        rank that pushes the same keys in the same order seals the same
        buckets — the invariant collective transports rely on."""
        dtype = np.dtype(array.dtype)
        nbytes = int(getattr(array, "nbytes",
                             np.size(array) * dtype.itemsize))
        max_bytes = self._max_bytes
        with self._cond:
            if self._failed is not None:
                raise MXNetError(
                    "gradient comm thread failed; no further pushes "
                    f"accepted: {self._failed}") from self._failed
            if self._stop:
                raise MXNetError("CommScheduler is closed")
            group = dtype.name
            ob = self._open.get(group)
            if ob is not None and ob.entries \
                    and ob.nbytes + nbytes > max_bytes:
                self._seal_locked(group)
                ob = None
            if ob is None:
                ob = self._open.setdefault(group, _OpenBucket())
            ob.entries.append(BucketEntry(
                key, getattr(array, "shape", ()), dtype,
                int(np.size(array)), ob.nbytes // dtype.itemsize,
                priority))
            ob.arrays.append(array)
            ob.nbytes += nbytes
            ob.priority = max(ob.priority, priority) if len(ob.entries) > 1 \
                else priority
            self._handles[key] = ob.handle
            handle = ob.handle
            # seal on bytes OR entry count: a wire frame's key count is
            # a u16, so a bucket of thousands of tiny keys must split
            # long before it could overflow the protocol
            if ob.nbytes >= max_bytes or len(ob.entries) >= MAX_BUCKET_KEYS:
                self._seal_locked(group)
        return handle

    def flush(self):
        """Seal every open bucket (deterministic group order)."""
        with self._cond:
            for group in sorted(self._open):
                self._seal_locked(group)

    def wait(self, key, timeout: float = 630.0):
        """Flush, then block until ``key``'s latest bucket completed —
        the per-key dependency point ``pull`` sits on."""
        self.flush()
        handle = self._handles.get(key)
        if handle is None or handle.done:
            if handle is not None:
                handle.wait(timeout)  # surface a stored failure
            return
        t0 = time.perf_counter()
        try:
            handle.wait(timeout)
        finally:
            dt = time.perf_counter() - t0
            self.blocked_s += dt
            # goodput decomposition: blocked-on-comm seconds drain
            # into the next fit-step sample as its "comm" slice
            _prof.goodput_tracker().add_comm(dt)

    def drain(self, timeout: float = 630.0):
        """Flush and wait for EVERY outstanding bucket (barrier /
        checkpoint / shutdown sites)."""
        self.flush()
        with self._cond:
            pending = list(self._outstanding)
        t0 = time.perf_counter()
        try:
            for h in pending:
                h.wait(timeout)
        finally:
            dt = time.perf_counter() - t0
            self.blocked_s += dt
            _prof.goodput_tracker().add_comm(dt)
        with self._cond:
            self._outstanding = [h for h in self._outstanding
                                 if not h.done]

    def close(self):
        """Drain, then stop the comm thread (idempotent).  Also drops
        the atexit registration so the scheduler (and everything its
        launch closure pins — e.g. a kvstore's parameter store) becomes
        garbage-collectable."""
        import atexit

        try:
            atexit.unregister(self._atexit_close)
        except Exception:  # noqa: BLE001 — interpreter tearing down
            pass
        try:
            self.drain()
        finally:
            with self._cond:
                self._stop = True
                self._cond.notify_all()
            self._thread.join(timeout=10.0)

    @property
    def depth(self) -> int:
        """Buckets sealed-but-not-completed (queued + in flight)."""
        with self._cond:
            return len(self._heap) + len(self._inflight)

    # -- internals ------------------------------------------------------
    def _seal_locked(self, group: str):
        ob = self._open.pop(group, None)
        if ob is None or not ob.entries:
            return
        seq = self._seq
        self._seq += 1
        # latch the wire dtype NOW (submitting thread): all ranks seal
        # the same bucket sequence, so a runtime MXNET_KVSTORE_GRAD_DTYPE
        # flip takes effect on the same bucket boundary everywhere
        bucket = CommBucket(ob.entries, ob.arrays, ob.nbytes,
                            ob.priority, seq, ob.handle,
                            wire=wire_dtype())
        # strict (collective) transports launch in submission order;
        # point-to-point transports honor priority (higher first)
        sort_key = 0 if self._strict else -int(ob.priority)
        heapq.heappush(self._heap, (sort_key, seq, bucket))
        # prune completed handles here (steady-state training calls
        # wait()/flush() but not drain(), and an append-only list
        # would grow one handle per bucket forever)
        if len(self._outstanding) > 2 * (len(self._heap)
                                         + len(self._inflight) + 4):
            self._outstanding = [h for h in self._outstanding
                                 if not h.done]
        self._outstanding.append(ob.handle)
        _prof.observe("kvstore.bucket_bytes", float(ob.nbytes))
        _prof.set_gauge("kvstore.inflight",
                        len(self._heap) + len(self._inflight))
        self._cond.notify_all()

    def _run(self):
        while True:
            with self._cond:
                while not self._heap and not self._inflight \
                        and not self._stop:
                    self._cond.wait(0.5)
                if self._stop and not self._heap and not self._inflight:
                    return
                bucket = None
                if self._heap:
                    _, _, bucket = heapq.heappop(self._heap)
            if bucket is None:
                # queue idle: drain an in-flight finisher so waiters
                # (and interleaved synchronous ops on the same
                # connections) make progress
                self._drain_one()
                continue
            bucket.t_launch = time.perf_counter()
            try:
                finisher = self._launch(bucket)
            except BaseException as e:  # noqa: BLE001 — a comm failure
                # must surface at wait()/drain(), not kill the thread
                self.busy_s += time.perf_counter() - bucket.t_launch
                self._complete(bucket, exc=e)
                continue
            self.busy_s += time.perf_counter() - bucket.t_launch
            if finisher is None:
                self._complete(bucket)
                continue
            self._inflight.append((bucket, finisher))
            window = (inflight_window() if self._window is None
                      else self._window)
            while len(self._inflight) >= window:
                self._drain_one()

    def _drain_one(self):
        if not self._inflight:
            return
        bucket, finisher = self._inflight.popleft()
        t0 = time.perf_counter()
        try:
            finisher()
        except BaseException as e:  # noqa: BLE001
            self.busy_s += time.perf_counter() - t0
            self._complete(bucket, exc=e)
            return
        # busy_s counts actual work (launch call + finisher call), NOT
        # the time a finisher sat queued behind the window — an
        # overlap ratio divides by it, and queue-idle time would
        # over-report comm utilization.  The span below still covers
        # launch→completion: "bucket in flight" is what a trace shows.
        self.busy_s += time.perf_counter() - t0
        self._complete(bucket)

    def _complete(self, bucket: CommBucket, exc=None):
        dur = time.perf_counter() - bucket.t_launch
        _prof.add_event(
            "kvstore.bucket", bucket.t_launch, dur, "comm",
            args={"keys": len(bucket.entries),
                  "bytes": int(bucket.nbytes),
                  "seq": bucket.seq, "priority": bucket.priority,
                  "wire": bucket.wire.name if bucket.wire is not None
                  else "native",
                  "ok": exc is None})
        _prof.observe("kvstore.bucket_ms", dur * 1e3)
        if exc is not None:
            # poison BEFORE releasing the handle: a waiter that wakes
            # on the failure must not be able to race a fresh submit
            # past the _failed check
            with self._cond:
                self._failed = exc
        bucket.handle._set(exc)
        if exc is not None:
            self._abort_pending(exc)
        with self._cond:
            _prof.set_gauge("kvstore.inflight",
                            len(self._heap) + len(self._inflight))

    def _abort_pending(self, exc):
        """One bucket failed (scheduler already poisoned): fail every
        QUEUED bucket, and DRAIN (not abandon) the in-flight finishers
        — an abandoned finisher would leave its response unread and
        stall every later ticket on that connection (_begin's
        contract).  In-flight buckets whose transport actually
        succeeded complete successfully; their waiters are
        unaffected."""
        with self._cond:
            stranded = [b for _, _, b in self._heap]
            self._heap.clear()
        for b in stranded:
            b.handle._set(MXNetError(
                f"gradient comm aborted by an earlier failure: {exc}"))
        # bounded recursion: each _drain_one pops one finisher; a
        # finisher that fails re-enters here with an empty heap
        while self._inflight:
            self._drain_one()
