"""Profiler — Chrome-trace timing, metrics registry, exporters.

Capability parity with the reference profiler (``src/engine/
profiler.h:20-130`` per-op stats dumped as Chrome tracing JSON,
controlled from ``python/mxnet/profiler.py``): same control surface
(``profiler_set_config`` / ``profiler_set_state`` / ``dump_profile``),
same output format (``chrome://tracing`` JSON).

TPU-first split: per-*kernel* timing lives in XLA, exposed by wrapping
``jax.profiler`` (``start_xla_trace``/``stop_xla_trace`` write a full
XPlane/TensorBoard trace — the modern equivalent of per-op stats);
this module's own events time the *host-visible program units* the
framework actually dispatches (forward / backward / fused step /
update / io / push / pull), which is the granularity a single-XLA-
program design has.  Framework internals mark spans with
``profiler.scope(name, cat, args=...)``: timed into the flight
recorder (and the chrome trace when it runs), and ALSO a
``jax.profiler.TraceAnnotation``, so any running jax trace shows the
span on its thread's line beside the device's ``XLA Ops``, on one
clock; ``args`` (step number, bytes moved, bucket key) render in the
trace viewer's detail pane.

The observability layer on top (the Dapper-style "where did this STEP
go, across every worker" question — Sigelman et al. 2010):

* per-rank traces — every event carries this process's pid; ``dump``
  adds Chrome ``M``-phase process metadata (rank name, sort index) and
  a ``clock_sync`` anchor (wall-clock ↔ perf_counter captured
  back-to-back) so ``tools/trace_merge.py`` can align traces from
  different processes onto one wall-clock timeline viewable in
  Perfetto.  ``dump_rank_trace(dir)`` writes ``trace_rank<N>.json``.
* metrics registry — always-on counters / gauges / histograms
  (``inc_counter`` / ``set_gauge`` / ``observe``); ``metrics_summary``
  adds p50/p90/p99 and per-counter rate-since-reset so the
  benchmark's runners and the reporter share one schema.
* exporters — ``prometheus_text()`` renders the registry in the
  Prometheus text exposition format (real ``histogram``
  ``_bucket``/``_sum``/``_count`` series since PR 12; the pre-PR-12
  ``_p50``/``_p90``/``_p99`` quantile gauges are retired — use
  ``histogram_quantile()``); ``start_reporter(path, interval)``
  appends a JSONL summary line every interval from a daemon thread.

The fleet-era additions (PR 12 — Dapper-style per-REQUEST accounting
across processes, and the "what was this process doing when it died"
question):

* **trace context** — :class:`TraceContext` carries a W3C-traceparent-
  style ``(trace_id, span_id, parent)`` triple; ``wire.py`` ships its
  string form on fleet request/control frames, every tier stamps
  child spans (``trace_span`` / ``add_trace_event``), and
  ``tools/trace_merge.py`` stitches the per-process spans back into
  one tree keyed by trace_id.
* **flight recorder** — an always-on bounded in-memory ring of recent
  spans/events/metric samples (``deque`` append: no locks, no file
  I/O in steady state).  With ``MXNET_FLIGHT_RECORDER_DIR`` set the
  ring ALSO write-throughs into a memory-mapped ring file — mmap
  stores are plain memory writes, and the OS flushes the pages after
  the process dies, so even a ``kill -9``'d replica leaves a readable
  last-N-seconds record.  ``dump_flight_record(reason)`` writes the
  post-mortem JSON; the engine/serving loops, replica conviction,
  DeadRankError, shed bursts and the SIGTERM path call it.
* **goodput / MFU** — :class:`GoodputTracker` turns per-step wall
  samples (io-wait / step / comm / checkpoint-blocking) plus the
  fused program's FLOPs into live ``training.mfu`` /
  ``training.goodput`` gauges and a step-time decomposition that sums
  to the wall clock; elastic recovery books its downtime as
  attributed lost time.
* **ops surface** — ``start_metrics_server`` serves ``/metrics``
  (Prometheus text), ``/statusz`` (JSON: gauges + registered
  providers) and ``/tracez`` (flight-recorder snapshot) from a tiny
  stdlib HTTP server (``MXNET_METRICS_PORT``); ``tools/fleet_top.py``
  polls ``/statusz`` across a fleet.
"""

from __future__ import annotations

import collections
import json
import os
import struct
import threading
import time
import weakref
from contextlib import contextmanager

# host-side only: neither import opens a backend (the package has
# imported jax before this module either way — context.py does)
import jax.monitoring as _monitoring
from jax.profiler import TraceAnnotation as _TraceAnnotation

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "scope", "name_thread", "add_event", "record_program",
           "compile_events", "COMPILE_EVENT_KINDS", "start_xla_trace",
           "stop_xla_trace", "Profiler", "MetricsRegistry", "inc_counter",
           "observe", "metrics_summary", "reset_metrics", "set_gauge",
           "inc_gauge", "gauge_generation", "process_rank",
           "dump_rank_trace", "prometheus_text", "start_reporter",
           "Reporter", "TraceContext", "trace_span", "add_trace_event",
           "trace_point", "make_trace", "clock_anchor", "FlightRecorder",
           "flight_recorder", "init_flight_recorder", "flight_snapshot",
           "dump_flight_record", "read_flight_file", "GoodputTracker",
           "goodput_tracker", "device_peak_flops", "peak_flops",
           "PEAK_BY_DEVICE_KIND", "MetricsServer",
           "start_metrics_server", "maybe_start_metrics_server",
           "metrics_server_running",
           "register_statusz", "unregister_statusz", "statusz",
           "program_scopes", "scope_tables", "holder_scopes",
           "hold_programs", "retire_programs"]


def process_rank() -> int:
    """This process's rank in a distributed run.

    The launcher (tools/launch.py) exports MXNET_WORKER_ID before any
    jax state exists, so the env var is authoritative and reading it
    never forces backend initialization.  Single process → 0."""
    try:
        return int(os.environ.get("MXNET_WORKER_ID") or 0)
    except ValueError:
        return 0


class Profiler:
    """Collects Chrome-trace 'X' (complete) events."""

    def __init__(self):
        self._events = []
        self._lock = threading.Lock()
        self._running = False
        self._filename = "profile.json"
        self._mode = "symbolic"  # 'symbolic' | 'all' (reference modes)
        # clock-sync anchor: the same instant on both clocks, so a
        # merger can map this trace's perf_counter-relative ts onto the
        # shared wall clock (NTP-level alignment across ranks)
        self._t0 = time.perf_counter()
        self._wall0 = time.time()

    # -- control (reference: profiler.py profiler_set_config/state) ----
    def set_config(self, mode="symbolic", filename="profile.json"):
        assert mode in ("symbolic", "all")
        self._mode = mode
        self._filename = filename

    def set_state(self, state="stop"):
        assert state in ("run", "stop")
        was = self._running
        self._running = state == "run"
        if was and not self._running and self._filename:
            self.dump(self._filename)

    @property
    def running(self):
        return self._running

    # -- event recording -----------------------------------------------
    def add_event(self, name, start_s, dur_s, cat="op", tid=None, args=None):
        rec = _flight_if_enabled()
        if not self._running and rec is None:
            return
        ev = {
            "name": name, "cat": cat, "ph": "X",
            "ts": (start_s - self._t0) * 1e6, "dur": dur_s * 1e6,
            "pid": os.getpid(),
            "tid": tid if tid is not None else threading.get_ident(),
        }
        if args:
            ev["args"] = dict(args)
        if self._running:
            with self._lock:
                self._events.append(ev)
        if rec is not None:
            rec.record(ev)

    def scope(self, name, cat="op", args=None):
        # Every span is ALSO a jax TraceAnnotation: whenever anyone has
        # a jax trace running (start_xla_trace, a benchmark's
        # start_trace, a TensorBoard capture) it lands on its thread's
        # line of /host:CPU in the same .xplane.pb, on the clock of
        # the device's XLA Ops.  With no trace running the annotation
        # is a sub-microsecond no-op.  With BOTH the chrome profiler
        # and the (always-on-by-default) flight recorder off the span
        # is not timed at all: the annotation alone is returned.
        if not self._running and _flight_if_enabled() is None:
            return _annotation(name, args)
        return self._span(name, cat, args)

    @contextmanager
    def _span(self, name, cat, args=None):
        start = time.perf_counter()
        try:
            with _annotation(name, args):
                yield
        finally:
            self.add_event(name, start, time.perf_counter() - start, cat,
                           args=args)

    def dump(self, filename=None):
        """Write accumulated events as Chrome tracing JSON.

        The file carries process metadata ('M' events: rank name and
        sort index) and a top-level ``metadata.clock_sync`` anchor so
        tools/trace_merge.py can merge per-rank files onto one
        wall-clock-aligned timeline."""
        filename = filename or self._filename
        with self._lock:
            events = list(self._events)
        rank = process_rank()
        pid = os.getpid()
        meta_events = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"rank {rank}"}},
            {"name": "process_sort_index", "ph": "M", "pid": pid, "tid": 0,
             "args": {"sort_index": rank}},
        ]
        with open(filename, "w") as f:
            json.dump({
                "traceEvents": meta_events + events,
                "displayTimeUnit": "ms",
                "metadata": {
                    "rank": rank,
                    "pid": pid,
                    "clock_sync": {"wall_time_s": self._wall0,
                                   "perf_counter_s": self._t0},
                },
            }, f)
        return filename


def name_thread(name):
    """Give the calling thread its OS-level name: what a jax trace
    titles the thread's line of ``/host:CPU`` with (Python before 3.14
    names a thread for itself only, so every Python thread's line reads
    ``python``).  Linux keeps 15 characters.  Best effort: a platform
    without ``pthread_setname_np`` keeps the old title."""
    import ctypes

    try:
        libc = ctypes.CDLL(None)
        libc.pthread_self.restype = ctypes.c_ulong
        libc.pthread_self.argtypes = []
        libc.pthread_setname_np.restype = ctypes.c_int
        libc.pthread_setname_np.argtypes = [ctypes.c_ulong,
                                            ctypes.c_char_p]
        libc.pthread_setname_np(libc.pthread_self(),
                                name.encode()[:15])
    except (OSError, AttributeError):
        pass


def _annotation(name, args=None):
    """The jax-trace form of one span.  ``args`` become the event's
    stats in the trace viewer; they are read when the span is ENTERED
    (the flight recorder reads them when it ends)."""
    if args:
        return _TraceAnnotation(name, **args)
    return _TraceAnnotation(name)


_profiler = Profiler()


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """reference: python/mxnet/profiler.py profiler_set_config"""
    _profiler.set_config(mode=mode, filename=filename)


def profiler_set_state(state="stop"):
    """reference: python/mxnet/profiler.py profiler_set_state"""
    _profiler.set_state(state)


def dump_profile(filename=None):
    """reference: MXDumpProfile"""
    return _profiler.dump(filename)


def dump_rank_trace(trace_dir):
    """Write this process's trace as ``<trace_dir>/trace_rank<N>.json``.

    Every distributed worker calls this with the same shared directory;
    ``tools/trace_merge.py`` then merges the per-rank files into one
    Perfetto-viewable timeline."""
    os.makedirs(trace_dir, exist_ok=True)
    return _profiler.dump(os.path.join(
        trace_dir, f"trace_rank{process_rank()}.json"))


def clock_anchor():
    """The ONE clock-sync convention every timestamped artifact this
    process writes shares: the same instant captured on ``time.time()``
    (the NTP-shared wall clock) and ``time.perf_counter()`` (the clock
    all event ``ts`` values are relative to).  ``Profiler.dump``,
    :class:`Reporter` JSONL lines and flight-recorder dumps all embed
    exactly this dict, so ``tools/trace_merge.py`` aligns all three
    sources with one rule and zero per-tool skew heuristics."""
    return {"wall_time_s": _profiler._wall0,
            "perf_counter_s": _profiler._t0}


def scope(name, cat="op", args=None):
    """Span context manager used by framework internals; no-op when
    off.  ``args`` (a small dict: step number, bytes, bucket key…)
    renders in the trace viewer."""
    return _profiler.scope(name, cat, args)


# -- distributed trace context (the Dapper/W3C-traceparent story) --------
class TraceContext:
    """One request's identity across process boundaries.

    ``trace_id`` (32 hex chars) names the REQUEST and never changes as
    it hops client → router → replica → engine; ``span_id`` (16 hex)
    names the current span; ``parent_id`` links it into the tree.  The
    wire form is W3C-traceparent-style: ``00-<trace>-<span>-01`` —
    ``wire.pack_trace`` ships it as an optional field on fleet
    request/control frames, and the receiving tier's spans become
    children of the sender's span (``from_header`` keeps the sender's
    span_id so ``child()`` parents correctly)."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id=None, span_id=None, parent_id=None):
        self.trace_id = trace_id or os.urandom(16).hex()
        self.span_id = span_id or os.urandom(8).hex()
        self.parent_id = parent_id

    def child(self) -> "TraceContext":
        """A fresh span under this one, same trace."""
        return TraceContext(self.trace_id, None, self.span_id)

    def to_header(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_header(cls, header: str) -> "TraceContext":
        """Parse a traceparent header.  The header's span_id becomes
        THIS context's span_id, so spans the receiver opens via
        :meth:`child` parent onto the sender's span — the cross-
        process edge of the tree."""
        parts = str(header).split("-")
        if (len(parts) != 4 or len(parts[1]) != 32
                or len(parts[2]) != 16):
            raise ValueError(f"malformed traceparent {header!r}")
        int(parts[1], 16), int(parts[2], 16)  # hex or raise
        return cls(parts[1], parts[2], None)

    def args(self):
        a = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.parent_id:
            a["parent_span_id"] = self.parent_id
        return a

    def __repr__(self):
        return (f"TraceContext({self.trace_id[:8]}…/{self.span_id}"
                f"<-{self.parent_id})")


def _trace_sample_rate() -> float:
    global _TRACE_SAMPLE
    if _TRACE_SAMPLE is None:
        raw = os.environ.get("MXNET_TRACE_SAMPLE")
        if raw is None:
            _TRACE_SAMPLE = 1.0
        else:
            try:
                v = float(raw)
            except ValueError:
                raise _mx_error(
                    f"MXNET_TRACE_SAMPLE={raw!r} is not a float in "
                    "[0, 1] (fraction of requests that get a root "
                    "trace context)")
            if not 0.0 <= v <= 1.0:
                raise _mx_error(
                    f"MXNET_TRACE_SAMPLE={v} must be within [0, 1]")
            _TRACE_SAMPLE = v
    return _TRACE_SAMPLE


_TRACE_SAMPLE = None


def _mx_error(msg):
    from .base import MXNetError

    return MXNetError(msg)


def make_trace(key=None):
    """Root trace context for a new request, or ``None`` when sampled
    out (``MXNET_TRACE_SAMPLE``, default 1.0 = trace everything).
    ``key`` (e.g. a ticket id) makes the decision deterministic —
    retries of the same request keep its sampling verdict."""
    rate = _trace_sample_rate()
    if rate >= 1.0:
        return TraceContext()
    if rate <= 0.0:
        return None
    if key is None:
        key = int.from_bytes(os.urandom(4), "little")
    # splitmix-style scramble: consecutive ids sample independently
    h = (int(key) * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    h ^= h >> 31
    return TraceContext() if (h & 0xFFFFFF) / float(1 << 24) < rate \
        else None


def add_trace_event(name, start_s, dur_s, ctx, cat="trace", args=None):
    """Record one span of ``ctx``'s trace (explicit timing — for spans
    whose start/end live on different threads).  The trace ids ride in
    the event args, which is what ``trace_merge.py``'s stitcher keys
    on.  ``ctx`` None (sampled out) is a no-op."""
    if ctx is None:
        return
    a = ctx.args()
    if args:
        a.update(args)
    _profiler.add_event(name, start_s, dur_s, cat, args=a)


def trace_point(name, ctx, args=None, cat="trace"):
    """Zero-duration marker on ``ctx``'s trace (admission verdicts,
    retry decisions, delivery)."""
    add_trace_event(name, time.perf_counter(), 0.0, ctx, cat, args)


@contextmanager
def trace_span(name, parent, cat="trace", args=None):
    """Open a CHILD span of ``parent`` around a code block; yields the
    child context (pass it further down / across the wire).  With
    ``parent`` None the block still runs, untraced."""
    if parent is None:
        yield None
        return
    ctx = parent.child()
    start = time.perf_counter()
    try:
        yield ctx
    finally:
        add_trace_event(name, start, time.perf_counter() - start, ctx,
                        cat, args)


# -- flight recorder -----------------------------------------------------
class FlightRecorder:
    """Bounded ring of this process's recent spans/events/metric
    samples — always on, no file I/O in steady state.

    * In-memory: a ``deque(maxlen=capacity)`` of Chrome-trace-shaped
      event dicts; appends are GIL-atomic (lock-free) and O(1), so
      the hot path pays one dict build per span.
    * Optional write-through ring FILE (``file_path``): a memory-
      mapped fixed-size buffer the recorder memcpys each event's JSON
      line into.  mmap stores are plain memory writes — no syscall —
      and the kernel flushes the dirty pages when the process dies,
      so a ``kill -9``'d process still leaves its last-N-seconds
      record on disk (``read_flight_file`` /
      ``tools/trace_merge.py`` recover it, skipping the torn line at
      the wrap seam).

    The file layout is ``MXFLTREC | u64 data-capacity | u64 total-
    bytes-written | f64 wall0 | f64 t0 | u32 rank | u32 pid`` followed
    by the data ring; the header's clock pair IS :func:`clock_anchor`,
    so merged post-mortems align with live rank traces."""

    MAGIC = b"MXFLTREC"
    _HDR = struct.Struct("<8sQQddII")

    def __init__(self, capacity=4096, file_path=None,
                 file_bytes=1 << 20):
        self._ring = collections.deque(maxlen=int(capacity))
        self.capacity = int(capacity)
        self._mm = None
        self._file_lock = threading.Lock()
        self._file_cap = 0
        self._written = 0
        self.file_path = None
        if file_path:
            try:
                self._open_file(file_path, int(file_bytes))
            except OSError:
                self._mm = None  # memory ring still works

    def _open_file(self, path, file_bytes):
        import mmap

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        size = self._HDR.size + file_bytes
        with open(path, "wb") as f:
            f.truncate(size)
        self._fh = open(path, "r+b")
        self._mm = mmap.mmap(self._fh.fileno(), size)
        anchor = clock_anchor()
        self._HDR.pack_into(
            self._mm, 0, self.MAGIC, file_bytes, 0,
            anchor["wall_time_s"], anchor["perf_counter_s"],
            process_rank(), os.getpid())
        self._file_cap = file_bytes
        self.file_path = path

    def record(self, ev: dict):
        """Append one Chrome-trace-shaped event; never raises."""
        self._ring.append(ev)
        if self._mm is None:
            return
        try:
            line = json.dumps(ev, separators=(",", ":"),
                              default=str).encode() + b"\n"
            if len(line) > self._file_cap:
                return
            hdr = self._HDR.size
            with self._file_lock:
                pos = self._written % self._file_cap
                first = min(len(line), self._file_cap - pos)
                self._mm[hdr + pos:hdr + pos + first] = line[:first]
                if first < len(line):  # wrap
                    self._mm[hdr:hdr + len(line) - first] = line[first:]
                self._written += len(line)
                struct.pack_into("<Q", self._mm, 16, self._written)
        except (ValueError, OSError):
            pass

    def snapshot(self, n=None):
        evs = list(self._ring)
        return evs if n is None else evs[-int(n):]

    def sync(self):
        """Flush the mmap ring to storage (dump time / tests only —
        never on the record path)."""
        if self._mm is not None:
            try:
                self._mm.flush()
            except (ValueError, OSError):
                pass

    def close(self):
        """Release the mmap/fd (recorder replacement); the in-memory
        ring stays readable."""
        mm, self._mm = self._mm, None
        if mm is not None:
            try:
                mm.flush()
                mm.close()
                self._fh.close()
            except (ValueError, OSError):
                pass

    def dump(self, reason: str, dir: str | None = None,
             extra: dict | None = None) -> str:
        """Write the post-mortem JSON: a Chrome-trace-compatible file
        (``trace_merge.py`` consumes it directly) carrying the ring
        snapshot, the shared clock anchor, a metrics summary, and the
        ``reason``.  Returns the path."""
        dir = dir or _flight_dir()
        os.makedirs(dir, exist_ok=True)
        path = os.path.join(
            dir, f"flightdump_rank{process_rank()}_pid{os.getpid()}"
                 f"_{reason}.json")
        try:
            metrics = metrics_summary()
        except Exception:  # noqa: BLE001 — the dump must still land
            metrics = {}
        doc = {
            "traceEvents": self.snapshot(),
            "displayTimeUnit": "ms",
            "metadata": {
                "flight_recorder": True,
                "reason": reason,
                "rank": process_rank(),
                "pid": os.getpid(),
                "wall_time_s": time.time(),
                "clock_sync": clock_anchor(),
                "metrics": metrics,
                **(extra or {}),
            },
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, default=str)
        os.replace(tmp, path)
        self.sync()
        return path


def read_flight_file(path: str):
    """Recover a (possibly kill -9 orphaned) mmap ring file → a
    Chrome-trace dict with ``metadata.clock_sync``.  Torn lines at the
    wrap seam are skipped.  (tools/trace_merge.py carries a standalone
    copy of this logic so it needs no package import.)"""
    with open(path, "rb") as f:
        raw = f.read()
    hdr = FlightRecorder._HDR
    magic, cap, written, wall0, t0, rank, pid = hdr.unpack_from(raw, 0)
    if magic != FlightRecorder.MAGIC:
        raise ValueError(f"{path}: not a flight-recorder ring file")
    data = raw[hdr.size:hdr.size + cap]
    if written <= cap:
        buf = data[:written]
    else:
        pos = written % cap
        buf = data[pos:] + data[:pos]
    events = []
    for line in buf.split(b"\n"):
        if not line.strip():
            continue
        try:
            events.append(json.loads(line))
        except ValueError:
            continue  # torn at the seam / mid-write
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"flight_recorder": True, "rank": rank,
                         "pid": pid,
                         "clock_sync": {"wall_time_s": wall0,
                                        "perf_counter_s": t0}}}


_flight: FlightRecorder | None = None
_flight_init_lock = threading.Lock()
_FLIGHT_ENABLED: bool | None = None
_flight_dumped: dict = {}  # reason -> last dump wall time (rate limit)


def _flight_dir() -> str:
    d = os.environ.get("MXNET_FLIGHT_RECORDER_DIR")
    if d:
        return d
    import tempfile

    return os.path.join(tempfile.gettempdir(), "mxnet_tpu_flight")


def _flight_if_enabled() -> FlightRecorder | None:
    global _FLIGHT_ENABLED
    if _FLIGHT_ENABLED is None:
        _FLIGHT_ENABLED = \
            os.environ.get("MXNET_FLIGHT_RECORDER", "1") != "0"
    if not _FLIGHT_ENABLED:
        return None
    return flight_recorder()


def _flight_capacity() -> int:
    """Validated MXNET_FLIGHT_RECORDER_SIZE (event count)."""
    raw = os.environ.get("MXNET_FLIGHT_RECORDER_SIZE")
    try:
        cap = int(raw) if raw else 4096
    except ValueError:
        raise _mx_error(
            f"MXNET_FLIGHT_RECORDER_SIZE={raw!r} is not an integer "
            "event count")
    if cap < 16:
        raise _mx_error(
            f"MXNET_FLIGHT_RECORDER_SIZE={cap} must be >= 16")
    return cap


def flight_recorder() -> FlightRecorder:
    """The process-global recorder (created lazily from
    ``MXNET_FLIGHT_RECORDER_SIZE`` / ``_DIR``)."""
    global _flight
    if _flight is None:
        with _flight_init_lock:
            if _flight is None:
                file_path = None
                d = os.environ.get("MXNET_FLIGHT_RECORDER_DIR")
                if d:
                    file_path = os.path.join(
                        d, f"flight_rank{process_rank()}"
                           f"_pid{os.getpid()}.ring")
                _flight = FlightRecorder(capacity=_flight_capacity(),
                                         file_path=file_path)
    return _flight


def init_flight_recorder(dir=None, capacity=None,
                         file_bytes=1 << 20) -> FlightRecorder:
    """(Re)configure the global recorder explicitly — the fleet
    replica main points the ring file at the shared fleet dir so the
    kill -9 drill's post-mortems land where the drill looks.  A
    previously-open ring file is closed, not leaked."""
    global _flight, _FLIGHT_ENABLED
    cap = capacity if capacity is not None else _flight_capacity()
    path = None
    if dir:
        path = os.path.join(dir, f"flight_rank{process_rank()}"
                                 f"_pid{os.getpid()}.ring")
    with _flight_init_lock:
        if _flight is not None:
            _flight.close()
        _flight = FlightRecorder(capacity=cap, file_path=path,
                                 file_bytes=file_bytes)
        _FLIGHT_ENABLED = True
    return _flight


def flight_snapshot(n=None):
    """Recent flight-recorder events (the ``/tracez`` payload)."""
    rec = _flight_if_enabled()
    return rec.snapshot(n) if rec is not None else []


def dump_flight_record(reason: str, dir=None, extra=None,
                       min_interval_s: float = 2.0):
    """Post-mortem dump trigger (DeadRankError, replica conviction,
    engine-loop crash, shed burst, SIGTERM).  Rate-limited per reason
    so a failure storm can't turn the recorder into a disk hog.
    Returns the path, or None (disabled / rate-limited / dump
    failed — a failing dump must never mask the original crash)."""
    rec = _flight_if_enabled()
    if rec is None:
        return None
    now = time.monotonic()
    last = _flight_dumped.get(reason)
    if last is not None and now - last < min_interval_s:
        return None
    _flight_dumped[reason] = now
    try:
        return rec.dump(reason, dir=dir, extra=extra)
    except Exception:  # noqa: BLE001
        return None


def add_event(name, start_s, dur_s, cat="op", args=None):
    """Record a complete span with explicit timing — for spans whose
    start and end live on different threads (e.g. serving dispatch →
    completion).  No-op when profiling is off."""
    _profiler.add_event(name, start_s, dur_s, cat, args=args)


@contextmanager
def record_program(name, compiled, cat="exec", args=None):
    """Span round one jitted-program dispatch — the ONE compile-
    accounting contract shared by Executor and the Module fused step:
    a first run (``compiled``) bumps the ``executor.compiles`` counter,
    samples ``executor.compile_ms``, and tags the span cat='compile';
    warm runs emit a plain exec span.  Every span carries the
    ``compile`` flag in its args.  Like :func:`scope` the span is a jax
    ``TraceAnnotation`` too; a dispatch that raises books nothing."""
    ev_args = {"compile": compiled}
    if args:
        ev_args.update(args)
    start = time.perf_counter()
    with _annotation(name, ev_args):
        yield
    dur_s = time.perf_counter() - start
    if compiled:
        inc_counter("executor.compiles")
        observe("executor.compile_ms", dur_s * 1e3)
    _profiler.add_event(name, start, dur_s,
                        "compile" if compiled else cat, args=ev_args)


# -- the names the program gave its device operations --------------------
# Who holds compiled programs (a DecodeEngine, a Module with a fused
# step) is known here weakly.  A holder that goes hands over its
# programs — the executables alone: no weights, no pools — so that a
# trace taken while it ran can still be named once it is closed: only
# the last one gone is kept, and its executables only until its tables
# have been asked for.
_program_holders = weakref.WeakSet()
_retired = {"programs": {}, "cache": {}, "tables": {}}


def hold_programs(holder):
    """``holder._programs_held()`` is {key: compiled executable}."""
    _program_holders.add(holder)


def retire_programs(holder):
    """``holder`` goes (an engine's ``close()``): what it ran stays
    nameable.  Builds nothing."""
    held = holder._programs_held()
    if held:
        _retired.update(
            programs=held, tables={},
            cache=holder.__dict__.get("_scope_tables", {}))


def scope_tables(programs, cache):
    """{program name: ``hlo.ScopeTable``} of {key: executable}.  An
    executable's text is asked for (``as_text()``) and parsed HERE,
    when someone asks, once an executable (``cache``: key -> (the
    runtime's executable, table)); a table's ``seconds`` says what
    that took."""
    from . import hlo

    out = {}
    for key, exe in programs.items():
        loaded, kept = exe.runtime_executable(), cache.get(key)
        if kept is None or kept[0] is not loaded:
            t0 = time.perf_counter()
            table = hlo.scope_table(exe.as_text())
            table.seconds = time.perf_counter() - t0
            kept = cache[key] = (loaded, table)
        out[kept[1].program] = kept[1]
    return out


def holder_scopes(holder):
    """``scope_tables`` of what ``holder`` holds, kept on the holder."""
    return scope_tables(holder._programs_held(),
                        holder.__dict__.setdefault("_scope_tables", {}))


def program_scopes():
    """{program name as a device trace's ``XLA Modules`` carry it
    (``jit_prefill_t1024``): {instruction: record}} over every live
    holder and the last one gone — what a reader of a trace looks a
    device operation up in (the record: ``hlo.scope_table``) without a
    handle on any engine.  Lazy: no text is read or parsed before
    someone calls this."""
    if _retired["programs"]:
        _retired.update(
            tables=scope_tables(_retired["programs"], _retired["cache"]),
            programs={}, cache={})
    out = dict(_retired["tables"])
    for holder in list(_program_holders):
        out.update(holder_scopes(holder))
    return out


# -- counters / gauges / histograms -------------------------------------
class MetricsRegistry:
    """Lightweight serving/runtime metrics: named monotonic counters,
    set/inc gauges, and bounded-reservoir histograms with percentile
    queries.

    This is the always-on companion to the span profiler above: spans
    answer "where did this program unit's time go", the registry
    answers "what are the steady-state rates and tails" (queue depth,
    batch-fill ratio, request latency, live buffer bytes) without
    requiring a trace to be running.  Thread-safe; the serving engine
    hammers it from three threads."""

    def __init__(self, reservoir=65536):
        import collections

        self._lock = threading.Lock()
        self._counters = {}
        self._gauges = {}
        self._hists = {}
        self._deque = collections.deque
        self._reservoir = reservoir
        self._t_reset = time.monotonic()
        self._gen = 0
        # counters of ONE hot writer that may not take the lock (the
        # jax compile listener below): plain dict adds, merged into
        # summary() under their names
        self._unlocked = {}

    def inc(self, name, value=1.0):
        with self._lock:
            # float() so numpy scalars can't poison json.dumps later
            self._counters[name] = self._counters.get(name, 0.0) \
                + float(value)

    def set_gauge(self, name, value):
        with self._lock:
            self._gauges[name] = float(value)

    def del_gauge(self, name):
        """Retire a gauge from the registry (e.g. a per-replica queue
        depth whose replica died): exporters stop advertising it
        instead of freezing its last value forever."""
        with self._lock:
            self._gauges.pop(name, None)

    def inc_gauge(self, name, delta, gen=None):
        """Adjust a gauge by ``delta``; returns the generation the
        delta was applied under (or None if dropped).  Delta-tracked
        gauges whose decrement may outlive a ``reset()`` (e.g. an
        executor finalizer releasing live-buffer bytes) pass the
        generation this method RETURNED for the increment: if a reset
        already cleared the increment, the stale decrement is dropped
        instead of driving the gauge negative forever.  The generation
        is read under the same lock as the update, so an increment can
        never be stamped with a generation it wasn't applied under."""
        with self._lock:
            if gen is not None and gen != self._gen:
                return None
            self._gauges[name] = self._gauges.get(name, 0.0) + float(delta)
            return self._gen

    @property
    def generation(self):
        """Bumped by every reset(); see inc_gauge."""
        return self._gen

    #: fixed Prometheus-histogram bucket upper bounds (ms-oriented but
    #: generic — ratios land in the first bucket, minutes in the last;
    #: +Inf is implicit = lifetime count).  Cumulated at export.
    BUCKET_BOUNDS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                    100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                    10000.0, 30000.0, 60000.0)

    def observe(self, name, value, n=1):
        """One sample, or ``n`` equal ones at the price of one (a
        decode step books its rows' shared cadence once)."""
        import bisect

        with self._lock:
            h = self._hists.get(name)
            if h is None:
                # (reservoir of last N, lifetime count, lifetime sum,
                # per-bucket counts) — percentiles come from the
                # reservoir; count/mean and the Prometheus _bucket
                # series are exact over the full lifetime
                h = self._hists[name] = [
                    self._deque(maxlen=self._reservoir), 0, 0.0,
                    [0] * len(self.BUCKET_BOUNDS)]
            v = float(value)
            h[0].extend((v,) * n)
            h[1] += n
            h[2] += n * v
            i = bisect.bisect_left(self.BUCKET_BOUNDS, v)
            if i < len(self.BUCKET_BOUNDS):
                h[3][i] += n

    def summary(self):
        """→ {'counters': {...}, 'rates': {name: per-second since
        reset}, 'gauges': {...}, 'histograms': {name: {count, mean,
        min, max, p50, p90, p99}}, 'elapsed_s': ...} — JSON-ready.

        The reporter's JSONL lines, ``serving.stats()`` and the
        benchmark's runners all consume this one schema."""
        import numpy as _np

        with self._lock:
            counters = dict(self._counters)
            counters.update((k, v) for k, v in self._unlocked.items()
                            if v)
            gauges = dict(self._gauges)
            hists = {k: (_np.asarray(h[0], dtype=_np.float64), h[1],
                         h[2], list(h[3]))
                     for k, h in self._hists.items()}
            elapsed = time.monotonic() - self._t_reset
        out = {"counters": counters,
               "rates": {k: v / max(elapsed, 1e-9)
                         for k, v in counters.items()},
               "gauges": gauges,
               "histograms": {},
               "elapsed_s": elapsed}
        for k, (vals, count, total, buckets) in hists.items():
            if not len(vals):
                continue
            out["histograms"][k] = {
                "count": int(count),
                "mean": float(total / count),
                "min": float(vals.min()), "max": float(vals.max()),
                "p50": float(_np.percentile(vals, 50)),
                "p90": float(_np.percentile(vals, 90)),
                "p99": float(_np.percentile(vals, 99)),
                "sum": float(total),
                # non-cumulative per-bound counts; exporters cumsum
                "buckets": buckets,
            }
        return out

    def reset(self):
        with self._lock:
            self._counters.clear()
            for k in self._unlocked:
                self._unlocked[k] = 0.0
            self._gauges.clear()
            self._hists.clear()
            self._t_reset = time.monotonic()
            self._gen += 1  # invalidate pending delta-gauge decrements


_metrics = MetricsRegistry()


def inc_counter(name, value=1.0):
    """Bump a named monotonic counter (e.g. ``serving.requests``)."""
    _metrics.inc(name, value)


def set_gauge(name, value):
    """Set a named gauge to an absolute value (e.g. queue depth)."""
    _metrics.set_gauge(name, value)


def del_gauge(name):
    """Retire a named gauge (a dead replica's queue depth must drop
    out of the exposition, not freeze at its last value)."""
    _metrics.del_gauge(name)


def inc_gauge(name, delta, gen=None):
    """Adjust a named gauge by a delta (e.g. live buffer bytes on
    executor alloc/free); returns the generation it applied under.
    Pass that value back as ``gen`` for the matching decrement when it
    may run after a ``reset_metrics()`` (see
    MetricsRegistry.inc_gauge)."""
    return _metrics.inc_gauge(name, delta, gen=gen)


def gauge_generation():
    """Current registry generation (bumped by reset_metrics)."""
    return _metrics.generation


def observe(name, value, n=1):
    """Record one histogram sample (e.g. ``serving.latency_ms``), or
    ``n`` equal ones at once.  Samples also land in the flight
    recorder as Chrome counter events (one event, whatever ``n``), so
    a post-mortem carries the metric timeline next to the spans."""
    _metrics.observe(name, value, n)
    rec = _flight_if_enabled()
    if rec is not None:
        rec.record({"name": name, "ph": "C",
                    "ts": (time.perf_counter()
                           - _profiler._t0) * 1e6,
                    "pid": os.getpid(), "tid": 0,
                    "args": {"value": float(value)}})


def metrics_summary():
    """Counters (+rates), gauges, histogram stats (p50/p90/p99)."""
    return _metrics.summary()


def reset_metrics():
    _metrics.reset()


# -- set-up by phase: what jax spent building programs -------------------
#: jax.monitoring duration events -> the counter each feeds
COMPILE_EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}
_COMPILE_COUNTER = {k: f"compile.{k}_s"
                    for k in COMPILE_EVENT_KINDS.values()}
_COMPILE_LOG_MIN_S = 1e-3
_compile_counters = _metrics._unlocked
_compile_counters.update({c: 0.0 for c in _COMPILE_COUNTER.values()},
                         **{"compile.programs": 0.0})
_compile_log = collections.deque(maxlen=8192)
# (start, seconds) of the trace events no later one has contained yet
_trace_open: list = []


def _on_compile_duration(event, duration, **_kw):
    """The ONE ``jax.monitoring`` duration listener of the package:
    why a process took two minutes to come up, as counters on
    ``/metrics``.  ``compile.trace_s`` — Python tracing to jaxprs;
    ``compile.lower_s`` — jaxpr to MLIR; ``compile.backend_s`` — XLA
    compiling a program OR fetching it from the persistent cache;
    ``compile.cache_fetch_s`` — the part of backend_s that was such a
    fetch; ``compile.programs`` — programs built either way.

    jax fires the trace event for every ``jnp`` function called inside
    a trace (thousands per program), so this stays a few dict and list
    operations: no lock (two threads building programs at the same
    instant can lose one add), no logging.  NESTED trace events do NOT
    double count: an event arrives when its trace ENDS, after the
    traces it contains, so it is booked with what those have not
    booked already (``_trace_open`` holds the intervals no later event
    has contained yet) and ``compile.trace_s`` is the wall time in
    which something was being traced.  Booked whole, the events of the
    first fused step of gpt2-medium summed to 198.6 s inside a set-up
    of 136.6 s (my chip run, PR 25); of a 4-layer LM on XLA:CPU to
    0.708 s over a union of 0.657 s.  Lower and backend events do not
    nest.  Checked against the benchmark's ``mark`` lines on the chip:
    per phase, trace + lower + backend stay below the phase's wall
    seconds (PERF.md section 5)."""
    kind = COMPILE_EVENT_KINDS.get(event)
    if kind is None:
        return
    now = time.perf_counter()
    if kind == "trace":
        start, whole = now - duration, duration
        while _trace_open and _trace_open[-1][0] >= start:
            duration -= _trace_open.pop()[1]
        _trace_open.append((start, whole))
        if len(_trace_open) > 65536:  # finished programs' intervals
            del _trace_open[:32768]
        duration = max(duration, 0.0)
    elif kind == "backend":
        _compile_counters["compile.programs"] += 1.0
    _compile_counters[_COMPILE_COUNTER[kind]] += duration
    if duration >= _COMPILE_LOG_MIN_S:
        _compile_log.append((now, kind, duration))


def compile_events():
    """The compile events of a millisecond or more, oldest first:
    ``(time.perf_counter() when it ended, kind, seconds)`` with kind
    one of ``trace``/``lower``/``backend``/``cache_fetch`` (a trace
    event's seconds are its own: what the traces inside it have not
    booked) — bounded (the newest 8192), never reset: a reader sums what fell before or
    after an instant of its own (the benchmark: before its window)."""
    return list(_compile_log)


# registered once, with the package's import: it sees set-up from the
# start.  (jax.monitoring keeps listeners for the life of the process.)
_monitoring.register_event_duration_secs_listener(_on_compile_duration)


# -- exporters -----------------------------------------------------------
def _prom_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s


def prometheus_text(registry: MetricsRegistry | None = None,
                    prefix: str = "mxnet") -> str:
    """Render the registry in the Prometheus text exposition format.

    Counters export as ``counter``, gauges as ``gauge``, histograms as
    REAL Prometheus ``histogram`` families — cumulative
    ``_bucket{le=...}`` series over the fixed
    :attr:`MetricsRegistry.BUCKET_BOUNDS` ladder plus exact
    ``_sum``/``_count`` — so server-side ``histogram_quantile()``
    works and histograms aggregate across ranks.  (The pre-PR-12
    ``_p50``/``_p90``/``_p99`` quantile gauges rode along for one
    release and are now RETIRED — use ``histogram_quantile()`` over
    the ``_bucket`` series.)  Serve it from any HTTP handler
    (``/metrics`` via
    :func:`start_metrics_server`), or dump it periodically next to
    the JSONL reporter — both views read the same registry, so
    ``serving.*`` counters and the training gauges show up with no
    extra wiring."""
    summ = (registry or _metrics).summary()
    rank = process_rank()
    lines = []
    for k in sorted(summ["counters"]):
        m = f"{prefix}_{_prom_name(k)}"
        lines.append(f"# TYPE {m} counter")
        lines.append(f'{m}{{rank="{rank}"}} {summ["counters"][k]:g}')
    for k in sorted(summ["gauges"]):
        m = f"{prefix}_{_prom_name(k)}"
        lines.append(f"# TYPE {m} gauge")
        lines.append(f'{m}{{rank="{rank}"}} {summ["gauges"][k]:g}')
    for k in sorted(summ["histograms"]):
        h = summ["histograms"][k]
        m = f"{prefix}_{_prom_name(k)}"
        lines.append(f"# TYPE {m} histogram")
        cum = 0
        for bound, n in zip(MetricsRegistry.BUCKET_BOUNDS,
                            h.get("buckets", ())):
            cum += n
            lines.append(
                f'{m}_bucket{{rank="{rank}",le="{bound:g}"}} {cum}')
        lines.append(
            f'{m}_bucket{{rank="{rank}",le="+Inf"}} {h["count"]}')
        lines.append(f'{m}_count{{rank="{rank}"}} {h["count"]}')
        lines.append(f'{m}_sum{{rank="{rank}"}} '
                     f'{h.get("sum", h["mean"] * h["count"]):g}')
    return "\n".join(lines) + "\n"


class Reporter:
    """Daemon thread appending one ``metrics_summary()`` JSONL line to
    ``path`` every ``interval`` seconds (plus a final line at stop) —
    the flight recorder for runs without a scrape endpoint."""

    def __init__(self, path, interval=10.0, registry=None):
        self._path = path
        self._interval = float(interval)
        self._registry = registry or _metrics
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="mxnet_tpu-metrics-reporter")
        self._thread.start()

    def _write_line(self):
        # clock_sync: the same anchor convention as Profiler.dump and
        # the flight-recorder dumps, so trace_merge.py can align JSONL
        # metric timelines with span timelines skew-free
        line = {"t": time.time(), "rank": process_rank(),
                "clock_sync": clock_anchor()}
        line.update(self._registry.summary())
        with open(self._path, "a") as f:
            f.write(json.dumps(line) + "\n")

    def _loop(self):
        while not self._stop.wait(self._interval):
            try:
                self._write_line()
            except Exception:  # noqa: BLE001 — a transient fs error or
                pass  # unserializable sample must not kill the recorder

    def stop(self):
        """Stop the thread and flush one final summary line."""
        if self._stop.is_set():
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        try:
            self._write_line()
        except Exception:  # noqa: BLE001
            pass


def start_reporter(path, interval=10.0, registry=None) -> Reporter:
    """Start a periodic JSONL metrics reporter; returns the handle
    (call ``.stop()`` to flush and join)."""
    return Reporter(path, interval=interval, registry=registry)


# -- live goodput / MFU accounting ---------------------------------------
# THE peak table: per-chip peak rates keyed by jax's ``device_kind``,
# each row with its source.  The live MFU gauge divides by these (the
# benchmark's own, benchmark/peaks.py, is held equal by a test).  It
# holds the one chip this repo runs on; a device that is not here is
# an error, not a default — add its row when it is first measured.
PEAK_BY_DEVICE_KIND = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM.  jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak_flops(device_kind: str) -> float:
    """Peak dense bf16 FLOP/s of one chip of ``device_kind`` (as
    ``jax.devices()[0].device_kind`` reports it).  Unknown kinds
    raise."""
    try:
        return PEAK_BY_DEVICE_KIND[device_kind]["bf16_flops"]
    except KeyError:
        raise _mx_error(
            f"no peak rate for device_kind {device_kind!r} in "
            f"profiler.PEAK_BY_DEVICE_KIND (knows "
            f"{sorted(PEAK_BY_DEVICE_KIND)}): add its row with the "
            f"source, or set MXNET_PEAK_TFLOPS") from None


def device_peak_flops():
    """Per-chip peak FLOP/s for the live MFU denominator:
    ``MXNET_PEAK_TFLOPS`` (authoritative) or :func:`peak_flops` of the
    first device.  None on a CPU backend — a CPU has no such peak, so
    the mfu gauge is withheld there (goodput still is exported); an
    accelerator the table does not know raises."""
    raw = os.environ.get("MXNET_PEAK_TFLOPS")
    if raw is not None:
        try:
            v = float(raw)
        except ValueError:
            raise _mx_error(
                f"MXNET_PEAK_TFLOPS={raw!r} is not a float (per-chip "
                "peak TFLOP/s for MFU accounting)")
        if v <= 0:
            raise _mx_error(f"MXNET_PEAK_TFLOPS={v} must be > 0")
        return v * 1e12
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return peak_flops(dev.device_kind)


class GoodputTracker:
    """Live training-efficiency accounting: MFU, goodput, and a
    step-time decomposition that sums to ~100% of wall time.

    The fit loop feeds one sample per step (``step(step_s, io_s,
    ckpt_s)``); the comm scheduler books its blocked-waiting seconds
    via :meth:`add_comm`; the pipeline executor declares its static
    bubble fraction; elastic recovery books re-mesh downtime via
    :meth:`add_lost`.  Each step updates the gauges:

    - ``training.mfu`` — flops_per_step / (EMA step seconds) / peak
      (absent until both flops and peak are known);
    - ``training.goodput`` — Σ productive step seconds ÷ wall seconds
      since tracking started (lost time, io stalls and checkpoint
      blocking all show up as the gap to 1.0);
    - ``training.step_time_ms`` and ``training.frac_{compute, comm,
      io_wait, pp_bubble, ckpt_block, other}`` — cumulative fractions
      of wall, summing to 1 by construction;
    - ``training.lost_s`` counter per ``add_lost`` reason
      (``training.lost_s.<reason>``), surviving re-mesh events.
    """

    _EMA = 0.2  # step-seconds smoothing for the live mfu gauge

    def __init__(self, registry: MetricsRegistry | None = None):
        self._lock = threading.Lock()
        self._registry = registry  # None = the global gauge surface
        self.reset()

    def reset(self):
        with self._lock:
            self._t_start = None
            self._t_last = None
            self._wall_s = 0.0
            self._flops = None
            self._peak = None
            self._peak_resolved = False
            self._pp_bubble = 0.0
            self._pending_comm = 0.0
            self._program_comm_frac = 0.0
            self._steps = 0
            self._step_s_ema = None
            self._cum = {"compute": 0.0, "comm": 0.0, "io_wait": 0.0,
                         "pp_bubble": 0.0, "ckpt_block": 0.0,
                         "other": 0.0}
            self._productive_s = 0.0
            self._lost = {}

    # -- configuration ---------------------------------------------------
    def set_flops_per_step(self, flops):
        """Model FLOPs of ONE optimizer step (fwd+bwd+update) — from
        the fused program's XLA cost analysis (Module) or an analytic
        formula."""
        with self._lock:
            self._flops = float(flops) if flops else None

    def set_peak_flops(self, flops_per_s):
        with self._lock:
            self._peak = float(flops_per_s) if flops_per_s else None
            self._peak_resolved = True

    def set_pp_bubble(self, frac):
        """Static pipeline-bubble fraction ((pp-1)/(M+pp-1)) of the
        step — attributed out of compute in the decomposition."""
        with self._lock:
            self._pp_bubble = min(max(float(frac), 0.0), 1.0)

    def set_program_comm_fraction(self, frac):
        """Static IN-PROGRAM collective fraction of one fused step —
        collective bytes / total bytes accessed, both from the XLA
        cost surface of the compiled step
        (``Module.account_program_comm``).  Before this, ``comm`` was
        booked only from host-side CommScheduler waits, so the
        reduce-scatter/all-gather running INSIDE the one fused XLA
        program silently reported as ``compute``.  Each step sample
        books ``frac`` of its in-step seconds as comm (on top of any
        scheduler waits, capped at the step); the fractions keep
        summing to 1 by construction."""
        with self._lock:
            self._program_comm_frac = min(max(float(frac), 0.0), 1.0)

    # -- attribution hooks -----------------------------------------------
    def add_comm(self, seconds):
        """Communication seconds the step blocked on (the comm
        scheduler's wait paths); drained into the next step sample."""
        with self._lock:
            self._pending_comm += max(0.0, float(seconds))

    def add_lost(self, seconds, reason: str):
        """Attributed lost wall time (elastic re-mesh, rollback,
        restore) — the goodput denominator keeps running through it,
        and the per-reason counter says where it went."""
        with self._lock:
            self._lost[reason] = self._lost.get(reason, 0.0) \
                + float(seconds)
        inc_counter(f"training.lost_s.{reason}", float(seconds))

    # -- per-step sample -------------------------------------------------
    def step(self, step_s, io_s=0.0, ckpt_s=0.0):
        """One training-loop iteration's wall decomposition: the
        fit.step seconds, the io.next wait, the checkpoint blocking.
        Everything between the previous sample and now that none of
        those cover lands in ``other``."""
        now = time.monotonic()
        with self._lock:
            if self._t_start is None:
                self._t_start = now - (step_s + io_s + ckpt_s)
                self._t_last = self._t_start
            if not self._peak_resolved:
                self._peak = device_peak_flops()
                self._peak_resolved = True
            # the wall this iteration accounts for: real elapsed since
            # the previous sample, floored by what the caller claims
            # happened (so synthetic/replayed samples stay consistent)
            wall = max(now - self._t_last, step_s + io_s + ckpt_s)
            self._wall_s += wall
            self._t_last = now
            in_program = self._program_comm_frac \
                * max(step_s - min(self._pending_comm, step_s), 0.0)
            comm = min(self._pending_comm + in_program, step_s)
            self._pending_comm = 0.0
            bubble = self._pp_bubble * max(step_s - comm, 0.0)
            compute = max(step_s - comm - bubble, 0.0)
            other = max(wall - step_s - io_s - ckpt_s, 0.0)
            self._cum["compute"] += compute
            self._cum["comm"] += comm
            self._cum["pp_bubble"] += bubble
            self._cum["io_wait"] += io_s
            self._cum["ckpt_block"] += ckpt_s
            self._cum["other"] += other
            self._productive_s += step_s
            self._steps += 1
            self._step_s_ema = (
                step_s if self._step_s_ema is None
                else (1 - self._EMA) * self._step_s_ema
                + self._EMA * step_s)
            self._export_locked(now)

    def _export_locked(self, now):
        set_g = (self._registry.set_gauge if self._registry is not None
                 else set_gauge)
        wall = max(self._wall_s, 1e-9)
        set_g("training.goodput", self._productive_s / wall)
        set_g("training.step_time_ms", self._step_s_ema * 1e3)
        set_g("training.steps", float(self._steps))
        total = max(sum(self._cum.values()), 1e-9)
        for k, v in self._cum.items():
            set_g(f"training.frac_{k}", v / total)
        if self._flops:
            set_g("training.flops_per_step", self._flops)
            if self._peak:
                set_g("training.mfu",
                      self._flops / max(self._step_s_ema, 1e-9)
                      / self._peak)

    def summary(self) -> dict:
        """JSON-ready snapshot (the ``/statusz`` training section)."""
        with self._lock:
            if self._t_start is None:
                return {"steps": 0}
            wall = max(self._wall_s, 1e-9)
            mean_step = self._productive_s / max(self._steps, 1)
            out = {
                "steps": self._steps,
                "wall_s": wall,
                "goodput": self._productive_s / wall,
                "step_time_ms": mean_step * 1e3,
                "step_time_ms_ema": (self._step_s_ema or 0.0) * 1e3,
                "flops_per_step": self._flops,
                "peak_flops": self._peak,
                "mfu": (self._flops / max(mean_step, 1e-9) / self._peak
                        if self._flops and self._peak else None),
                "program_comm_fraction": self._program_comm_frac,
                "lost_s": dict(self._lost),
            }
            total = max(sum(self._cum.values()), 1e-9)
            out["decomposition"] = {k: v / total
                                    for k, v in self._cum.items()}
            out["decomposition_s"] = dict(self._cum)
            return out


_goodput = GoodputTracker()


def goodput_tracker() -> GoodputTracker:
    """The process-global training-efficiency tracker (fit wires it)."""
    return _goodput


# -- ops surface: /metrics, /statusz, /tracez ----------------------------
_statusz_providers: dict = {}
_metrics_server = None
_metrics_server_lock = threading.Lock()


def register_statusz(name: str, fn):
    """Contribute a section to ``/statusz``: ``fn()`` must return a
    JSON-ready dict (called on the HTTP thread — must be thread-safe,
    like the engines' ``stats()``)."""
    _statusz_providers[str(name)] = fn


def unregister_statusz(name: str, fn=None):
    """Drop a section; with ``fn``, only if it is still that provider's
    (a closed engine must not take down its successor's section)."""
    if fn is None or _statusz_providers.get(str(name)) == fn:
        _statusz_providers.pop(str(name), None)


def statusz() -> dict:
    """The ``/statusz`` document: process identity, uptime, the gauge
    surface (goodput/MFU, cache_util, queue depths, membership epoch —
    whatever the process exports), and every registered provider's
    section (serving engine stats, router stats...)."""
    summ = metrics_summary()
    doc = {
        "rank": process_rank(),
        "pid": os.getpid(),
        "wall_time_s": time.time(),
        "clock_sync": clock_anchor(),
        "gauges": summ["gauges"],
        "counters": summ["counters"],
        "training": _goodput.summary(),
    }
    for name, fn in sorted(_statusz_providers.items()):
        try:
            doc[name] = fn()
        except Exception as exc:  # noqa: BLE001 — one bad provider
            doc[name] = {"error": f"{type(exc).__name__}: {exc}"}
    return doc


class MetricsServer:
    """Tiny stdlib HTTP server: ``/metrics`` (Prometheus text),
    ``/statusz`` (JSON), ``/tracez`` (flight-recorder snapshot;
    ``?n=`` bounds the event count).  Daemon threads; binds
    loopback by default — expose it beyond the host through your own
    proxy, it has no auth."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1"):
        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: N802 — stdlib name
                pass

            def do_GET(self):  # noqa: N802 — stdlib name
                try:
                    path, _, query = self.path.partition("?")
                    if path == "/metrics":
                        body = prometheus_text().encode()
                        ctype = "text/plain; version=0.0.4"
                    elif path == "/statusz":
                        body = json.dumps(statusz(),
                                          default=str).encode()
                        ctype = "application/json"
                    elif path == "/tracez":
                        n = 512
                        for part in query.split("&"):
                            if part.startswith("n="):
                                try:
                                    n = max(1, int(part[2:]))
                                except ValueError:
                                    pass
                        body = json.dumps(
                            {"rank": process_rank(),
                             "pid": os.getpid(),
                             "clock_sync": clock_anchor(),
                             "traceEvents": flight_snapshot(n)},
                            default=str).encode()
                        ctype = "application/json"
                    else:
                        self.send_error(404)
                        return
                    self.send_response(200)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except BrokenPipeError:
                    pass

        class Server(http.server.ThreadingHTTPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server((host, int(port)), Handler)
        self.host = host
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="mxnet_tpu-metrics-http")
        self._thread.start()

    def close(self):
        global _metrics_server
        self._server.shutdown()
        self._server.server_close()
        with _metrics_server_lock:
            if _metrics_server is self:
                _metrics_server = None


def start_metrics_server(port: int | None = None,
                         host: str = "127.0.0.1") -> MetricsServer:
    """Start (or return) THE process metrics server.  ``port`` None
    reads ``MXNET_METRICS_PORT`` (0/unset = refuse — use
    :func:`maybe_start_metrics_server` for the env-gated autostart);
    ``port=0`` binds an ephemeral port (the fleet-replica idiom — the
    bound port is published via an endpoint file)."""
    global _metrics_server
    with _metrics_server_lock:
        if _metrics_server is not None:
            return _metrics_server
        if port is None:
            raw = os.environ.get("MXNET_METRICS_PORT")
            try:
                port = int(raw) if raw else 0
            except ValueError:
                raise _mx_error(
                    f"MXNET_METRICS_PORT={raw!r} is not an integer "
                    "port (0/unset disables the ops endpoint)")
            if port <= 0:
                raise _mx_error(
                    "start_metrics_server(): no port given and "
                    "MXNET_METRICS_PORT is unset/0")
        if port < 0 or port > 65535:
            raise _mx_error(f"metrics port {port} out of range")
        _metrics_server = MetricsServer(port=port, host=host)
        return _metrics_server


def metrics_server_running() -> bool:
    """True when THE process metrics server is up (an operator is
    watching /statusz — the fit loop uses this to decide whether the
    in-program comm attribution is worth walking the program's HLO
    at step 1 instead of step 8)."""
    return _metrics_server is not None


def maybe_start_metrics_server():
    """Env-gated idempotent autostart: a no-op unless
    ``MXNET_METRICS_PORT`` names a positive port.  Called from the
    serving engines, the fleet router, and ``fit`` so any process
    under load is inspectable without code changes.  Returns the
    server or None."""
    raw = os.environ.get("MXNET_METRICS_PORT")
    if not raw:
        return None
    try:
        port = int(raw)
    except ValueError:
        raise _mx_error(
            f"MXNET_METRICS_PORT={raw!r} is not an integer port")
    if port <= 0:
        return None
    try:
        return start_metrics_server(port=port)
    except OSError:
        # the port is taken (a second process on this host with the
        # same env): observability must never kill the workload
        return None


# -- XLA-level tracing (the per-kernel story) ---------------------------
def start_xla_trace(logdir):
    """Start a jax.profiler trace (XPlane; view in TensorBoard/Perfetto).

    This is where TPU per-kernel timing lives — the XLA-era equivalent
    of the reference's per-op OprExecStat."""
    import jax

    jax.profiler.start_trace(logdir)


def stop_xla_trace():
    import jax

    jax.profiler.stop_trace()


# env autostart (reference: MXNET_PROFILER_AUTOSTART, env_var.md:63-72)
def _env_autostart(environ=None) -> bool:
    """Start the profiler when MXNET_PROFILER_AUTOSTART=1 — unless
    MXNET_PROFILER_NO_AUTOSTART=1 opts out (test suites and embedding
    apps must be able to import the package without a module import
    flipping global profiler state).  Returns whether it started."""
    env = os.environ if environ is None else environ
    if env.get("MXNET_PROFILER_AUTOSTART", "0") != "1":
        return False
    if env.get("MXNET_PROFILER_NO_AUTOSTART", "0") == "1":
        return False
    profiler_set_state("run")
    return True


_env_autostart()
