"""What every runner shares: finding a cell's files by name, the clock
of a run (set-up, window, traced slice), the log of compilations, and
the benchmark's own spans around its calls into the program.

Nothing here knows a cell, a configuration or a metric by name: they
are files, found through ``BENCHMARK.json``.
"""

import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def log(**fields):
    """One JSON line on stdout, before the result line."""
    print(json.dumps(fields), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """A cell's files, found by the names ``BENCHMARK.json`` gives."""

    def __init__(self, root, workload):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in entries:
            raise SystemExit(
                f"benchmark: no workload {workload!r} in BENCHMARK.json "
                f"(has {sorted(entries)})")
        self.entry = entries[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.dir = os.path.join(root, self.bench["paths"][0])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(
            os.path.join(root, configs[self.entry["config"]]["file"]))
        self.workload = load_json(
            os.path.join(self.dir, "workloads", workload + ".json"))
        self.traffic = load_json(
            os.path.join(self.dir, "traffic",
                         self.entry["traffic"] + ".json"))

    def reports(self, metric):
        """Does this cell report ``metric`` (an entry of ``end_to_end``
        or ``per_layer``)?"""
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self):
        """The per-layer metrics this cell may report, with the reader
        file of each."""
        out = []
        for m in self.bench["per_layer"]:
            if self.reports(m):
                spec = load_json(os.path.join(
                    self.dir, "layer_metrics", m["name"] + ".json"))
                out.append((m, spec))
        return out


def plugin(kind, name):
    """``benchmark/<kind>/<name>.py``: a runner, a traffic generator, a
    reference, a counter of operations or a reducer, by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


class CompileLog:
    """Counts programs built (compiled, or fetched from the persistent
    cache) and cache misses, split at the start of the window."""

    def __init__(self):
        import jax.monitoring as mon

        self.setup = self.window = self.misses = 0
        self.in_window = False
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event != COMPILE_EVENT:
            return
        if self.in_window:
            self.window += 1
        else:
            self.setup += 1

    def _on_event(self, event, **kw):
        if event == CACHE_MISS_EVENT:
            self.misses += 1


class Run:
    """The clock and the instruments of one run of one cell."""

    def __init__(self, cell, seed, seconds, trace, t_process,
                 keep_trace=None):
        import jax

        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_process = t_process
        self.keep_trace = keep_trace
        self.compiles = CompileLog()
        self.devices = jax.devices()[:cell.chips]
        self.trace_dir = None
        self.traced = False         # a slice of the window was traced
        self._tracing = False
        self.t0 = self.t1 = None
        self.setup_s = None
        self.extras = {}            # what runners hand the reducers

    def mark(self, label):
        """Where set-up's seconds go: one line per phase, on the run's
        own clock."""
        log(mark=label, t=round(time.perf_counter() - self.t_process, 3))

    # -- spans of the benchmark's own, in the profiler's trace --------
    @contextlib.contextmanager
    def span(self, name):
        if not self._tracing:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation("bench:" + name):
            yield

    # -- the window ---------------------------------------------------
    def start_window(self):
        """Set-up ends here: everything before is ``setup_s``."""
        self.compiles.in_window = True
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - self.t_process
        return self.t0

    def trace_seconds(self):
        return float(self.cell.workload.get("trace_seconds", 3.0))

    def tick(self):
        """Called by the runner from its loop: starts the profiler for
        the last ``trace_seconds`` of the window of a ``--trace 1``
        run."""
        if not self.trace or self._tracing or self.traced:
            return
        now = time.perf_counter()
        if now - self.t0 >= max(0.0, self.seconds - self.trace_seconds()):
            import jax

            base = os.environ.get("TMPDIR") or tempfile.gettempdir()
            self.trace_dir = self.keep_trace or tempfile.mkdtemp(
                prefix="bench-trace-", dir=base)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no event per Python call
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=opts)
            self._tracing = True
            self._window_span = jax.profiler.TraceAnnotation(
                "bench:window")
            self._window_span.__enter__()

    def end_window(self):
        """The window has closed (the runner has waited for the device).
        Stops the profiler where it runs."""
        self.t1 = time.perf_counter()
        self.compiles.in_window = False
        if self._tracing:
            import jax

            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._tracing = False
            self.traced = True
        return self.t1 - self.t0

    def cleanup(self):
        if self.trace_dir and not self.keep_trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    # -- the device ---------------------------------------------------
    def memory_peak(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)


def check(name, value, limit, results):
    """Print one compared number beside its limit; remember whether it
    passed (a NaN does not)."""
    ok = value is not None and value <= limit
    log(check=name, value=value, limit=limit, ok=bool(ok))
    results.append(bool(ok))
    return ok


def fail(message):
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    raise SystemExit(3)
