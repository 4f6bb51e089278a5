"""The program's own spans, read from the run's trace.

``mxnet_tpu.profiler.scope`` writes every span of the framework into
the profiler's trace as a ``TraceAnnotation``: it lands on its thread's
line of ``/host:CPU`` in the same ``.xplane.pb`` as the device's ``XLA
Ops``, on the same clock.  ``trace_reduce.Trace`` keeps only the
benchmark's ``bench:`` spans, so the readers of the program's spans
open the file themselves, through this one cached helper.

What is kept: per host thread (a line of ``/host:CPU``; the engine's
is titled ``mx-decode-loop``) the events whose name starts with one of
``PREFIXES``, as ``(name, start_ns, end_ns, stats)``.  Device
operations and the traced window come from ``sources["trace"]``: one
file, one clock.  A program that writes no such span (the parent of
the PR that brought them) leaves every thread empty, and each reader
returns ``None``.
"""

import functools
import glob
import gzip
import json
import os

HOST_PLANE = "/host:CPU"
PREFIXES = ("serving.", "Module.", "Executor.", "fit.")
IDLE_SPAN = "serving.idle"
STEP_SPAN = "serving.step"
SYNC_SPAN = "serving.d2h_sync"
PREFILL_PREFIX = "serving.prefill."
PROGRAM_PREFIXES = ("serving.decode_step.", "serving.verify_step.")


class ProgramSpans:
    def __init__(self, threads):
        # {thread: [(name, start_ns, end_ns, stats), ...]}, by start
        self.threads = {t: sorted(ev, key=lambda e: (e[1], -e[2]))
                        for t, ev in threads.items() if ev}

    # -- reading ------------------------------------------------------
    @classmethod
    def from_xplane(cls, path):
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        threads = {}
        for plane in data.planes:
            if plane.name != HOST_PLANE:
                continue
            for i, line in enumerate(plane.lines):
                events = [(ev.name, float(ev.start_ns),
                           float(ev.start_ns + ev.duration_ns),
                           {k: v for k, v in ev.stats})
                          for ev in line.events
                          if ev.name.startswith(PREFIXES)]
                if events:
                    threads[f"{line.name}#{i}"] = events
        return cls(threads)

    @classmethod
    def from_json(cls, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            raw = json.load(f)
        return cls({t: [(n, float(s), float(e), dict(st))
                        for n, s, e, st in ev]
                    for t, ev in raw.items()})

    def to_json(self, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump({t: [[n, s, e, {k: str(v) for k, v in st.items()}]
                           for n, s, e, st in ev]
                       for t, ev in self.threads.items()}, f)

    # -- pieces -------------------------------------------------------
    def engine_thread(self):
        """The events of the thread that ran the decode loop (the one
        with ``serving.step`` spans; where several engines ran, the one
        with most), or ``[]``."""
        best, most = [], 0
        for events in self.threads.values():
            n = sum(1 for e in events if e[0] == STEP_SPAN)
            if n > most:
                best, most = events, n
        return best

    def all_spans(self):
        return [e for events in self.threads.values() for e in events]


def inside(events, lo, hi):
    """The events that lie wholly inside [lo, hi]."""
    return [e for e in events if e[1] >= lo and e[2] <= hi]


def clipped_ns(events, lo, hi):
    """Summed length of the events' parts inside [lo, hi]."""
    return sum(max(0.0, min(e[2], hi) - max(e[1], lo)) for e in events)


def children(events, parent, name=None, prefixes=None):
    """Events that lie inside ``parent`` (another event of the same
    thread), by exact ``name`` or by ``prefixes``."""
    out = []
    for e in events:
        if e is parent or e[1] < parent[1] or e[2] > parent[2]:
            continue
        if (name is not None and e[0] == name) or \
                (prefixes is not None and e[0].startswith(prefixes)):
            out.append(e)
    return out


@functools.lru_cache(maxsize=2)
def _from_dir(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return ProgramSpans.from_xplane(max(paths, key=os.path.getmtime))


def load(sources):
    """The traced run's program spans, or ``None`` (no trace).  A test
    hands its recorded ones in as ``sources["program_spans"]``."""
    given = sources.get("program_spans")
    if given is not None:
        return given
    run = sources.get("run")
    if run is None or not getattr(run, "traced", False) \
            or not run.trace_dir:
        return None
    return _from_dir(run.trace_dir)
