"""The share of the chip's idle time that a span of the program
explains: of the stretches of the traced window in which no operation
ran on the first chip, the part covered by a ``serving.*`` span other
than ``serving.idle`` (the engine had work and the chip did not).  The
five longest gaps are logged, each with the innermost program span
that covers most of it (``serving.idle`` among them: a gap it covers is
named, not attributed)."""

from benchmark import harness, trace_reduce
from benchmark.reducers import program_spans as ps


def name_gaps(holes, events, top=5):
    """The ``top`` longest holes: [span covering most of it (of equal
    cover the innermost, i.e. the shortest), seconds]."""
    out = []
    for s, e in sorted(holes, key=lambda g: g[0] - g[1])[:top]:
        best, key = "unattributed", (0.0, 0.0)
        for name, a, b, _ in events:
            cover = min(e, b) - max(s, a)
            if cover > 0 and (cover, a - b) > key:
                best, key = name, (cover, a - b)
        out.append([best, (e - s) / 1e9])
    return out


def read(sources):
    spans, trace = ps.load(sources), sources.get("trace")
    if spans is None or trace is None or not trace.device_planes():
        return None
    events = [e for e in spans.all_spans() if e[0].startswith("serving.")]
    if not events:
        return None
    lo, hi = trace.window()
    busy = [(a, b) for _, a, b in trace.ops(trace.device_planes()[0])]
    holes = trace_reduce.gaps(busy, lo, hi)
    idle = sum(e - s for s, e in holes)
    if idle <= 0:
        return None
    working = [(a, b) for n, a, b, _ in events if n != ps.IDLE_SPAN]
    covered = sum(
        trace_reduce.union_seconds(
            [(max(a, s), min(b, e)) for a, b in working
             if min(b, e) > max(a, s)])
        for s, e in holes)
    harness.log(program_spans="device_idle_attributed",
                idle_s=idle / 1e9, attributed_s=covered / 1e9,
                gaps=len(holes), longest_gaps=name_gaps(holes, events))
    return 100.0 * covered / idle
