"""Device milliseconds a step spends in collectives that no compute
hides: on the first chip, inside the whole executions of the programs
whose name holds ``program``, the time in which a collective operation
(``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``; their ``-start`` / ``-done``
halves too) runs and no other operation does, over those executions.
An asynchronous collective that is hidden shows as a short ``-start``
and a ``-done`` that returns at once; one that is exposed, as a ``-done``
that waits.  One chip's trace has no collectives: ``None``."""

from benchmark import harness
from benchmark import trace_reduce as tr

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(sources, program):
    trace = sources.get("trace")
    if trace is None or len(trace.device_planes()) < 2:
        return None
    plane = trace.device_planes()[0]
    lo, hi = trace.window()
    runs = sorted((s, s + d) for name, s, d in
                  trace.planes[plane].get(tr.MODULES_LINE, [])
                  if program in name and s >= lo and s + d <= hi)
    if not runs:
        return None
    first, last = runs[0][0], runs[-1][1]
    every, compute, n_coll = [], [], 0
    for name, a, b in trace.ops(plane):
        a, b = max(a, first), min(b, last)
        if b <= a:
            continue
        every.append((a, b))
        if name.lstrip("%").startswith(COLLECTIVES):
            n_coll += 1
        else:
            compute.append((a, b))
    exposed = tr.union_seconds(every) - tr.union_seconds(compute)
    harness.log(collective_ops_per_step=n_coll / len(runs),
                executions=len(runs),
                busy_ms_per_step=tr.union_seconds(every) / 1e6 / len(runs))
    return exposed / 1e6 / len(runs)
