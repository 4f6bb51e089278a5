"""Host milliseconds the engine's thread spends per decode program.

Each ``serving.step`` span (one scheduling step, from its first line to
its last) minus its ``serving.d2h_sync`` children (where the host waits
for the device) is the step's self time: preparing the batch, staging,
dispatch, booking the results, resolving futures.  Summed over the
steps wholly inside the traced window and divided by the decode
programs dispatched inside them (``serving.decode_step.*``: a pipelined
pair is one ``serving.step`` and two programs).
"""

from benchmark import harness
from benchmark.reducers import program_spans as ps


def read(sources):
    spans, trace = ps.load(sources), sources.get("trace")
    if spans is None or trace is None:
        return None
    thread = spans.engine_thread()
    lo, hi = trace.window()
    steps = ps.inside([e for e in thread if e[0] == ps.STEP_SPAN], lo, hi)
    self_ns, programs = 0.0, 0
    for step in steps:
        syncs = ps.children(thread, step, name=ps.SYNC_SPAN)
        self_ns += (step[2] - step[1]) - sum(e[2] - e[1] for e in syncs)
        programs += len(ps.children(thread, step,
                                    prefixes=ps.PROGRAM_PREFIXES))
    if not programs:
        return None
    harness.log(program_spans="engine_host_ms_per_step",
                steps=len(steps), programs=programs,
                self_ms=self_ns / 1e6)
    return self_ns / 1e6 / programs
