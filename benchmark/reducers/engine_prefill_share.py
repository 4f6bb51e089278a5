"""The share of the engine thread's working time that is prefill:
sum of ``serving.prefill.*`` spans over the sum of those and the
``serving.step`` spans, inside the traced window (clipped to it)."""

from benchmark.reducers import program_spans as ps


def read(sources):
    spans, trace = ps.load(sources), sources.get("trace")
    if spans is None or trace is None:
        return None
    thread = spans.engine_thread()
    lo, hi = trace.window()
    prefill = ps.clipped_ns(
        [e for e in thread if e[0].startswith(ps.PREFILL_PREFIX)], lo, hi)
    steps = ps.clipped_ns(
        [e for e in thread if e[0] == ps.STEP_SPAN], lo, hi)
    if prefill + steps <= 0:
        return None
    return 100.0 * prefill / (prefill + steps)
