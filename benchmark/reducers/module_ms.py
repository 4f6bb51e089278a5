"""Device milliseconds per execution of one compiled program: the
``XLA Modules`` events whose name holds one of ``match``; where several
do, the one with most time."""


def read(sources, match):
    trace = sources.get("trace")
    if trace is None:
        return None
    found = {k: v for k, v in trace.modules().items()
             if any(m in k for m in match)}
    if not found:
        return None
    n, seconds = max(found.values(), key=lambda v: v[1])
    return 1e3 * seconds / n
