"""The share of the device's program time that the programs whose name
holds one of ``match`` take: whole ``XLA Modules`` executions of those,
over the whole executions of every program (first chip, traced
slice)."""


def read(sources, match):
    trace = sources.get("trace")
    if trace is None:
        return None
    modules = trace.modules()
    total = sum(seconds for _, seconds in modules.values())
    if total <= 0:
        return None
    return 100.0 * sum(seconds for name, (_, seconds) in modules.items()
                       if any(m in name for m in match)) / total
