"""One number of ``DecodeEngine.stats()`` over the window."""


def read(sources, key):
    stats = sources.get("engine_stats")
    if stats is None or stats.get(key) is None:
        return None
    return float(stats[key])
