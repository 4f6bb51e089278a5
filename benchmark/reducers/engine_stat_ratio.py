"""One number of ``DecodeEngine.stats()`` over another, both summed
over the window: ``stats[over] / stats[under]`` — the rows an expert a
decode step worked on, say (``moe_pairs_here / moe_experts_hit``: both
count over layers and steps).  ``None`` where either is absent or the
divisor 0 (a program without the counters)."""


def read(sources, over, under):
    stats = sources.get("engine_stats")
    if not stats or stats.get(over) is None or not stats.get(under):
        return None
    return float(stats[over]) / float(stats[under])
