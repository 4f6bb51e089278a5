"""How uneven the routed load on the held experts is — ``moe_load``'s
number for a family whose configuration counts the experts held under
another key: ``moe_load_max x held / moe_pairs_here`` from the engine's
counters (both summed over layers and decode steps; 1 = even), ``held``
= ``config[held_key]``."""


def read(sources, held_key):
    stats, cell = sources.get("engine_stats"), sources.get("cell")
    if not stats or not stats.get("moe_pairs_here"):
        return None
    return stats["moe_load_max"] * int(cell.config[held_key]) \
        / stats["moe_pairs_here"]
