"""How uneven the routed load on the held experts is: the largest
expert's count over the mean count, both summed over layers and decode
steps — ``moe_load_max x held / moe_pairs_here`` from the engine's
counters (1 = even)."""


def read(sources):
    stats, cell = sources.get("engine_stats"), sources.get("cell")
    if not stats or not stats.get("moe_pairs_here"):
        return None
    held = int(cell.config["n_routed_experts"])
    return stats["moe_load_max"] * held / stats["moe_pairs_here"]
