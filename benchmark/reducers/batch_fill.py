"""How full the decode batch ran: stream-steps over steps x the widest
batch, from the engine's counters over the window."""


def read(sources):
    stats, engine = sources.get("engine_stats"), sources.get("engine")
    if not stats or not engine or not stats.get("steps"):
        return None
    return 100.0 * stats["stream_steps"] / (
        stats["steps"] * engine["max_streams"])
