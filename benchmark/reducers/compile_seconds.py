"""Seconds of set-up that jax spent building programs, by kind: the
program's own log of jax's compile events
(``mxnet_tpu.profiler.compile_events()``: Python tracing, lowering to
MLIR, the backend's compile-or-fetch), summed over the events that
ended before the window opened (the reference's own compiles come
after it).  ``kinds`` names what is summed.  A program without that
log reports nothing."""

from benchmark import harness

BIN_S = 2.0


def read(sources, kinds):
    run = sources.get("run")
    if run is None or run.t0 is None:
        return None
    try:
        from mxnet_tpu import profiler
        events = profiler.compile_events()
    except (ImportError, AttributeError):
        return None
    # this run's set-up: from the process's start to the window's
    t_process = getattr(run, "t_process", None)
    lo = float("-inf") if t_process is None else t_process
    events = [e for e in events if lo <= e[0] <= run.t0]
    by_kind = {}
    for _, kind, seconds in events:
        by_kind[kind] = by_kind.get(kind, 0.0) + seconds
    if t_process is not None:
        # on the clock of the run's ``mark`` lines, what a table of
        # set-up by phase is read from: the long events, and every
        # event by the 2 s of set-up it ended in
        harness.log(compile_events_of_250ms_or_more=[
            [round(t_end - t_process, 2), kind, round(seconds, 2)]
            for t_end, kind, seconds in events if seconds >= 0.25][:80])
        bins = {}
        for t_end, kind, seconds in events:
            row = bins.setdefault(
                kind, [0.0] * (int((run.t0 - t_process) / BIN_S) + 1))
            row[int((t_end - t_process) / BIN_S)] += seconds
        harness.log(compile_seconds_by_bin={
            k: [round(x, 2) for x in v] for k, v in bins.items()},
            bin_s=BIN_S)
    harness.log(compile_seconds_in_setup=by_kind, summed=list(kinds))
    return sum(by_kind.get(k, 0.0) for k in kinds)
