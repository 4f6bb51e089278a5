#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

A new process each time.  It refuses to start without the TPU chips the
cell asks for, places the compile cache, builds the cell from its files
(``workloads/``, ``configs/``, ``traffic/``, found by the names in
``BENCHMARK.json``), lets the cell's runner warm up the cell's own
shapes, measure for ``--seconds`` and check the timed path against the
plain reference, and prints one JSON object as the last line: the
cell's end-to-end metrics (``--trace 0``) or its per-layer metrics
(``--trace 1``).  It spawns nothing.
"""

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profiler's trace here and keep it "
                         "(for looking at one by hand)")
    return ap.parse_args(argv)


def find_devices(cell, require_tpu=True):
    """The chips the cell asks for, or no run."""
    import jax

    from benchmark import peaks

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            print(f"benchmark: needs a TPU, jax reports {dev.platform!r} "
                  f"devices", file=sys.stderr)
            raise SystemExit(2)
        if len(devices) < cell.chips:
            print(f"benchmark: cell {cell.name} asks for {cell.chips} "
                  f"chip(s), jax reports {len(devices)}", file=sys.stderr)
            raise SystemExit(2)
        peaks.lookup(dev.device_kind)  # an unknown kind ends the run
    return devices[:cell.chips]


def layer_metrics(cell, run):
    """Each per-layer metric through its own reader; one that finds
    nothing to read is left out of the line."""
    from benchmark import harness, trace_reduce

    trace = None
    if run.traced:
        trace = trace_reduce.Trace.from_dir(run.trace_dir)
    sources = {"trace": trace, "compile_log": run.compiles,
               "cell": cell, "run": run, **run.extras}
    metrics = {}
    for entry, spec in cell.per_layer():
        reducer = harness.plugin("reducers", spec["reducer"])
        value = reducer.read(sources, **spec.get("args", {}))
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    return metrics, trace


def main(argv=None, root=ROOT, require_tpu=True):
    args = parse(argv)
    from benchmark import harness

    cell = harness.Cell(root, args.workload)
    # the package before jax: it hands libtpu its flags before a backend
    # exists.  Alone in a directory, without the package, the run ends
    # here: non-zero, no result.
    import mxnet_tpu  # noqa: F401
    import jax

    devices = find_devices(cell, require_tpu)
    from mxnet_tpu.config import place_compile_cache

    cache_dir = place_compile_cache()
    run = harness.Run(cell, args.seed, args.seconds, args.trace,
                      T_PROCESS, keep_trace=args.keep_trace)
    dev = devices[0]
    harness.log(start=cell.name, seed=args.seed, seconds=args.seconds,
                trace=args.trace, device_kind=dev.device_kind,
                devices=len(devices), jax=jax.__version__,
                compile_cache_dir=cache_dir)
    runner = harness.plugin("runners", cell.workload["runner"])
    try:
        outcome = runner.run(run)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": outcome["memory_peak_bytes"]}
        result = {"correct": bool(outcome["correct"]),
                  "attempted": int(outcome["attempted"]),
                  "failed": int(outcome["failed"])}
        if run.compiles.window:
            harness.log(error="programs were compiled inside the window",
                        count=run.compiles.window)
            result["correct"] = False
        if args.trace:
            metrics, trace = layer_metrics(cell, run)
            if trace is not None:
                busy, window = trace.busy_and_window()
                device["busy_s"], device["window_s"] = busy, window
                result["breakdown"] = trace.breakdown()
        else:
            names = {m["name"]: m for m in cell.end_to_end()}
            values = dict(outcome["metrics"], setup_s=run.setup_s)
            metrics = {n: {"value": float(values[n]), "unit": m["unit"]}
                       for n, m in names.items() if n in values}
        result["metrics"] = metrics
        result["device"] = device
    finally:
        run.cleanup()
    harness.log(setup_s=run.setup_s,
                programs_built_in_setup=run.compiles.setup,
                programs_built_in_window=run.compiles.window,
                compile_cache_misses=run.compiles.misses,
                total_s=time.perf_counter() - T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
