#!/usr/bin/env python3
"""Read, in one process, what the limits of a cell's comparison are set
from ("How ``correct`` is decided", steps 3 to 5): the numbers that
sound runs of the program give over a dozen seeds, and the numbers that
the control gives (the reference computed in fp8, the nearest precision
below the bfloat16 the configurations state) on three.

    python3 benchmark/control.py --workload <name> \
        --seeds 11,12,... --control-seeds 11,12,13 [--seconds 8]

Training needs no window.  Serving runs a short window per seed at the
cell's own load on ONE engine (``swap_params`` between seeds), then
frees the engine and runs the references.  Writes every reading to
``chiprun_out/control-<workload>.json``.  Not run by the benchmark.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def train(cell, seeds, control_seeds, seconds, precision, harness):
    from benchmark import compare
    from benchmark.runners import train_lm

    ref = harness.plugin("reference", cell.config["family"])
    wl, limits = cell.workload, cell.workload["limits"]
    lr = wl["optimizer_params"]["learning_rate"]
    rows = []

    def gaps(a, b):
        out = {f"loss_step{i + 1}_rel_gap": abs(x - y) / abs(y)
               for i, (x, y) in enumerate(zip(a["losses"], b["losses"]))}
        out["token_loss_rms_gap"] = train_lm.token_loss_rms_gap(a, b)
        out["grad_norm_gap"], out["grad_leaf"] = compare.worst_leaf_gap(
            a["grad_norm"], b["grad_norm"])
        out["change_norm_gap"], out["change_leaf"] = \
            compare.worst_leaf_gap(a["change_norm"], b["change_norm"],
                                   skip=limits.get("change_skip", ()))
        return out

    # the program's readings first, on ONE compiled step (its weights
    # and Adam's state put back to the seed's between seeds: building a
    # module costs minutes); then the program is freed for the reference
    trainer, readings = None, {}
    generate = harness.plugin("traffic", cell.traffic["generator"])
    for seed in seeds:
        run = harness.Run(cell, seed, seconds, 0, time.perf_counter())
        if trainer is None:
            trainer, tokens, program = train_lm.set_up(run, ref)
        else:
            reset(trainer, train_lm.seeded_weights(run, ref))
            tokens = generate.token_batches(cell.traffic, seed,
                                            cell.config["vocab_size"])
            trainer.feed(tokens)
            program = train_lm.first_steps(run, trainer, tokens, ref)
        readings[seed] = (tokens, program)
        harness.log(seed=seed, program_losses=program["losses"])
    del trainer
    gc.collect()
    for seed in seeds:
        tokens, program = readings[seed]
        reference = ref.train_three(cell.config, seed, tokens, lr,
                                    micro=int(wl.get("reference_micro", 2)))
        row = {"seed": seed, "program": gaps(program, reference)}
        if seed in control_seeds:
            low = ref.train_three(cell.config, seed, tokens, lr,
                                  precision=precision,
                                  micro=int(wl.get("reference_micro", 2)))
            row["control"] = gaps(low, reference)
        harness.log(**row)
        rows.append(row)
    return rows


def reset(trainer, weights):
    """Put a built module back to seeded weights and fresh Adam state,
    keeping its compiled step (the control's shortcut, not the
    benchmark's: every run of a cell builds its own)."""
    import jax
    import jax.numpy as jnp

    mod, mx = trainer.mod, trainer.mx
    mod.init_params(initializer=None, force_init=True,
                    arg_params={k: mx.nd.NDArray(v, trainer.ctx)
                                for k, v in weights.items()})
    mod._fused_state = jax.tree_util.tree_map(jnp.zeros_like,
                                              mod._fused_state)
    mod._fused_t = jnp.zeros_like(mod._fused_t)
    mod._step_count = 0
    trainer.steps = 0


def sweep(cell, rates, seconds, harness):
    """One engine, one window per offered rate: what comes back, how
    late, and what is still unfinished when the window closes."""
    from benchmark import stats
    from benchmark.runners import serve_lm

    ref = harness.plugin("reference", cell.config["family"])
    generate = harness.plugin("traffic", cell.traffic["generator"])
    cfg, wl = cell.config, cell.workload
    serve_lm.DRAIN_S = 120.0  # see every request through
    eng, rows = None, []
    for k, rate in enumerate(rates):
        run = harness.Run(cell, 4242 + k, seconds, 0, time.perf_counter())
        if eng is None:
            weights = ref.program_names(ref.draw(
                cfg, run.seed, embed_dtype=wl["dtype"], dtype=wl["dtype"]))
            eng = serve_lm.build_engine(run, weights)
            del weights
            serve_lm.warm_up(run, eng, cfg["vocab_size"])
        mix = json.loads(json.dumps(cell.traffic))
        if mix["arrivals"]["process"] == "closed":
            mix["arrivals"]["clients"] = int(rate)
        else:
            mix["arrivals"]["rate"] = float(rate)
        run.cell.traffic = mix
        eng.reset_stats()
        reqs = generate.requests(mix, run.seed, seconds, cfg["vocab_size"])
        load, sent, st, t0 = serve_lm.serve_window(run, eng, reqs)
        t_end = t0 + seconds
        lat = [1e3 * (load.done[i] - load.due[i]) for i in sent
               if load.done[i] is not None]
        done_in = [i for i in sent if load.done[i] is not None
                   and load.done[i] <= t_end]
        row = {"rate": rate, "sent": len(sent),
               "finished_in_window": len(done_in),
               "backlog_at_window_end": len(sent) - len(done_in),
               "never_finished": sum(load.done[i] is None for i in sent),
               "tokens_per_s": sum(len(load.out[i]) for i in done_in)
               / seconds,
               "request_ms_p50": stats.percentile(lat, 50),
               "request_ms_p95": stats.percentile(lat, 95),
               "request_ms_max": max(lat) if lat else None,
               "last_done_after_window_s": max(
                   load.done[i] for i in sent
                   if load.done[i] is not None) - t_end,
               "engine_time_per_token_p50_ms": st["p50_ms"],
               "engine_ttft_p50_ms": st["ttft_p50_ms"],
               "steps": st["steps"], "stream_steps": st["stream_steps"],
               "prefills": st["prefills"],
               "compiled_in_window": run.compiles.window}
        harness.log(**row)
        rows.append(row)
        load.eng = None
        settle(eng)
    eng.close()
    return rows


def settle(eng):
    """A closed loop leaves requests in flight past its window: wait
    for them before the engine's weights or load change."""
    while True:
        st = eng.stats()
        if not st["active_streams"] and not st["pending"]:
            return
        time.sleep(0.1)


def serve(cell, seeds, control_seeds, seconds, precision, harness):
    import numpy as np

    from benchmark.runners import serve_lm

    ref = harness.plugin("reference", cell.config["family"])
    generate = harness.plugin("traffic", cell.traffic["generator"])
    cfg, wl = cell.config, cell.workload
    eng, samples = None, {}
    for seed in seeds:
        run = harness.Run(cell, seed, seconds, 0, time.perf_counter())
        weights = ref.program_names(ref.draw(
            cfg, seed, embed_dtype=wl["dtype"], dtype=wl["dtype"]))
        if eng is None:
            eng = serve_lm.build_engine(run, weights)
            serve_lm.warm_up(run, eng, cfg["vocab_size"])
        else:
            eng.swap_params({k: np.asarray(v) for k, v in weights.items()})
        del weights
        reqs = generate.requests(cell.traffic, seed, seconds,
                                 cfg["vocab_size"])
        load, sent, st, _ = serve_lm.serve_window(run, eng, reqs)
        samples[seed] = (run, serve_lm.pick_sample(run, load, sent))
        harness.log(seed=seed, sent=len(sent),
                    finished=sum(load.out[i] is not None for i in sent),
                    compiled_in_window=run.compiles.window)
        load.eng = None
        settle(eng)
    eng.close()
    del eng
    gc.collect()
    rows = []
    for seed in seeds:
        run, sample = samples[seed]
        low = precision if seed in control_seeds else "float32"
        served, lowgap = serve_lm.sample_gaps(run, sample, ref, low)
        row = {"seed": seed, "tokens": int(len(served)),
               "program": {"logit_gap_widest": float(served.max()),
                           "logit_gap_mean": float(served.mean()),
                           "equal_best": int((served == 0).sum())}}
        if seed in control_seeds:
            row["control"] = {"logit_gap_widest": float(lowgap.max()),
                              "logit_gap_mean": float(lowgap.mean()),
                              "equal_best": int((lowgap == 0).sum())}
        harness.log(**row)
        rows.append(row)
    return rows


def main(argv=None, root=ROOT, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--sweep", default="",
                    help="rates (open loop) or client counts (closed) "
                         "to offer, one window each, instead")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)
    from benchmark import harness, run as bench_run

    cell = harness.Cell(root, args.workload)
    import mxnet_tpu  # noqa: F401  (before jax)

    bench_run.find_devices(cell, require_tpu)
    from mxnet_tpu.config import place_compile_cache

    place_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    if args.sweep:
        rows = sweep(cell, [float(r) for r in args.sweep.split(",")],
                     args.seconds, harness)
        with open(os.path.join(args.out,
                               f"sweep-{args.workload}.json"), "w") as f:
            json.dump(rows, f, indent=1)
        return rows
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    kind = {"train_lm": train, "serve_lm": serve}[cell.workload["runner"]]
    rows = kind(cell, seeds, control, args.seconds, args.precision,
                harness)
    summary = {"workload": args.workload, "precision": args.precision,
               "rows": rows}
    for key in rows[0]["program"]:
        if isinstance(rows[0]["program"][key], str) or key == "equal_best":
            continue
        sound = [r["program"][key] for r in rows]
        low = [r["control"][key] for r in rows if "control" in r]
        summary[key] = {"sound_largest": max(sound),
                        "control_smallest": min(low) if low else None}
    harness.log(**{k: v for k, v in summary.items() if k != "rows"})
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"control-{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return summary


if __name__ == "__main__":
    main()
