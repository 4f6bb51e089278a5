"""Runner ``serve_pages``: a language model whose family brings its own
model spec (``reference/<family>.spec(cfg)``) and whose per-stream state
is ALL K/V pages — of one pool or of two (``window_pages``) — served
through ``mx.DecodeEngine(params, model=spec)``: one chip, closed or
open loop.

``serve_spec``'s run without the slot probes (there is no slot to read
back): the engine, the accounting of a window's tokens, the reference's
feed and the sample's gaps are ``serve_spec``'s, by import; the load,
the warm-up, the window and the sample of finished requests
``serve_lm``'s.  The run is held by the served LOGITS alone: over a
seeded sample of finished requests — the longest among them, which lies
beyond any window — the widest and the mean gap of a served token's
logit under the float32 reference's best.

``CONTROLS`` (empty in a benchmark run; ``benchmark/control_pages.py``
sets it) names forms of the reference — a lower precision, or a
mechanism left out — that are put through the same checks against the
same limits on the first ``CONTROL_REQUESTS`` of the sample: each must
read ``correct: false``.
"""

import gc
import time

import numpy as np

from benchmark import harness, stats
from benchmark.runners.serve_lm import (KERNEL, LATE_LIMIT_SHARE,
                                        pick_sample, serve_window, warm_up)
from benchmark.runners.serve_spec import account, build_engine, sample_gaps

# requests a control is computed on (each is another pass of the
# reference); the sample's first is the longest finished request
CONTROL_REQUESTS = 2
CONTROLS = ()
VERDICTS = {}       # control -> did it pass every check (it must not)

ENGINE_KEYS = (
    "requests", "tokens", "prefills", "prefill_tokens", "steps",
    "stream_steps", "preempted", "d2h_syncs", "d2h_syncs_saved",
    "context_tokens", "ttft_p50_ms", "p50_ms", "p99_ms", "active_streams",
    "pending", "cache_util", "moe_pairs_here", "moe_pairs_elsewhere",
    "moe_experts_hit", "moe_load_max", "window_pages", "window_pages_live",
    "window_pages_released", "window_pages_held_share",
    "window_context_tokens", "window_prefill_pairs")


def held(prefix, limits, logit, results):
    """The two held numbers, each beside its limit."""
    harness.check(prefix + "served_logit_gap_widest", float(logit.max()),
                  limits["logit_gap_widest"], results)
    harness.check(prefix + "served_logit_gap_mean", float(logit.mean()),
                  limits["logit_gap_mean"], results)
    return all(results)


def serve_check(run, sample, ref):
    """Each number compared, beside its limit; then every control of
    ``CONTROLS`` through the same checks (must read false: logged, and
    no part of this run's ``correct``)."""
    limits = run.cell.workload["limits"]
    if not sample:
        harness.log(error="no request finished: nothing to compare")
        return False
    wl = run.cell.workload
    w = ref.to_float32(ref.draw(run.cell.config, run.seed,
                                embed_dtype=wl["dtype"], dtype=wl["dtype"]))
    flat, _ = sample_gaps(run, sample, ref, w)
    harness.log(compared_requests=len(sample), compared_tokens=len(flat),
                compared_lengths=[len(p) + len(o) for p, o in sample],
                tokens_equal_reference_best=int(np.sum(flat == 0.0)))
    ok = held("", limits, flat, [])
    for p in CONTROLS:
        low, differ = sample_gaps(run, sample[:CONTROL_REQUESTS], ref, w, p)
        passed = held(f"control.{p}.", limits, low, [])
        VERDICTS[p] = passed
        harness.log(control=p, correct=passed,
                    requests=min(CONTROL_REQUESTS, len(sample)),
                    tokens=len(low),
                    positions_expert_sets_differ=int(differ.sum()))
    return ok


def run(run):
    ref = harness.plugin("reference", run.cell.config["family"])
    generate = harness.plugin("traffic", run.cell.traffic["generator"])
    cfg, wl, mix = run.cell.config, run.cell.workload, run.cell.traffic
    vocab = cfg["vocab_size"]
    # first of all: a program that has no such family ends the run here,
    # in seconds, before anything is drawn
    spec = ref.spec(cfg)

    weights = ref.program_names(ref.draw(
        cfg, run.seed, embed_dtype=wl["dtype"], dtype=wl["dtype"]))
    run.mark("weights_drawn")
    eng = build_engine(run, weights, spec)
    del weights
    run.mark("engine_built")
    try:
        reqs = generate.requests(mix, run.seed, run.seconds, vocab)
        warm_up(run, eng, vocab)
        run.mark("warmed_up")
        load, sent, st, t0 = serve_window(run, eng, reqs)
    except BaseException:
        eng.close()  # the engine's thread must not outlive a failure
        raise
    t_end = t0 + run.seconds
    peak = run.memory_peak()

    a = account(run, load, sent, t0)
    late = [load.sent[i] - load.due[i] for i in sent]
    harness.log(attempted=a["attempted"], failed=a["failed"],
                sent_in_all=len(sent),
                resolved_in_window=a["whole_requests"],
                serve_out_tokens_per_s=a["tokens"] / run.seconds,
                tokens_per_s_by_whole_requests=a["whole_tokens"]
                / run.seconds,
                errors=[load.error[i] for i in sent if load.error[i]
                        and load.due[i] < t_end][:3],
                lateness_ms_p50=1e3 * stats.percentile(late, 50),
                lateness_ms_max=1e3 * max(late),
                request_ms_p50=stats.percentile(a["lat_ms"], 50),
                request_ms_p95=stats.percentile(a["lat_ms"], 95),
                engine={k: st.get(k) for k in ENGINE_KEYS})
    correct = True
    # (a traced run is exempt: the profiler stalls the host)
    late_limit = LATE_LIMIT_SHARE * run.seconds
    if not run.trace and max(late) > late_limit:
        harness.log(error="the generator ran late", max_s=max(late),
                    limit_s=late_limit)
        correct = False
    if a["failed"]:
        correct = False
    run.extras["engine_stats"] = st
    run.extras["engine"] = dict(wl["engine"])

    kernel_ok = True
    if run.devices[0].platform == "tpu":
        for key in [k for k in eng.compiles if k[0] == "decode"]:
            kernel_ok = kernel_ok and KERNEL in eng.executable_text(key)
        harness.log(check="kernel_in_decode_executables", marker=KERNEL,
                    ok=kernel_ok)
    sample = pick_sample(run, load, sent)
    eng.close()
    del eng, load.eng
    gc.collect()
    t_ref = time.perf_counter()
    correct = serve_check(run, sample, ref) and correct
    harness.log(reference_s=time.perf_counter() - t_ref)
    metrics = {
        "serve_out_tokens_per_s": a["tokens"] / run.seconds,
        "serve_request_p95_ms": stats.percentile(a["lat_ms"], 95)}
    return {"correct": correct and kernel_ok,
            "attempted": a["attempted"], "failed": a["failed"],
            "metrics": metrics, "memory_peak_bytes": peak}
