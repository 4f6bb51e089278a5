"""Runner ``serve_pages_relative``: ``serve_pages``' run, its served gap
held RELATIVE to what the reference's own bfloat16 form reads on the
same sample.

For a model whose served gap swings with the draw more than a lower
precision moves it.  Under one expert a token a choice that flips under
rounding moves that token's whole stream, so how far a sound program's
served tokens lie below the float32 reference's best is a property of
the seed (how flat its logits are, how near its choices): over seeds the
mean gap spreads twelve-fold, and fp8 linear layers, 5-14 x their own
seed's sound mean, lie inside that spread (PERF.md section 2).  The
seed's own yardstick is the reference itself in the precision the
configuration computes in (``OWN``: its products multiplied in bfloat16
AND every activation a layer hands on held in bfloat16, as a program of
that compute type holds them): teacher-forced on the same tokens, the
token it puts first lies below the float32 reference's best by what
bfloat16 costs THERE.  Held, over a seeded sample of
finished requests with the longest:

* ``served_logit_gap_widest`` — the widest gap of a served token's
  logit below the float32 reference's best (``logit_gap_widest``): a
  wrong token;
* ``served_logit_gap_mean_over_bfloat16`` — the mean of those gaps over
  the mean gap of ``OWN``'s first tokens on the same positions
  (``logit_gap_mean_over_bfloat16``), the divisor no less than
  ``logit_gap_mean_floor`` (a sample on which bfloat16 costs nothing
  measures no ratio).

The engine, the load, the warm-up, the window, the accounting and the
sample are ``serve_pages``', ``serve_spec``'s and ``serve_lm``'s, by
import; :func:`run` is ``serve_pages.run`` with this comparison in the
other's place (that file calls its own by name).

``CONTROLS`` (empty in a benchmark run; ``benchmark/control_cca.py``
sets it) names forms of the reference — a lower precision, or a
mechanism left out — whose first tokens are put through the same checks
against the same limits and the same divisor, on the first
``CONTROL_REQUESTS`` of the sample (None: all of it): each must read
``correct: false``.
"""

import gc
import time

import numpy as np

from benchmark import harness, stats
from benchmark.runners.serve_lm import (KERNEL, LATE_LIMIT_SHARE,
                                        pick_sample, serve_window, warm_up)
from benchmark.runners.serve_pages import ENGINE_KEYS
from benchmark.runners.serve_spec import (account, build_engine,
                                          reference_feed)

OWN = "bfloat16_held"   # the precision the configuration computes in
CONTROL_REQUESTS = None
CONTROLS = ()
VERDICTS = {}       # control -> did it pass every check (it must not)


def request_gaps(run, sample, ref, w, precision):
    """Per request of the sample, teacher-forced through the float32
    reference: (the gaps of the served tokens below its best logit, the
    gaps of the tokens that ``precision`` puts first, the positions
    where a layer's chosen experts differ between the two)."""
    cfg = run.cell.config
    out = []
    for prompt, served in sample:
        row, start, pad, n_out = reference_feed(run, ref, prompt, served)
        got = ref.served_gaps(cfg, w, row, start, pad, precision=precision,
                              n_out=n_out)
        out.append(tuple(np.asarray(a)[:len(served)] for a in got))
    return out


def held(prefix, limits, gaps, own, results):
    """The two held numbers, each beside its limit: ``gaps`` and ``own``
    are per position, of the same positions."""
    floor = float(limits["logit_gap_mean_floor"])
    harness.check(prefix + "served_logit_gap_widest", float(gaps.max()),
                  limits["logit_gap_widest"], results)
    harness.check(prefix + "served_logit_gap_mean_over_bfloat16",
                  float(gaps.mean()) / max(float(own.mean()), floor),
                  limits["logit_gap_mean_over_bfloat16"], results)
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
    per = request_gaps(run, sample, ref, w, OWN)
    served = np.concatenate([g for g, _, _ in per])
    own = np.concatenate([low for _, low, _ in per])
    harness.log(compared_requests=len(sample), compared_tokens=len(served),
                compared_lengths=[len(p) + len(o) for p, o in sample],
                tokens_equal_reference_best=int(np.sum(served == 0.0)),
                served_logit_gap_mean=float(served.mean()),
                own_form=OWN, own_logit_gap_mean=float(own.mean()),
                own_logit_gap_widest=float(own.max()),
                own_tokens_equal_reference_best=int(np.sum(own == 0.0)))
    ok = held("", limits, served, own, [])
    n = len(sample) if CONTROL_REQUESTS is None else CONTROL_REQUESTS
    own = np.concatenate([low for _, low, _ in per[:n]])
    for p in CONTROLS:
        got = request_gaps(run, sample[:n], ref, w, p)
        low = np.concatenate([low for _, low, _ in got])
        passed = held(f"control.{p}.", limits, low, own, [])
        VERDICTS[p] = passed
        harness.log(control=p, correct=passed, requests=len(got),
                    tokens=len(low), logit_gap_mean=float(low.mean()),
                    own_logit_gap_mean=float(own.mean()),
                    tokens_equal_reference_best=int(np.sum(low == 0.0)),
                    positions_expert_sets_differ=int(
                        sum(d.sum() for _, _, d in got)))
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
