"""Runner ``serve_spec``: a language model whose family brings its own
model spec (``reference/<family>.spec(cfg)``), served through
``mx.DecodeEngine(params, model=spec)``: one chip, closed or open loop.

The load, the warm-up, the window and the sample of finished requests
are ``serve_lm``'s, by import; the accounting of a window's tokens is
``serve_lm.run``'s, repeated in :func:`account` (it is no function
there; ``tests/test_solar_cell.py`` feeds both one load and demands
the same numbers).  What differs: how the engine is built, and how the
run is compared — the family's own ``served_gaps`` on the sample, and
two held numbers that logits cannot give, on the recurrent STATE
itself.  Once the window has closed, the first ``PROBES`` prompts of
the sample are sent once more with ``return_state=True``, through the
same engine and programs, among the requests still in flight.  What
each stream's slot holds at retirement is compared with the last state
of the reference's scan over the same tokens (a wrong, stale or shared
slot, or products below bfloat16, show there), and its float32 words
are looked at: the configuration states the state's type
(``state_dtype``), and a state rounded to bfloat16 token by token
differs from the float32 scan by no more than the program's own
bfloat16 products make it differ (PERF.md section 2) — but every word
of it is a bfloat16.

``CONTROLS`` (empty in a benchmark run; ``benchmark/control_spec.py``
sets it) names precisions of the reference that are put through the
same checks against the same limits: each must read ``correct: false``.
"""

import gc
import time

import numpy as np

from benchmark import harness, stats
from benchmark.runners.serve_lm import (DRAIN_S, KERNEL, LATE_LIMIT_SHARE,
                                        pick_sample, serve_window, warm_up)

# streams whose slot state is read back and compared; the controls are
# computed on the same requests (each is another pass of the reference)
PROBES = 2
CONTROLS = ()
VERDICTS = {}       # control -> did it pass every check (it must not)


def build_engine(run, weights, spec):
    import mxnet_tpu as mx

    wl = run.cell.workload
    ctx = mx.tpu(0) if run.devices[0].platform == "tpu" else mx.cpu()
    eng = wl["engine"]
    return mx.DecodeEngine(
        weights, model=spec, max_len=eng["max_len"],
        kv_block=eng["kv_block"], max_streams=eng["max_streams"],
        decode_buckets=tuple(eng["decode_buckets"]),
        cache_buckets=tuple(eng["cache_buckets"]),
        prefill_buckets=tuple(eng["prefill_buckets"]),
        temperature=0.0, ctx=ctx, dtype=wl["dtype"])


def account(run, load, sent, t0):
    """What became of the requests (``serve_lm.run``'s accounting): a
    request counts where its life, from due to resolved, overlaps the
    window; its tokens count by the share of that life inside it."""
    t_end = t0 + run.seconds
    worse = 1e3 * (run.seconds + DRAIN_S)
    attempted = failed = whole_requests = 0
    lat_ms, tokens, whole_tokens = [], 0.0, 0
    for i in sent:
        due, done = load.due[i], load.done[i]
        if due >= t_end or (done is not None and done <= t0):
            continue                     # the first turn of the loop
        attempted += 1
        ok = (load.out[i] is not None
              and load.out[i].shape == (load.reqs["max_new"][i],))
        failed += not ok
        if due >= t0:
            lat_ms.append(1e3 * (done - due) if ok else worse)
        if ok:
            inside = min(done, t_end) - max(due, t0)
            tokens += len(load.out[i]) * inside / (done - due)
            if done <= t_end:
                whole_requests += 1
                whole_tokens += len(load.out[i])
    return {"attempted": attempted, "failed": failed, "tokens": tokens,
            "lat_ms": lat_ms, "whole_requests": whole_requests,
            "whole_tokens": whole_tokens}


def reference_feed(run, ref, prompt, served):
    """One request as the reference takes it: the padded row of tokens,
    the index that predicts the first served token, the padded served
    tokens and their padded length."""
    import jax.numpy as jnp

    T = int(run.cell.workload["engine"]["max_len"])
    n_out = int(run.cell.traffic["output_tokens"].get("max")
                or run.cell.traffic["output_tokens"]["value"])
    row = np.zeros((T,), np.int32)
    row[:len(prompt)] = prompt
    row[len(prompt):len(prompt) + len(served)] = served
    pad = np.zeros(n_out, np.int32)
    pad[:len(served)] = served
    return jnp.asarray(row), len(prompt) - 1, jnp.asarray(pad), n_out


def sample_gaps(run, sample, ref, w, precision="float32"):
    """The sample teacher-forced through the float32 reference: per
    served position, how far below the reference's best logit the
    served token's lies — or, for a control, the token that
    ``precision`` puts first — and whether any layer's top-k expert set
    differs between float32 and ``precision`` there."""
    cfg = run.cell.config
    gaps, differ = [], []
    for prompt, served in sample:
        row, start, pad, n_out = reference_feed(run, ref, prompt, served)
        g, gl, d = ref.served_gaps(cfg, w, row, start, pad,
                                   precision=precision, n_out=n_out)
        gaps.append(np.asarray(g if precision == "float32" else gl)
                    [:len(served)])
        differ.append(np.asarray(d)[:len(served)])
    return np.concatenate(gaps), np.concatenate(differ)


def probe_states(eng, sample):
    """The first ``PROBES`` prompts of the sample served once more, each
    with the state its slot held at retirement, a head's matrix turned
    from the pool's (d_v, d_k) to the reference's (d_k, d_v):
    ``(prompt, tokens, {pool name: (H, d_k, d_v)})``."""
    futures = [eng.submit(prompt, max_new_tokens=len(served),
                          return_state=True)
               for prompt, served in sample[:PROBES]]
    out = []
    for (prompt, _), f in zip(sample, futures):
        got = f.result(timeout=600)
        out.append((prompt, got["tokens"],
                    {k: np.asarray(v).transpose(0, 2, 1)
                     for k, v in got["state"].items()}))
    return out


def state_gaps(run, probes, ref, w, precision="float32"):
    """(per probe, kda layer and head: the Frobenius norm of (state −
    the float32 reference's last state) over that state's norm; the
    states that were compared).  The state is the program's slot or,
    for a control, the reference's own scan in ``precision``."""
    cfg = run.cell.config
    out, seen = [], []
    for prompt, tokens, state in probes:
        row, _, _, _ = reference_feed(run, ref, prompt, tokens)
        fed = len(prompt) + len(tokens) - 1    # the last is never fed
        want = ref.final_states(cfg, w, row, fed)
        if precision != "float32":
            state = ref.final_states(cfg, w, row, fed, precision)
        for name, s32 in want.items():
            s32 = np.asarray(s32, np.float64)
            seen.append(np.asarray(state[name]))
            got = seen[-1].astype(np.float64)
            out.append(np.sqrt(np.sum((got - s32) ** 2, axis=(1, 2))
                               / np.sum(s32 ** 2, axis=(1, 2))))
    return np.concatenate(out), seen


def bfloat16_share(states):
    """The largest share, over the states given, of a state's float32
    words whose low 16 bits are all zero: that bfloat16 holds exactly.
    2**-16 of a float32 state's words by chance; every word of a state
    that was held in bfloat16."""
    return max(float(np.mean(np.ascontiguousarray(s, np.float32)
                             .view(np.uint32) & 0xFFFF == 0))
               for s in states)


def held(prefix, limits, logit, state, share, results):
    """The four held numbers, each beside its limit."""
    harness.check(prefix + "served_logit_gap_widest", float(logit.max()),
                  limits["logit_gap_widest"], results)
    harness.check(prefix + "served_logit_gap_mean", float(logit.mean()),
                  limits["logit_gap_mean"], results)
    harness.check(prefix + "kda_state_gap_worst_head", float(state.max()),
                  limits["state_gap_worst_head"], results)
    harness.check(prefix + "kda_state_bfloat16_share", share,
                  limits["state_bfloat16_share"], results)
    return all(results)


def serve_check(run, sample, probes, ref):
    """Each number compared, beside its limit; then every control of
    ``CONTROLS`` through the same checks (must read false: logged, and
    no part of this run's ``correct``)."""
    limits = run.cell.workload["limits"]
    if not sample or not probes:
        harness.log(error="no request finished: nothing to compare")
        return False
    wl = run.cell.workload
    w = ref.to_float32(ref.draw(run.cell.config, run.seed,
                                embed_dtype=wl["dtype"], dtype=wl["dtype"]))
    flat, _ = sample_gaps(run, sample, ref, w)
    state, seen = state_gaps(run, probes, ref, w)
    harness.log(compared_requests=len(sample), compared_tokens=len(flat),
                tokens_equal_reference_best=int(np.sum(flat == 0.0)),
                probed_streams=len(probes), compared_head_states=len(state),
                kda_state_gap_mean_head=float(state.mean()))
    ok = held("", limits, flat, state, bfloat16_share(seen), [])
    for p in CONTROLS:
        low, differ = sample_gaps(run, sample[:PROBES], ref, w, p)
        state, seen = state_gaps(run, probes, ref, w, p)
        passed = held(f"control.{p}.", limits, low, state,
                      bfloat16_share(seen), [])
        VERDICTS[p] = passed
        harness.log(control=p, correct=passed, requests=PROBES,
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
                engine={k: st[k] for k in (
                    "requests", "tokens", "prefills", "prefill_tokens",
                    "steps", "stream_steps", "preempted", "d2h_syncs",
                    "d2h_syncs_saved", "context_tokens", "ttft_p50_ms",
                    "p50_ms", "p99_ms", "active_streams", "pending",
                    "state_slots", "state_slots_live", "state_pool_bytes",
                    "moe_pairs_here", "moe_pairs_elsewhere",
                    "moe_experts_hit", "moe_load_max")})
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
    t_ref = time.perf_counter()
    sample = pick_sample(run, load, sent)
    try:
        probes = probe_states(eng, sample)
    finally:
        eng.close()
    harness.log(probes_s=time.perf_counter() - t_ref)
    del eng, load.eng
    gc.collect()
    t_ref = time.perf_counter()
    correct = serve_check(run, sample, probes, ref) and correct
    harness.log(reference_s=time.perf_counter() - t_ref)
    metrics = {
        "serve_out_tokens_per_s": a["tokens"] / run.seconds,
        "serve_request_p95_ms": stats.percentile(a["lat_ms"], 95)}
    return {"correct": correct and kernel_ok,
            "attempted": a["attempted"], "failed": a["failed"],
            "metrics": metrics, "memory_peak_bytes": peak}
