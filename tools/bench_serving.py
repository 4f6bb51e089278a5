#!/usr/bin/env python
"""Serving benchmark: dynamic-batching InferenceEngine vs naive
per-request Predictor.forward, under a closed-loop multi-threaded
client sweep.

Prints ONE JSON line (the `bench.py` convention, so the serving
trajectory lands in future BENCH_*.json rounds):

  {"metric": "serving_throughput", "value": N, "unit": "img/s",
   "throughput_img_s": N, "p50_ms": N, "p99_ms": N,
   "batch_fill_ratio": N, "naive_img_s": N, "vs_naive": N,
   "model": "...", "clients": N, "sweep": [...], ...}

Methodology (PERF.md appendix "Serving benchmark"):
- Closed loop: each of C client threads submits ONE single-sample
  request, blocks on its future, then submits the next — so offered
  load scales with C and queueing is self-limiting, never open-loop
  overload.  Latency is measured client-side around submit→result
  (true end-to-end wall, includes queueing + padding + H2D + compute
  + D2H).
- The engine is prewarmed (all buckets compiled) before timing; the
  naive baseline's batch-1 program is warmed the same way.  Compile
  time is a one-off cost both sides pay once, not a serving-rate term.
- The naive baseline is sequential per-request `Predictor.forward` at
  batch 1 — what the predict API gives a service that dispatches each
  request as it arrives (Predictor.forward is not thread-safe, and N
  threads over one jitted program serialize on the device anyway).
- batch_fill_ratio = real samples / padded bucket slots, lifetime mean
  over the engine — how much of the MXU the padding wastes.

Env knobs: SERVE_MODELS (default "resnet50,transformer"),
SERVE_CLIENTS (default "1,2,4,8,16,32,64"; CPU "1,4,8,16"),
SERVE_REQUESTS (requests per client per point; default 64, CPU 12),
SERVE_BUCKETS (default "1,8,32,128"; CPU "1,8,32"),
SERVE_TIMEOUT_MS (default 2), SERVE_NAIVE_REQUESTS (default 64, CPU 24).
Model-parallel mode (--tp N [--pp M]): TP_CLIENTS, TP_REQUESTS,
TP_PROMPT, TP_NEW, TP_DEVICE_POOL_BYTES (per-device pool budget the
tp=1 pool must exceed; see the "Model-parallel serving" PERF.md
appendix).
On the TPU the full-size models run; with no TPU the script fails.
``--cpu`` asks for the CPU rehearsal instead: shrunken models (ResNet-50
CIFAR-style at 32x32, a 2-layer transformer) on mx.cpu(), minutes long,
its numbers no device metric.
"""

import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

from mxnet_tpu.config import place_compile_cache

place_compile_cache()

import numpy as np


def log(msg):
    print(f"[bench_serving] {msg}", file=sys.stderr, flush=True)


def run_mode():
    """(backend, cpu).  This is a measurement script: it needs the chip
    and fails without one.  The shrunken CPU sweep runs only when asked
    for with ``--cpu`` — a rehearsal of the control flow whose numbers
    say nothing about the device — never because no chip was found."""
    backend = jax.default_backend()
    cpu = "--cpu" in sys.argv
    if not cpu and backend != "tpu":
        raise SystemExit(
            f"bench_serving: jax's default backend is {backend!r}, not "
            f"'tpu' — a serving measurement needs the chip (pass --cpu "
            f"for the shrunken CPU rehearsal)")
    return backend, cpu


def bench_ctx():
    """Where every module and engine of this script lives: the chip,
    or the host under ``--cpu`` — said, not detected."""
    import mxnet_tpu as mx

    return mx.cpu() if "--cpu" in sys.argv else mx.tpu()


def _csv_ints(s):
    return [int(x) for x in s.split(",") if x.strip()]


def build_predictor(model_name, cpu):
    """Random-init the model via Module, hand the params to a batch-1
    Predictor (the serving engine re-jits per bucket from it)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    if model_name == "resnet50":
        image = (3, 32, 32) if cpu else (3, 224, 224)
        sym = models.resnet(num_classes=10 if cpu else 1000,
                            num_layers=50, image_shape=image)
        data_shape = image
        label_shape = ()
        mk_sample = lambda rng: {  # noqa: E731
            "data": rng.rand(1, *image).astype(np.float32),
            "softmax_label": np.zeros((1,), np.float32)}
    elif model_name == "transformer":
        # CPU fallback is sized so per-sample work is small relative to
        # per-dispatch overhead — the regime where micro-batching wins
        # even without an MXU to fill (see PERF.md appendix)
        vocab, T = (512, 16) if cpu else (8000, 128)
        sym = models.transformer_lm(
            vocab, T, num_layers=2 if cpu else 4,
            num_heads=2 if cpu else 4, d_model=32 if cpu else 256)
        data_shape = (T,)
        label_shape = (T,)
        mk_sample = lambda rng: {  # noqa: E731
            "data": rng.randint(1, vocab, size=(1,) + data_shape)
            .astype(np.float32),
            "softmax_label": np.zeros((1,) + label_shape, np.float32)}
    else:
        raise SystemExit(f"unknown model {model_name!r} "
                         "(SERVE_MODELS wants resnet50|transformer)")

    ctx = bench_ctx()
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[("data", (2,) + data_shape)],
             label_shapes=[("softmax_label", (2,) + label_shape)],
             for_training=False)
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0))
    arg, aux = mod.get_params()
    pred = mx.Predictor(
        sym, {**arg, **aux},
        {"data": (1,) + data_shape, "softmax_label": (1,) + label_shape},
        ctx=ctx)
    return pred, mk_sample


def bench_naive(pred, mk_sample, n_requests):
    """Sequential per-request Predictor.forward at batch 1."""
    rng = np.random.RandomState(7)
    sample = mk_sample(rng)
    for _ in range(2):  # warm the batch-1 program
        pred.forward(**sample)
        pred.get_output(0)
    lat = []
    t0 = time.perf_counter()
    for _ in range(n_requests):
        s = mk_sample(rng)
        t1 = time.perf_counter()
        pred.forward(**s)
        pred.get_output(0)  # blocks to host, like a server replying
        lat.append((time.perf_counter() - t1) * 1e3)
    wall = time.perf_counter() - t0
    return {"img_s": n_requests / wall,
            "p50_ms": float(np.percentile(lat, 50)),
            "p90_ms": float(np.percentile(lat, 90)),
            "p99_ms": float(np.percentile(lat, 99))}


def bench_point(eng, mk_sample, clients, per_client):
    """Closed loop: C threads × per_client single-sample requests."""
    lat_lock = threading.Lock()
    lats = []
    errs = []
    start = threading.Barrier(clients + 1)

    def client(cid):
        rng = np.random.RandomState(1000 + cid)
        try:
            start.wait(timeout=60)
            for _ in range(per_client):
                s = mk_sample(rng)
                t1 = time.perf_counter()
                eng.infer(s)
                dt = (time.perf_counter() - t1) * 1e3
                with lat_lock:
                    lats.append(dt)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    st0 = eng.stats()
    start.wait(timeout=60)
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errs:
        raise errs[0]
    st1 = eng.stats()
    total = clients * per_client
    batches = st1["batches"] - st0["batches"]
    # p50/p90/p99 here deliberately match MetricsRegistry.summary()'s
    # histogram schema (and eng.stats()), so the bench, the JSONL
    # reporter and the Prometheus exporter all speak one vocabulary
    return {
        "clients": clients,
        "throughput_img_s": round(total / wall, 2),
        "p50_ms": round(float(np.percentile(lats, 50)), 3),
        "p90_ms": round(float(np.percentile(lats, 90)), 3),
        "p99_ms": round(float(np.percentile(lats, 99)), 3),
        "avg_batch": round(total / max(batches, 1), 2),
        "batches": batches,
    }


# ---------------------------------------------------------------------------
# --decode: autoregressive serving under a closed-loop chat workload.
#
# Methodology (PERF.md appendix "Decode serving benchmark"):
# - Closed loop: each of C client threads submits ONE generation
#   (prompt length ~ U[pmin, pmax], output length ~ U[nmin, nmax]),
#   blocks on its future, then submits the next — offered concurrency
#   is exactly C streams.
# - tokens_s_chip counts GENERATED tokens only (prefill tokens are
#   reported separately); divided by local device count.
# - p50/p90/p99 time-per-token come from the engine's per-step
#   histogram (each active stream's step wall is one token time) —
#   the serving-tier TPOT numbers, same percentile schema as every
#   other bench in this repo.
# - The request-level baseline is what the pre-decode serving tier
#   could do for an LM: one request at a time, each new token re-runs
#   the FULL prefill at the bucketed sequence length (O(T^2) work per
#   sequence, idle device between requests).  Its forwards are warmed
#   per bucket before timing, same as the engine's executables.
# ---------------------------------------------------------------------------


def build_decode_config(cpu):
    # CPU sizes are chosen so per-token work dominates the ~1 ms
    # dispatch floor — at toy sizes a FULL forward costs one dispatch
    # and the O(T^2) re-prefill penalty the baseline pays is invisible
    if cpu:
        return dict(vocab_size=512, num_layers=2, num_heads=4,
                    d_model=128, max_len=128, kv_block=16)
    return dict(vocab_size=8000, num_layers=4, num_heads=4,
                d_model=256, max_len=512, kv_block=16)


def build_lm_params(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import models

    sym = models.transformer_lm(
        cfg["vocab_size"], cfg["max_len"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        d_model=cfg["d_model"], block_size=cfg["kv_block"])
    mod = mx.mod.Module(sym, context=bench_ctx())
    T = cfg["max_len"]
    mod.bind(data_shapes=[("data", (2, T))],
             label_shapes=[("softmax_label", (2, T))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.0))
    arg, aux = mod.get_params()
    return {**arg, **aux}


def bench_decode_baseline(params, cfg, workload):
    """Request-level baseline: sequential generations, each token via
    a full re-prefill at the bucketed length."""
    import jax as _jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.kv_cache import bucket_ladder
    from mxnet_tpu.models.transformer import transformer_lm_prefill

    ps = transformer_lm_prefill(
        cfg["vocab_size"], num_layers=cfg["num_layers"],
        num_heads=cfg["num_heads"], d_model=cfg["d_model"],
        kv_block=cfg["kv_block"], paged=False)
    gfn = build_graph_fn(ps)
    base = {n: jnp.asarray(params[n].asnumpy())
            for n in ps.list_arguments() if n in params}
    kvb = cfg["kv_block"]
    buckets = [b * kvb for b in
               bucket_ladder(-(-cfg["max_len"] // kvb))]

    @_jax.jit
    def fwd(tokens, positions, lengths):
        a = dict(base)
        a.update(data=tokens, positions=positions, lengths=lengths)
        outs, _ = gfn(a, {}, _jax.random.PRNGKey(0), False)
        return jnp.argmax(
            outs[0][jnp.arange(1), lengths - 1], axis=-1)

    def step(seq):
        n = len(seq)
        tb = next(b for b in buckets if b >= n)
        tokens = np.zeros((1, tb), np.int32)
        tokens[0, :n] = seq
        return int(np.asarray(fwd(
            jnp.asarray(tokens),
            jnp.asarray(np.arange(tb, dtype=np.int32)[None]),
            jnp.asarray(np.asarray([n], np.int32))))[0])

    for b in buckets:  # warm every bucket's program
        step([1] * b)
    lat = []
    tokens = 0
    t0 = time.perf_counter()
    for prompt, n_new in workload:
        seq = list(prompt)
        for _ in range(n_new):
            t1 = time.perf_counter()
            seq.append(step(seq))
            lat.append((time.perf_counter() - t1) * 1e3)
        tokens += n_new
    wall = time.perf_counter() - t0
    return {"tokens_s": tokens / wall,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99))}


def bench_decode_point(eng, mk_request, clients, per_client):
    """Closed loop: C chat clients, each submits one generation at a
    time."""
    # per-point percentiles: lifetime histograms would blend every
    # previous sweep point's samples into this one's p50/p99
    eng.reset_stats()
    errs, done = [], []
    lock = threading.Lock()
    start = threading.Barrier(clients + 1)

    def client(cid):
        rng = np.random.RandomState(5000 + cid)
        try:
            start.wait(timeout=120)
            for _ in range(per_client):
                prompt, n_new = mk_request(rng)
                t1 = time.perf_counter()
                out = eng.generate(prompt, n_new)
                dt = time.perf_counter() - t1
                with lock:
                    done.append((len(out), dt))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    st0 = eng.stats()
    util, streams = [], []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            st = eng.stats()
            util.append(st["cache_util"])
            streams.append(st["active_streams"])
            time.sleep(0.05)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    start.wait(timeout=120)
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stop.set()
    poller.join(timeout=2)
    if errs:
        raise errs[0]
    st1 = eng.stats()
    tokens = sum(n for n, _ in done)
    out = {
        "clients": clients,
        "tokens_s": round(tokens / wall, 2),
        "p50_ms": st1["p50_ms"],
        "p90_ms": st1["p90_ms"],
        "p99_ms": st1["p99_ms"],
        "ttft_p50_ms": st1["ttft_p50_ms"],
        "generations": len(done),
        "steps": st1["steps"] - st0["steps"],
        "preempted": st1["preempted"] - st0["preempted"],
        # concurrency the pool actually sustained: the sharing
        # multiplier the prefix cache exists to raise
        "admitted_streams": int(np.max(streams)) if streams else 0,
        "cache_util_mean": round(float(np.mean(util)), 4) if util
        else 0.0,
        "cache_util_max": round(float(np.max(util)), 4) if util
        else 0.0,
        # speculative decoding / chunked prefill / D2H-overlap
        # accounting (all zero when those features are off)
        "accepted_token_rate": st1["accepted_token_rate"],
        "tokens_per_step": st1["tokens_per_step"],
        "spec_steps": st1["spec_steps"] - st0["spec_steps"],
        "prefill_chunks": st1["prefill_chunks"] - st0["prefill_chunks"],
        "d2h_syncs": st1["d2h_syncs"] - st0["d2h_syncs"],
        "d2h_syncs_saved": (st1["d2h_syncs_saved"]
                            - st0["d2h_syncs_saved"]),
    }
    if st1.get("prefix_cache"):
        out["prefix_hit_rate"] = st1["prefix_hit_rate"]
        out["prefix_hit_tokens"] = st1["prefix_hit_tokens"]
        out["cow_copies"] = st1["cow_copies"]
        out["evictions"] = st1["evictions"]
        out["shared_blocks_max"] = st1["shared_blocks"]
        out["ttft_hit_ms"] = st1["ttft_hit_p50_ms"]
        out["ttft_miss_ms"] = st1["ttft_miss_p50_ms"]
    return out


def main_decode():
    import mxnet_tpu as mx

    backend, cpu = run_mode()
    cfg = build_decode_config(cpu)
    clients_sweep = _csv_ints(os.environ.get(
        "DECODE_CLIENTS", "1,4,8" if cpu else "1,8,32,64"))
    per_client = int(os.environ.get("DECODE_REQUESTS",
                                    "4" if cpu else "16"))
    pmin, pmax = _csv_ints(os.environ.get("DECODE_PROMPT",
                                          "8,48" if cpu else "16,128"))
    nmin, nmax = _csv_ints(os.environ.get("DECODE_NEW",
                                          "16,48" if cpu else "32,128"))
    base_reqs = int(os.environ.get("DECODE_BASELINE_REQUESTS",
                                   "6" if cpu else "16"))
    cache_blocks = os.environ.get("DECODE_CACHE_BLOCKS")
    log(f"decode backend={backend} cfg={cfg} clients={clients_sweep} "
        f"prompt=U[{pmin},{pmax}] new=U[{nmin},{nmax}]")

    t0 = time.perf_counter()
    params = build_lm_params(cfg)
    log(f"model built in {time.perf_counter() - t0:.1f}s")

    def mk_request(rng):
        p = rng.randint(pmin, pmax + 1)
        n = rng.randint(nmin, nmax + 1)
        return rng.randint(1, cfg["vocab_size"],
                           size=p).astype(np.int32), n

    rng = np.random.RandomState(77)
    workload = [mk_request(rng) for _ in range(base_reqs)]
    naive = bench_decode_baseline(params, cfg, workload)
    log(f"request-level baseline (full re-prefill per token): "
        f"{naive['tokens_s']:.1f} tok/s, p50 {naive['p50_ms']:.1f} ms")

    max_streams = max(clients_sweep)
    eng = mx.DecodeEngine(
        params, ctx=bench_ctx(), vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        d_model=cfg["d_model"], max_len=cfg["max_len"],
        kv_block=cfg["kv_block"], max_streams=max_streams,
        cache_blocks=int(cache_blocks) if cache_blocks else None,
        temperature=0.0, prewarm=True)
    n_dev = max(1, jax.local_device_count())
    try:
        sweep = []
        for c in clients_sweep:
            pt = bench_decode_point(eng, mk_request, c, per_client)
            pt["tokens_s_chip"] = round(pt["tokens_s"] / n_dev, 2)
            pt["vs_baseline"] = round(
                pt["tokens_s"] / naive["tokens_s"], 3)
            sweep.append(pt)
            log(f"{c:3d} clients -> {pt['tokens_s']:8.1f} tok/s "
                f"(x{pt['vs_baseline']:.2f} baseline), "
                f"p50 {pt['p50_ms']:.1f} ms, p99 {pt['p99_ms']:.1f} "
                f"ms/token, cache {pt['cache_util_mean']:.0%}, "
                f"preempted {pt['preempted']}")
        st = eng.stats()
        loaded = [p for p in sweep if p["clients"] >= 8] or sweep
        best = max(loaded, key=lambda p: p["tokens_s"])
        print(json.dumps({
            "metric": "serving_decode_throughput",
            "value": best["tokens_s_chip"],
            "unit": "tokens/s/chip",
            "backend": backend,
            "model": "transformer_lm",
            "config": cfg,
            "clients": best["clients"],
            "tokens_s_chip": best["tokens_s_chip"],
            "tokens_s": best["tokens_s"],
            "p50_ms": best["p50_ms"],
            "p90_ms": best["p90_ms"],
            "p99_ms": best["p99_ms"],
            "ttft_p50_ms": best["ttft_p50_ms"],
            "cache_util": best["cache_util_mean"],
            "accepted_token_rate": best["accepted_token_rate"],
            "tokens_per_step": best["tokens_per_step"],
            "prefill_chunks": best["prefill_chunks"],
            "d2h_syncs_saved": best["d2h_syncs_saved"],
            "preempted": sum(p["preempted"] for p in sweep),
            "baseline_tokens_s": round(naive["tokens_s"], 2),
            "vs_baseline": best["vs_baseline"],
            "kv_block": st["kv_block"],
            "decode_buckets": st["decode_buckets"],
            "compiles": st["compiles"],
            # per-phase percentiles from the per-request spans: a p99
            # regression names queue_wait/prefill/decode, not just one
            # opaque number (lifetime over the whole sweep)
            "latency_breakdown": st["latency_breakdown"],
            "sweep": sweep,
        }))
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# --decode --lora: multi-tenant paged-LoRA serving.
#
# Methodology (PERF.md appendix "Multi-tenant serving"):
# - The single-tenant reference is a pool-LESS engine (no LoRA
#   epilogue compiled in) under the same closed-loop chat workload —
#   "what you give up for tenancy" includes the gather epilogue, not
#   just adapter traffic.
# - The sweep then runs ONE pool-backed engine at 0/1/4/8 distinct
#   adapters mixed into the batch (80% adapter traffic, 20% plain;
#   70/30 interactive/batch SLO mix; tenant == adapter owner).  The
#   0-adapter point isolates the epilogue overhead on plain traffic.
# - Adapter slots are fewer than the widest mix (default 4 slots vs
#   8 adapters) so the LRU pool actually parks/evicts and the hit
#   rate means something; slots >= clients keeps acquire safe (a
#   closed loop holds at most `clients` live adapters).
# - Quota shed is demonstrated on a separate tiny engine with a hard
#   token budget (refill 0): over-budget submits must shed TYPED
#   (QuotaExceededError, reason "tenant_quota"), never mid-stream.
# ---------------------------------------------------------------------------


def bench_lora_point(eng, mk_request, clients, per_client):
    """Closed loop like bench_decode_point, but each request carries
    (tenant, adapter, slo_class) and quota sheds are caught per
    client rather than failing the point."""
    from mxnet_tpu.adapters import QuotaExceededError

    eng.reset_stats()
    errs, done, sheds = [], [], []
    lock = threading.Lock()
    start = threading.Barrier(clients + 1)

    def client(cid):
        rng = np.random.RandomState(7000 + cid)
        try:
            start.wait(timeout=120)
            for _ in range(per_client):
                prompt, n_new, kw = mk_request(rng)
                try:
                    out = eng.generate(prompt, n_new, **kw)
                except QuotaExceededError:
                    with lock:
                        sheds.append(kw.get("slo_class", "interactive"))
                    continue
                with lock:
                    done.append(len(out))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    st0 = eng.stats()
    ad0 = st0.get("adapters", {})
    start.wait(timeout=120)
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errs:
        raise errs[0]
    st1 = eng.stats()
    ad1 = st1.get("adapters", {})
    hits = ad1.get("hits", 0) - ad0.get("hits", 0)
    misses = ad1.get("misses", 0) - ad0.get("misses", 0)
    out = {
        "clients": clients,
        "tokens_s": round(sum(done) / wall, 2),
        "p50_ms": st1["p50_ms"],
        "p99_ms": st1["p99_ms"],
        "ttft_p50_ms": st1["ttft_p50_ms"],
        "generations": len(done),
        "shed": st1["shed"] - st0["shed"],
        "shed_tenant_quota": (st1["shed_tenant_quota"]
                              - st0["shed_tenant_quota"]),
        "shed_by_class": {c: sheds.count(c) for c in sorted(set(sheds))},
        "adapter_acquires": hits + misses,
        "adapter_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else None,
        "adapter_evictions": (ad1.get("evictions", 0)
                              - ad0.get("evictions", 0)),
        "tenants": {t: dict(d)
                    for t, d in sorted(st1.get("tenants", {}).items())},
    }
    return out


def main_decode_lora():
    import mxnet_tpu as mx
    from mxnet_tpu.adapters import AdapterPool, TenantQuota

    backend, cpu = run_mode()
    cfg = build_decode_config(cpu)
    adapters_sweep = _csv_ints(os.environ.get("LORA_ADAPTERS", "1,4,8"))
    clients = int(os.environ.get("LORA_CLIENTS", "4" if cpu else "16"))
    per_client = int(os.environ.get("LORA_REQUESTS",
                                    "4" if cpu else "12"))
    slots = int(os.environ.get("LORA_SLOTS", str(max(4, clients))))
    pmin, pmax = _csv_ints(os.environ.get("LORA_PROMPT",
                                          "8,32" if cpu else "16,96"))
    nmin, nmax = _csv_ints(os.environ.get("LORA_NEW",
                                          "16,32" if cpu else "32,96"))
    log(f"lora backend={backend} cfg={cfg} adapters={adapters_sweep} "
        f"clients={clients} slots={slots} "
        f"prompt=U[{pmin},{pmax}] new=U[{nmin},{nmax}]")

    t0 = time.perf_counter()
    params = build_lm_params(cfg)
    log(f"model built in {time.perf_counter() - t0:.1f}s")
    kw = dict(ctx=bench_ctx(), vocab_size=cfg["vocab_size"],
              num_layers=cfg["num_layers"],
              num_heads=cfg["num_heads"], d_model=cfg["d_model"],
              max_len=cfg["max_len"], kv_block=cfg["kv_block"],
              max_streams=clients, temperature=0.0, prewarm=True)

    def mk_plain(rng):
        p = rng.randint(pmin, pmax + 1)
        n = rng.randint(nmin, nmax + 1)
        return (rng.randint(1, cfg["vocab_size"], size=p)
                .astype(np.int32), n, {})

    # single-tenant reference: NO adapter pool -> no LoRA epilogue in
    # the compiled decode step at all
    eng = mx.DecodeEngine(params, **kw)
    try:
        plain = bench_lora_point(eng, mk_plain, clients, per_client)
    finally:
        eng.close()
    log(f"single-tenant reference: {plain['tokens_s']:.1f} tok/s, "
        f"p50 {plain['p50_ms']:.1f} ms")

    # ranks 5 and 8 both pad into the r8 bucket, so every adapter
    # contends for the SAME `slots` rows — the widest sweep point
    # (default 8 adapters over 4 slots) forces real LRU paging
    rank_buckets = (4, 8)
    pool = AdapterPool(num_layers=cfg["num_layers"],
                       d_model=cfg["d_model"], slots=slots,
                       rank_buckets=rank_buckets)
    n_max = max(adapters_sweep)
    wrng = np.random.RandomState(42)
    for j in range(n_max):
        r = 8 if j % 2 else 5
        pool.publish(
            f"ad{j}",
            (wrng.randn(cfg["num_layers"], cfg["d_model"], r)
             * 0.05).astype(np.float32),
            (wrng.randn(cfg["num_layers"], r, 3 * cfg["d_model"])
             * 0.05).astype(np.float32))

    def mk_mixed(n_adapters):
        def mk(rng):
            prompt, n, _ = mk_plain(rng)
            kw2 = {"slo_class": "interactive"
                   if rng.rand() < 0.7 else "batch"}
            if n_adapters and rng.rand() < 0.8:
                j = rng.randint(n_adapters)
                kw2.update(adapter=f"ad{j}", tenant=f"tn{j}")
            else:
                kw2.update(tenant="tn-plain")
            return prompt, n, kw2
        return mk

    eng = mx.DecodeEngine(params, adapters=pool, **kw)
    try:
        sweep = []
        for n_ad in [0] + adapters_sweep:
            pt = bench_lora_point(eng, mk_mixed(n_ad), clients,
                                  per_client)
            pt["adapters"] = n_ad
            pt["vs_single_tenant"] = round(
                pt["tokens_s"] / plain["tokens_s"], 3)
            sweep.append(pt)
            hr = pt["adapter_hit_rate"]
            log(f"{n_ad:2d} adapters -> {pt['tokens_s']:8.1f} tok/s "
                f"(x{pt['vs_single_tenant']:.2f} single-tenant), "
                f"p50 {pt['p50_ms']:.1f} ms, hit rate "
                f"{'-' if hr is None else f'{hr:.0%}'}, "
                f"evictions {pt['adapter_evictions']}, "
                f"shed {pt['shed']}")
        pool_stats = eng.stats().get("adapters", {})
    finally:
        eng.close()

    # typed quota shed on a hard budget (refill 0): first requests
    # admit, the over-budget tail sheds before any decode step
    quota_cap = int(os.environ.get("LORA_QUOTA_TOKENS", "64"))
    qeng = mx.DecodeEngine(
        params, adapters=pool,
        tenant_quota=TenantQuota(quota_cap, refill_rate=0.0),
        **{**kw, "prewarm": False, "max_streams": 2})

    def mk_quota(rng):
        prompt, _, _ = mk_plain(rng)
        return prompt[:8], 16, {"tenant": "tn0", "adapter": "ad0",
                                "slo_class": "batch"}

    try:
        qpt = bench_lora_point(qeng, mk_quota, 1, 8)
        qstats = qeng.stats()
    finally:
        qeng.close()
    log(f"quota demo (cap {quota_cap} tokens): "
        f"{qpt['generations']} admitted, "
        f"{qpt['shed_tenant_quota']} shed typed")

    mixed = [p for p in sweep if p["adapters"] > 0]
    widest = max(mixed, key=lambda p: p["adapters"])
    print(json.dumps({
        "metric": "serving_lora_multitenancy",
        "value": widest["tokens_s"],
        "unit": "tokens/s",
        "backend": backend,
        "model": "transformer_lm",
        "config": cfg,
        "clients": clients,
        "adapter_slots": slots,
        "rank_buckets": list(rank_buckets),
        "tokens_s": widest["tokens_s"],
        "adapters_mixed": widest["adapters"],
        "vs_single_tenant": widest["vs_single_tenant"],
        "lora_epilogue_overhead": round(
            sweep[0]["tokens_s"] / plain["tokens_s"], 3),
        "adapter_hit_rate": widest["adapter_hit_rate"],
        "adapter_evictions": sum(p["adapter_evictions"] for p in sweep),
        "shed": sum(p["shed"] for p in sweep),
        "single_tenant_tokens_s": plain["tokens_s"],
        "pool": pool_stats,
        "quota_demo": {
            "capacity_tokens": quota_cap,
            "admitted": qpt["generations"],
            "shed_tenant_quota": qpt["shed_tenant_quota"],
            "shed_by_class": qpt["shed_by_class"],
            "tenants": qstats.get("tenants", {}),
        },
        "sweep": sweep,
    }))


# ---------------------------------------------------------------------------
# --decode --shared-prefix: the prefix-cache acceptance workload.
#
# Methodology (PERF.md appendix "Prefix caching"):
# - 80%-shared chat workload: 80% of requests are <long shared system
#   prompt> + <short unique suffix> (the production shape prefix
#   caching targets); 20% are unrelated short prompts.
# - The SAME constrained page pool serves two engines back to back:
#   exclusive-owner (MXNET_SERVING_PREFIX_CACHE=0 semantics) and
#   prefix-shared.  The pool is sized to ~3 exclusive streams, so the
#   admitted-concurrent-streams multiplier is the headline number —
#   sharing is what lets one pool hold many streams.
# - admitted_streams = max concurrent active streams observed (50 ms
#   polls); ttft_hit_ms / ttft_miss_ms come from the engine's split
#   TTFT histograms (a hit pays only suffix prefill).
# ---------------------------------------------------------------------------


def main_decode_shared():
    import mxnet_tpu as mx
    from mxnet_tpu.kv_cache import blocks_for_tokens

    backend, cpu = run_mode()
    cfg = build_decode_config(cpu)
    kvb = cfg["kv_block"]
    clients = int(os.environ.get("DECODE_CLIENTS",
                                 "12" if cpu else "48"))
    per_client = int(os.environ.get("DECODE_REQUESTS",
                                    "3" if cpu else "8"))
    shared_len = int(os.environ.get("DECODE_SHARED_LEN",
                                    "96" if cpu else "384"))
    smin, smax = _csv_ints(os.environ.get("DECODE_SUFFIX", "1,8"))
    nmin, nmax = _csv_ints(os.environ.get("DECODE_NEW",
                                          "4,8" if cpu else "16,32"))
    shared_frac = float(os.environ.get("DECODE_SHARED_FRAC", "0.8"))
    # pool: ~3 exclusive-owner streams' worth (forces the multiplier
    # to come from sharing, not from slack)
    per_stream = blocks_for_tokens(shared_len + smax + nmax, kvb)
    cache_blocks = int(os.environ.get(
        "DECODE_CACHE_BLOCKS", str(1 + 3 * per_stream)))
    log(f"shared-prefix decode backend={backend} cfg={cfg} "
        f"clients={clients} shared_len={shared_len} "
        f"suffix=U[{smin},{smax}] new=U[{nmin},{nmax}] "
        f"pool={cache_blocks} blocks ({per_stream}/exclusive stream)")

    params = build_lm_params(cfg)
    rng0 = np.random.RandomState(99)
    shared = rng0.randint(1, cfg["vocab_size"],
                          size=shared_len).astype(np.int32)

    def mk_request(rng):
        n = rng.randint(nmin, nmax + 1)
        if rng.rand() < shared_frac:
            sfx = rng.randint(1, cfg["vocab_size"],
                              size=rng.randint(smin, smax + 1))
            return np.concatenate([shared, sfx]).astype(np.int32), n
        return rng.randint(1, cfg["vocab_size"], size=rng.randint(
            24, 33)).astype(np.int32), n

    def ttft_probe(eng, rng, reps=6):
        """Idle-engine TTFT, hit vs miss, apples to apples: same
        prompt length, one at a time — the pure prefill-cost split
        (the loaded split in the sweep point mixes in queue wait,
        which load distributes unevenly between early misses and
        late hits)."""
        out = {}
        for kind in ("miss", "hit"):
            vals = []
            for _ in range(reps):
                if kind == "hit":
                    sfx = rng.randint(1, cfg["vocab_size"], size=smax)
                    p = np.concatenate([shared, sfx]).astype(np.int32)
                else:
                    p = rng.randint(1, cfg["vocab_size"],
                                    size=shared_len + smax) \
                        .astype(np.int32)
                eng.reset_stats()
                t1 = time.perf_counter()
                eng.generate(p, 1)
                vals.append((time.perf_counter() - t1) * 1e3)
            out[kind] = round(float(np.median(vals)), 3)
        return out

    def run(prefix_on):
        eng = mx.DecodeEngine(
            params, ctx=bench_ctx(), vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
            d_model=cfg["d_model"], max_len=cfg["max_len"],
            kv_block=kvb, max_streams=clients,
            cache_blocks=cache_blocks, temperature=0.0,
            prefix_cache=prefix_on, prewarm=True)
        try:
            pt = bench_decode_point(eng, mk_request, clients,
                                    per_client)
            if prefix_on:
                pt["ttft_idle"] = ttft_probe(
                    eng, np.random.RandomState(123))
            return pt
        finally:
            eng.close()

    t0 = time.perf_counter()
    base = run(0)
    log(f"exclusive-owner: {base['tokens_s']:.1f} tok/s, "
        f"admitted {base['admitted_streams']} streams, "
        f"ttft p50 {base['ttft_p50_ms']:.1f} ms "
        f"({time.perf_counter() - t0:.0f}s)")
    t0 = time.perf_counter()
    pt = run(1)
    log(f"prefix-shared:   {pt['tokens_s']:.1f} tok/s, "
        f"admitted {pt['admitted_streams']} streams, hit rate "
        f"{pt['prefix_hit_rate']:.0%}, idle ttft hit "
        f"{pt['ttft_idle']['hit']} / miss {pt['ttft_idle']['miss']} "
        f"ms ({time.perf_counter() - t0:.0f}s)")
    n_dev = max(1, jax.local_device_count())
    streams_x = (pt["admitted_streams"]
                 / max(base["admitted_streams"], 1))
    print(json.dumps({
        "metric": "serving_prefix_cache",
        "value": round(streams_x, 2),
        "unit": "x admitted streams vs exclusive-owner",
        "backend": backend,
        "model": "transformer_lm",
        "config": cfg,
        "clients": clients,
        "cache_blocks": cache_blocks,
        "shared_prefix_tokens": shared_len,
        "shared_fraction": shared_frac,
        "admitted_streams": pt["admitted_streams"],
        "admitted_streams_baseline": base["admitted_streams"],
        "streams_vs_baseline": round(streams_x, 2),
        "tokens_s": pt["tokens_s"],
        "tokens_s_chip": round(pt["tokens_s"] / n_dev, 2),
        "tokens_s_baseline": base["tokens_s"],
        "vs_baseline": round(pt["tokens_s"]
                             / max(base["tokens_s"], 1e-9), 3),
        "prefix_hit_rate": pt["prefix_hit_rate"],
        "prefix_hit_tokens": pt["prefix_hit_tokens"],
        "cow_copies": pt["cow_copies"],
        "evictions": pt["evictions"],
        "shared_blocks_max": pt["shared_blocks_max"],
        # idle probe: the pure prefill-cost split (suffix-only vs full)
        "ttft_hit_ms": pt["ttft_idle"]["hit"],
        "ttft_miss_ms": pt["ttft_idle"]["miss"],
        # under the closed-loop load (includes queue wait)
        "ttft_hit_loaded_ms": pt["ttft_hit_ms"],
        "ttft_miss_loaded_ms": pt["ttft_miss_ms"],
        "ttft_miss_baseline_ms": base["ttft_p50_ms"],
        "p50_ms": pt["p50_ms"],
        "p99_ms": pt["p99_ms"],
        "preempted": pt["preempted"],
        "preempted_baseline": base["preempted"],
        "generations": pt["generations"],
    }))


# ---------------------------------------------------------------------------
# --decode --spec: speculative decoding on a repetitive-text workload.
#
# Methodology (PERF.md appendix "Speculative decoding"):
# - Repetitive text is what self-drafting speculation targets (code,
#   templated chat, quoting): each prompt tiles a per-client motif, so
#   the stream's own history predicts its continuation and the n-gram
#   proposer's accepted-token rate is high.  Random text would propose
#   ~nothing — and the engine then falls back to the plain step, so
#   the comparison on THIS workload bounds the win, not the loss.
# - The SAME engine config runs spec off then spec on (k from
#   DECODE_SPEC_TOKENS, default 4); greedy, so outputs are bit-equal
#   by the engine contract and only the step cadence differs.
# - Headline: accepted_token_rate, tokens_per_step, and end-to-end
#   tokens/s/chip vs the non-speculative run.
# - The served model is TRAINED (briefly, ~1-2 min on the sandbox) to
#   continue periodic token streams before benchmarking.  A random-
#   init model's greedy chains are near-chaotic (~15% self-
#   predictable, measured), which benchmarks the proposer against
#   noise; speculation's premise is a model whose output is locally
#   predictable — copy/induction behavior — and a model taught to
#   copy is the smallest honest instance of it.  DECODE_TRAIN_EPOCHS=0
#   skips training (and shows the noise floor).
# ---------------------------------------------------------------------------


def train_copy_lm(cfg, epochs, seqs=1024, batch=16, lr=2e-3):
    """Teach the bench LM to continue periodic token streams (the
    2-layer attention stack learns the induction pattern): data is
    random short motifs tiled across the sequence, labels the
    next-token shift."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    V, T = cfg["vocab_size"], cfg["max_len"]
    rng = np.random.RandomState(13)
    X = np.zeros((seqs, T), np.float32)
    y = np.zeros((seqs, T), np.float32)
    for i in range(seqs):
        m = rng.randint(2, 6)
        motif = rng.randint(1, V, size=m)
        seq = np.tile(motif, -(-(T + 1) // m))[:T + 1]
        X[i] = seq[:-1]
        y[i] = seq[1:]
    it = mx.io.NDArrayIter(X, y, batch_size=batch,
                           label_name="softmax_label")
    sym = models.transformer_lm(
        V, T, num_layers=cfg["num_layers"],
        num_heads=cfg["num_heads"], d_model=cfg["d_model"],
        block_size=cfg["kv_block"])
    mod = mx.mod.Module(sym, context=bench_ctx())
    mod.fit(it, num_epoch=epochs, optimizer="adam",
            optimizer_params={"learning_rate": lr},
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            eval_metric=mx.metric.Perplexity(0))
    arg, aux = mod.get_params()
    return {**arg, **aux}


def main_decode_spec():
    import mxnet_tpu as mx

    backend, cpu = run_mode()
    cfg = build_decode_config(cpu)
    clients = int(os.environ.get("DECODE_CLIENTS", "4" if cpu else "16"))
    per_client = int(os.environ.get("DECODE_REQUESTS",
                                    "4" if cpu else "12"))
    nmin, nmax = _csv_ints(os.environ.get("DECODE_NEW",
                                          "24,48" if cpu else "48,128"))
    pmin, pmax = _csv_ints(os.environ.get("DECODE_PROMPT",
                                          "12,32" if cpu else "32,128"))
    spec_k = int(os.environ.get("DECODE_SPEC_TOKENS", "4"))
    epochs = int(os.environ.get("DECODE_TRAIN_EPOCHS", "6"))
    proposer_name = os.environ.get("DECODE_PROPOSER", "ngram")
    log(f"spec decode backend={backend} cfg={cfg} clients={clients} "
        f"k={spec_k} train_epochs={epochs} proposer={proposer_name}")
    t0 = time.perf_counter()
    if epochs > 0:
        params = train_copy_lm(cfg, epochs)
        log(f"copy-trained LM in {time.perf_counter() - t0:.0f}s")
    else:
        params = build_lm_params(cfg)

    proposer = None
    dcfg = None
    if proposer_name == "draft_lm":
        # the Leviathan setup: a SMALLER LM trained on the same
        # distribution drafts for the big one (vs the n-gram
        # self-drafter, which can only replay the stream's history).
        # Depth stays 2 (induction needs two attention layers; 1L
        # measured 35% acceptance vs 2L's 38%); width shrinks to a
        # quarter of the target's.
        from mxnet_tpu.speculative import DraftLMProposer
        dcfg = dict(cfg, d_model=64)
        t0 = time.perf_counter()
        dparams = train_copy_lm(dcfg, epochs) if epochs > 0 \
            else build_lm_params(dcfg)
        log(f"draft LM ({dcfg['num_layers']}L d{dcfg['d_model']}) "
            f"ready in {time.perf_counter() - t0:.0f}s")
        proposer = DraftLMProposer(dparams,
                                   num_heads=dcfg["num_heads"],
                                   kv_block=cfg["kv_block"])

    def mk_request(rng):
        # repetitive prompt: a per-request motif tiled to the length —
        # the stream's own history predicts its continuation
        p = rng.randint(pmin, pmax + 1)
        n = rng.randint(nmin, nmax + 1)
        motif = rng.randint(1, cfg["vocab_size"],
                            size=rng.randint(2, 6))
        return np.tile(motif, -(-p // len(motif)))[:p] \
            .astype(np.int32), n

    def run(k):
        eng = mx.DecodeEngine(
            params, ctx=bench_ctx(), vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
            d_model=cfg["d_model"], max_len=cfg["max_len"],
            kv_block=cfg["kv_block"], max_streams=clients,
            temperature=0.0, spec_tokens=k,
            proposer=proposer if k else None, prewarm=True)
        try:
            return bench_decode_point(eng, mk_request, clients,
                                      per_client)
        finally:
            eng.close()

    t0 = time.perf_counter()
    base = run(0)
    log(f"non-speculative: {base['tokens_s']:.1f} tok/s, p50 "
        f"{base['p50_ms']:.2f} ms/token "
        f"({time.perf_counter() - t0:.0f}s)")
    t0 = time.perf_counter()
    pt = run(spec_k)
    log(f"speculative k={spec_k}: {pt['tokens_s']:.1f} tok/s, "
        f"accepted {pt['accepted_token_rate']:.0%}, "
        f"{pt['tokens_per_step']:.2f} tok/step, p50 "
        f"{pt['p50_ms']:.2f} ms/token "
        f"({time.perf_counter() - t0:.0f}s)")
    n_dev = max(1, jax.local_device_count())
    print(json.dumps({
        # draft_lm records under its own metric name so the n-gram
        # baseline history keeps a single-proposer noise model
        "metric": "serving_speculative_decode"
        + ("" if proposer_name == "ngram" else f"_{proposer_name}"),
        "value": round(pt["tokens_s"] / max(base["tokens_s"], 1e-9), 3),
        "unit": "x tokens/s vs non-speculative",
        "backend": backend,
        "model": "transformer_lm",
        "config": cfg,
        "clients": clients,
        "spec_tokens": spec_k,
        "proposer": proposer_name,
        "draft_config": dcfg,
        "accepted_token_rate": pt["accepted_token_rate"],
        "tokens_per_step": pt["tokens_per_step"],
        "spec_steps": pt["spec_steps"],
        "tokens_s": pt["tokens_s"],
        "tokens_s_chip": round(pt["tokens_s"] / n_dev, 2),
        "tokens_s_baseline": base["tokens_s"],
        "tokens_s_chip_baseline": round(base["tokens_s"] / n_dev, 2),
        "vs_nonspec": round(pt["tokens_s"]
                            / max(base["tokens_s"], 1e-9), 3),
        "p50_ms": pt["p50_ms"],
        "p99_ms": pt["p99_ms"],
        "p50_ms_baseline": base["p50_ms"],
        "p99_ms_baseline": base["p99_ms"],
        "d2h_syncs": pt["d2h_syncs"],
        "d2h_syncs_baseline": base["d2h_syncs"],
        "d2h_syncs_saved_baseline": base["d2h_syncs_saved"],
        "generations": pt["generations"],
    }))


# ---------------------------------------------------------------------------
# --decode --mixed-prefill: the chunked-prefill p99 acceptance load.
#
# Methodology (PERF.md appendix "Chunked prefill"):
# - C chat clients run short prompts continuously; one "document"
#   client keeps admitting near-max_len prompts.  Unchunked, every
#   long admission runs as ONE monolithic prefill between decode
#   steps, so each admission stalls every active chat stream's token
#   cadence — the p99 time-per-token IS the prefill wall.  Chunked,
#   the scheduler interleaves fixed-size suffix-prefill continuations
#   with decode steps, bounding the stall at one chunk.
# - Same engine config, chunk off then on (DECODE_PREFILL_CHUNK);
#   p50/p99 time-per-token come from the engine's per-step histogram
#   (per-point reset, the PR-7 convention).
# ---------------------------------------------------------------------------


def main_decode_mixed():
    import mxnet_tpu as mx

    backend, cpu = run_mode()
    cfg = build_decode_config(cpu)
    chat_clients = int(os.environ.get("DECODE_CLIENTS",
                                      "4" if cpu else "16"))
    per_client = int(os.environ.get("DECODE_REQUESTS",
                                    "6" if cpu else "12"))
    nmin, nmax = _csv_ints(os.environ.get("DECODE_NEW",
                                          "24,40" if cpu else "48,96"))
    long_len = int(os.environ.get("DECODE_LONG_LEN",
                                  "112" if cpu else "448"))
    long_new = int(os.environ.get("DECODE_LONG_NEW", "4"))
    chunk = int(os.environ.get("DECODE_PREFILL_CHUNK",
                               "32" if cpu else "128"))
    log(f"mixed-prefill decode backend={backend} cfg={cfg} "
        f"chat={chat_clients} long_len={long_len} chunk={chunk}")
    params = build_lm_params(cfg)

    def mk_chat(rng):
        p = rng.randint(8, 17)
        n = rng.randint(nmin, nmax + 1)
        return rng.randint(1, cfg["vocab_size"],
                           size=p).astype(np.int32), n

    def run(chunk_tokens):
        eng = mx.DecodeEngine(
            params, ctx=bench_ctx(), vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
            d_model=cfg["d_model"], max_len=cfg["max_len"],
            kv_block=cfg["kv_block"], max_streams=chat_clients + 1,
            temperature=0.0, prefill_chunk=chunk_tokens, prewarm=True)
        stop = threading.Event()

        def long_client():
            rng = np.random.RandomState(31337)
            while not stop.is_set():
                p = rng.randint(1, cfg["vocab_size"],
                                size=long_len).astype(np.int32)
                try:
                    eng.generate(p, long_new)
                except Exception:
                    return
                stop.wait(0.05)

        lt = threading.Thread(target=long_client, daemon=True)
        try:
            lt.start()
            time.sleep(0.2)  # let the first long admission land
            pt = bench_decode_point(eng, mk_chat, chat_clients,
                                    per_client)
            return pt
        finally:
            stop.set()
            eng.close()
            lt.join(timeout=10)

    t0 = time.perf_counter()
    base = run(0)
    log(f"monolithic prefill: chat p50 {base['p50_ms']:.2f} / p99 "
        f"{base['p99_ms']:.2f} ms/token "
        f"({time.perf_counter() - t0:.0f}s)")
    t0 = time.perf_counter()
    pt = run(chunk)
    log(f"chunk={chunk}: chat p50 {pt['p50_ms']:.2f} / p99 "
        f"{pt['p99_ms']:.2f} ms/token, {pt['prefill_chunks']} chunks "
        f"({time.perf_counter() - t0:.0f}s)")
    print(json.dumps({
        "metric": "serving_chunked_prefill_p99",
        "value": round(base["p99_ms"] / max(pt["p99_ms"], 1e-9), 3),
        "unit": "x p99 time-per-token vs monolithic prefill",
        "backend": backend,
        "model": "transformer_lm",
        "config": cfg,
        "chat_clients": chat_clients,
        "long_prompt_tokens": long_len,
        "prefill_chunk": chunk,
        "prefill_chunks": pt["prefill_chunks"],
        "p50_ms": pt["p50_ms"],
        "p99_ms": pt["p99_ms"],
        "p50_ms_unchunked": base["p50_ms"],
        "p99_ms_unchunked": base["p99_ms"],
        "p99_improvement": round(
            base["p99_ms"] / max(pt["p99_ms"], 1e-9), 3),
        "tokens_s": pt["tokens_s"],
        "tokens_s_unchunked": base["tokens_s"],
        "generations": pt["generations"],
    }))


# ---------------------------------------------------------------------------
# --tp N [--pp M]: model-parallel decode through the serving mesh.
#
# Methodology (PERF.md appendix "Model-parallel serving"):
# - The model+pool are sized so the KV pool alone EXCEEDS a per-device
#   pool budget (TP_DEVICE_POOL_BYTES; default 60% of the tp=1 pool —
#   on a real TPU slice this is the chip's free HBM after weights):
#   tp=1 provably cannot hold it, the tp-sharded engine provably can.
#   All byte numbers land in the JSON so the claim is checkable.
# - The tp=1 reference point still RUNS (CPU backend has no real HBM
#   wall) — that's what makes vs_tp1 measurable: same workload, same
#   closed loop, per-device pool bytes cut to 1/(tp*pp).
# - Decoded tokens are argmax (temperature 0): any cross-mesh numeric
#   drift would change tokens, so throughput and correctness are the
#   same run (the engine's tp bit-identity contract is separately
#   enforced by tests/test_serving_mesh.py).
# ---------------------------------------------------------------------------


def build_tp_config(cpu):
    # sized so the PAGED POOL dominates weights — the regime model-
    # parallel serving exists for (pool scales with streams x context,
    # weights don't)
    if cpu:
        return dict(vocab_size=512, num_layers=4, num_heads=4,
                    d_model=128, max_len=256, kv_block=16)
    return dict(vocab_size=8000, num_layers=8, num_heads=8,
                d_model=512, max_len=2048, kv_block=32)


def main_decode_tp():
    import mxnet_tpu as mx
    from mxnet_tpu.kv_cache import blocks_for_tokens, pool_device_bytes

    tp = int(sys.argv[sys.argv.index("--tp") + 1])
    pp = int(sys.argv[sys.argv.index("--pp") + 1]) \
        if "--pp" in sys.argv else 1
    backend, cpu = run_mode()
    cfg = build_tp_config(cpu)
    clients = int(os.environ.get("TP_CLIENTS", "4"))
    per_client = int(os.environ.get("TP_REQUESTS", "3" if cpu else "8"))
    pmin, pmax = _csv_ints(os.environ.get("TP_PROMPT",
                                          "8,48" if cpu else "64,512"))
    nmin, nmax = _csv_ints(os.environ.get("TP_NEW",
                                          "8,24" if cpu else "64,256"))
    max_streams = clients
    cache_blocks = 1 + max_streams * blocks_for_tokens(
        cfg["max_len"], cfg["kv_block"])
    pool_tp1 = pool_device_bytes(
        cache_blocks, cfg["kv_block"], cfg["num_layers"],
        cfg["num_heads"], cfg["d_model"])
    pool_tpn = pool_device_bytes(
        cache_blocks, cfg["kv_block"], cfg["num_layers"],
        cfg["num_heads"], cfg["d_model"], tp=tp, pp=pp)
    budget = int(os.environ.get("TP_DEVICE_POOL_BYTES",
                                int(pool_tp1 * 0.6)))
    log(f"tp={tp} pp={pp} backend={backend} cfg={cfg} "
        f"pool tp1={pool_tp1} sharded={pool_tpn} budget={budget}")
    if not pool_tpn <= budget < pool_tp1:
        log(f"WARNING: budget {budget} does not separate sharded "
            f"({pool_tpn}) from tp=1 ({pool_tp1}) — size the model "
            f"up or lower TP_DEVICE_POOL_BYTES")

    params = build_lm_params(cfg)
    weights_bytes = sum(
        int(np.prod(v.shape)) * 4 for v in params.values())

    def mk_request(rng):
        p = rng.randint(pmin, pmax + 1)
        n = rng.randint(nmin, nmax + 1)
        return rng.randint(1, cfg["vocab_size"],
                           size=p).astype(np.int32), n

    def run(tp_, pp_):
        eng = mx.DecodeEngine(
            params, ctx=bench_ctx(), vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
            d_model=cfg["d_model"], max_len=cfg["max_len"],
            kv_block=cfg["kv_block"], max_streams=max_streams,
            cache_blocks=cache_blocks, temperature=0.0,
            tp=tp_, pp=pp_, prewarm=True)
        try:
            pt = bench_decode_point(eng, mk_request, clients,
                                    per_client)
            pt["pool_bytes_per_device"] = \
                eng.stats()["pool_bytes_per_device"]
            return pt
        finally:
            eng.close()

    base = run(1, 1)
    log(f"tp=1: {base['tokens_s']:.1f} tok/s, p50 "
        f"{base['p50_ms']:.1f} ms, pool/dev "
        f"{base['pool_bytes_per_device']}")
    pt = run(tp, pp)
    log(f"tp={tp} pp={pp}: {pt['tokens_s']:.1f} tok/s, p50 "
        f"{pt['p50_ms']:.1f} ms, pool/dev "
        f"{pt['pool_bytes_per_device']}")
    print(json.dumps({
        "metric": "serving_tp_decode",
        "value": pt["tokens_s"],
        "unit": "tokens/s",
        "backend": backend,
        "model": "transformer_lm",
        "config": cfg,
        "tp": tp,
        "pp": pp,
        "clients": clients,
        "tokens_s": pt["tokens_s"],
        "p50_ms": pt["p50_ms"],
        "p99_ms": pt["p99_ms"],
        "ttft_p50_ms": pt["ttft_p50_ms"],
        "pool_bytes_per_device": pt["pool_bytes_per_device"],
        "pool_bytes_tp1": base["pool_bytes_per_device"],
        "weights_bytes": weights_bytes,
        "device_pool_budget_bytes": budget,
        "fits_one_device": bool(pool_tp1 <= budget),
        "fits_sharded": bool(pool_tpn <= budget),
        "tokens_s_tp1": base["tokens_s"],
        "vs_tp1": round(pt["tokens_s"] / max(base["tokens_s"], 1e-9),
                        3),
        "generations": pt["generations"],
    }))


def main():
    import mxnet_tpu as mx

    backend, cpu = run_mode()
    models_arg = os.environ.get("SERVE_MODELS", "resnet50,transformer")
    clients_sweep = _csv_ints(os.environ.get(
        "SERVE_CLIENTS", "1,4,8,16" if cpu else "1,2,4,8,16,32,64"))
    per_client = int(os.environ.get("SERVE_REQUESTS", "12" if cpu else "64"))
    buckets = _csv_ints(os.environ.get(
        "SERVE_BUCKETS", "1,8,32" if cpu else "1,8,32,128"))
    timeout_ms = float(os.environ.get("SERVE_TIMEOUT_MS", "2"))
    idle_ms = float(os.environ.get("SERVE_IDLE_MS", "1"))
    naive_n = int(os.environ.get("SERVE_NAIVE_REQUESTS",
                                 "24" if cpu else "64"))
    log(f"backend={backend} models={models_arg} clients={clients_sweep} "
        f"requests/client={per_client} buckets={buckets} "
        f"timeout={timeout_ms}ms")

    results = []
    for model_name in [m.strip() for m in models_arg.split(",") if m.strip()]:
        t0 = time.perf_counter()
        pred, mk_sample = build_predictor(model_name, cpu)
        log(f"{model_name}: built + params in {time.perf_counter()-t0:.1f}s")

        naive = bench_naive(pred, mk_sample, naive_n)
        log(f"{model_name}: naive sequential {naive['img_s']:.1f} img/s "
            f"(p50 {naive['p50_ms']:.1f} ms)")

        t0 = time.perf_counter()
        eng = mx.InferenceEngine(pred, buckets=buckets,
                                 batch_timeout_ms=timeout_ms,
                                 idle_timeout_ms=idle_ms,
                                 prewarm=True)
        log(f"{model_name}: {len(buckets)} buckets prewarmed "
            f"in {time.perf_counter()-t0:.1f}s")
        try:
            sweep = []
            for c in clients_sweep:
                pt = bench_point(eng, mk_sample, c, per_client)
                pt["vs_naive"] = round(
                    pt["throughput_img_s"] / naive["img_s"], 3)
                sweep.append(pt)
                log(f"{model_name}: {c:3d} clients -> "
                    f"{pt['throughput_img_s']:8.1f} img/s "
                    f"(x{pt['vs_naive']:.2f} naive), p50 "
                    f"{pt['p50_ms']:.1f} ms, p99 {pt['p99_ms']:.1f} ms, "
                    f"avg batch {pt['avg_batch']}")
            st = eng.stats()
            loaded = [p for p in sweep if p["clients"] >= 8] or sweep
            best = max(loaded, key=lambda p: p["throughput_img_s"])
            results.append({
                "model": model_name,
                "naive_img_s": round(naive["img_s"], 2),
                "naive_p50_ms": round(naive["p50_ms"], 3),
                "best": best,
                "sweep": sweep,
                "batch_fill_ratio": (round(st["batch_fill_ratio"], 4)
                                     if st["batch_fill_ratio"] else None),
                "compiles": {str(k): v for k, v in st["compiles"].items()},
            })
        finally:
            eng.close()

    head = results[0]
    print(json.dumps({
        "metric": "serving_throughput",
        "value": head["best"]["throughput_img_s"],
        "unit": "img/s",
        "model": head["model"],
        "backend": backend,
        "clients": head["best"]["clients"],
        "throughput_img_s": head["best"]["throughput_img_s"],
        "p50_ms": head["best"]["p50_ms"],
        "p90_ms": head["best"]["p90_ms"],
        "p99_ms": head["best"]["p99_ms"],
        "batch_fill_ratio": head["batch_fill_ratio"],
        "naive_img_s": head["naive_img_s"],
        "vs_naive": head["best"]["vs_naive"],
        "buckets": buckets,
        "batch_timeout_ms": timeout_ms,
        "requests_per_client": per_client,
        "models": results,
    }))


if __name__ == "__main__":
    if "--decode" in sys.argv and "--lora" in sys.argv:
        main_decode_lora()
    elif "--decode" in sys.argv and "--shared-prefix" in sys.argv:
        main_decode_shared()
    elif "--decode" in sys.argv and "--spec" in sys.argv:
        main_decode_spec()
    elif "--decode" in sys.argv and "--mixed-prefill" in sys.argv:
        main_decode_mixed()
    elif "--decode" in sys.argv:
        main_decode()
    elif "--tp" in sys.argv:
        main_decode_tp()
    else:
        main()
