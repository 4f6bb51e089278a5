#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

One process, one TPU chip, the entry points a user calls:

1. ``lm_train``  — ``models.transformer_lm`` at 12 layers / d_model 768
   / 12 heads / vocab 32,768 / bf16, batch 8 x T 1024, through
   ``mx.mod.Module(context=mx.tpu())``: bind, init_params,
   init_optimizer(adam), fused forward_backward/update steps on one
   repeated batch made from ``--seed``.  Passes when the loss is
   finite and fell, and the compiled fused step holds the flash
   attention kernel (``tpu_custom_call``) — the lax formulation
   standing in for it is a failure.
2. ``lm_serve``  — the trained parameters through
   ``mx.DecodeEngine(ctx=mx.tpu(), dtype="bfloat16")`` at the same
   widths: 8 ``submit()``s, prompts of 32-256 tokens, 32 greedy new
   tokens each.  Every future must resolve, the decode executable must
   hold the paged attention kernel, and the tokens are checked against
   a reference that shares nothing with the paged path: the full
   causal forward of the TRAINING symbol (``Module.forward``) over
   prompt + generated tokens.  If every generated token is that
   forward's argmax at its position, the engine's output IS the greedy
   continuation a re-run-the-whole-forward decoder would produce (by
   induction over positions).  bf16 leaves near-ties, so a token that
   is not the argmax passes only while the reference puts it within
   ``LOGPROB_TOL`` nats of its argmax, and at least ``MIN_EXACT`` of
   all tokens must be the argmax outright.
3. ``resnet_train`` — ResNet-50, batch 128, bf16, three fused steps
   through Module (``Module.fit``'s fused step, synthetic batches).

``--chips 4`` runs ONLY the multi-chip paths, in one process that
drives all four chips: the LM training step under a dp=2 x tp=2
``MeshPlan`` with ``kvstore="tpu"`` (ZeRO-1) beside the same steps on
one chip from the same seed (losses within ``loss_tol``, state spread
over the four devices), and ``DecodeEngine(tp=2)`` beside the one-chip
engine on the same requests (each held to the full-forward reference;
token equality between them reported per request).

Every phase prints one JSON line.  The LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only if every phase passed; a failed phase raises and
the process exits non-zero.  With no TPU the script exits non-zero
before any other work.  It spawns no process.
"""

import argparse
import gc
import json
import sys
import time

import numpy as np

# the package before jax: it hands libtpu its flags before a backend
# exists (mxnet_tpu/config.py).  Alone in a directory, without the
# package, the script ends here: non-zero, no result.
import mxnet_tpu as mx

LM = dict(layers=12, d_model=768, heads=12, vocab=32768, seq_len=1024,
          batch=8, steps=24, lr=1e-3, period=16)
SERVE = dict(requests=8, prompt_min=32, prompt_max=256, new_tokens=32,
             max_streams=8, prefill_buckets=(64, 128, 256),
             cache_buckets=(32, 64))
RESNET = dict(batch=128, image=224, classes=1000, layers=50, steps=3)
MESH = dict(steps=3, extra_steps=21, loss_tol=0.05, requests=4)

# a generated token that is not the reference forward's argmax passes
# only within this many nats of it (bf16 activations: the two paths
# round differently, which shows at near-ties only; the worst gap seen
# on one v5e is 0.065 nats, a token the model has not learnt sits
# several nats down) ...
LOGPROB_TOL = 0.25
# ... and at least this share of all tokens must be the argmax itself
MIN_EXACT = 0.9

KERNEL = "tpu_custom_call"  # how a Mosaic kernel shows in compiled HLO


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(device):
    stats = device.memory_stats()  # None where the backend reports none
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# data: rows that repeat a random period-P token pattern — a bigram
# table a 12-layer model memorizes in a few dozen steps, so the served
# continuations are confident instead of near-uniform over 32k tokens
# ---------------------------------------------------------------------------

def lm_tokens(cfg, seed):
    rng = np.random.RandomState(seed)
    B, T, P = cfg["batch"], cfg["seq_len"], cfg["period"]
    pattern = rng.choice(np.arange(1, cfg["vocab"]), size=(B, P),
                         replace=False)
    return np.tile(pattern, (1, T // P + 2))[:, :T + 1]


def lm_batch(toks, ctx):
    T = toks.shape[1] - 1
    return mx.io.DataBatch(
        [mx.nd.array(toks[:, :T].astype(np.float32), ctx=ctx)],
        [mx.nd.array(toks[:, 1:].astype(np.float32), ctx=ctx)])


def lm_loss(mod, toks):
    """Mean next-token cross-entropy of the module's last forward,
    reduced on the device (the (B, T, V) probabilities stay there)."""
    import jax.numpy as jnp

    probs = mod.get_outputs()[0].handle
    picked = jnp.take_along_axis(
        probs, jnp.asarray(toks[:, 1:, None], jnp.int32), axis=-1)
    return float(-jnp.mean(jnp.log(jnp.maximum(
        picked.astype(jnp.float32), 1e-12))))


def lm_module(cfg, ctx, seed):
    from mxnet_tpu import models

    sym = models.transformer_lm(
        vocab_size=cfg["vocab"], seq_len=cfg["seq_len"],
        num_layers=cfg["layers"], num_heads=cfg["heads"],
        d_model=cfg["d_model"], dtype="bfloat16")
    shape = (cfg["batch"], cfg["seq_len"])
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", shape)],
             label_shapes=[mx.io.DataDesc("softmax_label", shape)],
             for_training=True)
    mx.random.seed(seed)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="avg", magnitude=3))
    return mod


def fit_steps(mod, batch, toks, steps):
    losses = []
    for _ in range(steps):
        mod.forward_backward(batch)
        mod.update()
        losses.append(lm_loss(mod, toks))
    return losses


def check_losses(losses):
    if not all(np.isfinite(losses)):
        raise AssertionError(f"loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


def check_kernel(text, marker, what):
    if marker is not None and marker not in text:
        raise AssertionError(
            f"{what}: no {marker!r} in the compiled program — the "
            f"Pallas kernel is not on this path")
    return marker is not None


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------

def phase_lm_train(cfg, ctx, seed, kernel_marker=KERNEL):
    t0 = time.time()
    toks = lm_tokens(cfg, seed)
    mod = lm_module(cfg, ctx, seed)
    mod.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params={"learning_rate": cfg["lr"]})
    batch = lm_batch(toks, ctx)
    first = fit_steps(mod, batch, toks, 1)
    setup_s = time.time() - t0
    t0 = time.time()
    losses = first + fit_steps(mod, batch, toks, cfg["steps"] - 1)
    steps_s = time.time() - t0
    check_losses(losses)
    has_kernel = check_kernel(mod.fused_hlo_text(), kernel_marker,
                              "fused LM training step")
    emit("lm_train", layers=cfg["layers"], d_model=cfg["d_model"],
         heads=cfg["heads"], vocab=cfg["vocab"], seq_len=cfg["seq_len"],
         batch=cfg["batch"], dtype="bfloat16", steps=cfg["steps"],
         setup_compile_s=round(setup_s, 2),
         later_steps_s=round(steps_s, 2),
         loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
         kernel_in_fused_step=has_kernel,
         peak_bytes_in_use=peak_bytes(ctx.jax_device()))
    return mod, toks


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------

def serving_params(mod):
    """The trained parameters as the engine takes them.  The training
    graph casts the (float32) token embedding to bfloat16 right after
    the lookup; the serving graphs have no cast, so the table itself is
    handed over in bfloat16 — the same values enter the first block."""
    arg, aux = mod.get_params()
    params = {**arg, **aux}
    params["tok_embed_weight"] = params["tok_embed_weight"].astype(
        "bfloat16")
    return params


def make_engine(params, cfg, serve, ctx=None, **kw):
    return mx.DecodeEngine(
        params, vocab_size=cfg["vocab"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], d_model=cfg["d_model"],
        max_len=cfg["seq_len"], max_streams=serve["max_streams"],
        decode_buckets=(serve["max_streams"],),
        cache_buckets=serve["cache_buckets"],
        prefill_buckets=serve["prefill_buckets"],
        temperature=0.0, ctx=ctx, dtype="bfloat16", **kw)


def make_prompts(toks, serve, seed, n):
    """Prompts are prefixes of the training rows, lengths spread over
    [prompt_min, prompt_max] from the seed."""
    rng = np.random.RandomState(seed + 1)
    lens = np.linspace(serve["prompt_min"], serve["prompt_max"], n)
    lens = rng.permutation(lens.astype(int))
    return [toks[i % len(toks), :n_tok].astype(np.int32)
            for i, n_tok in enumerate(lens)]


def generate(eng, prompts, new_tokens):
    futures = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    outs = [np.asarray(f.result(timeout=900)) for f in futures]
    for p, o in zip(prompts, outs):
        if o.shape != (new_tokens,):
            raise AssertionError(
                f"request with a {len(p)}-token prompt resolved to "
                f"shape {o.shape}, wanted ({new_tokens},)")
    return outs


def reference_check(mod, cfg, ctx, prompts, outs):
    """Teacher-forced full causal forward of the training symbol over
    prompt + generated tokens, one request per batch row."""
    import jax.numpy as jnp

    B, T = cfg["batch"], cfg["seq_len"]
    exact = total = 0
    worst = 0.0
    for lo in range(0, len(prompts), B):
        rows = list(zip(prompts[lo:lo + B], outs[lo:lo + B]))
        data = np.zeros((B, T), np.float32)  # causal: the tail is inert
        for r, (p, o) in enumerate(rows):
            data[r, :len(p) + len(o)] = np.concatenate([p, o])
        mod.forward(mx.io.DataBatch(
            [mx.nd.array(data, ctx=ctx)],
            [mx.nd.array(np.zeros((B, T), np.float32), ctx=ctx)]),
            is_train=False)
        logp = jnp.log(jnp.maximum(
            mod.get_outputs()[0].handle.astype(jnp.float32), 1e-30))
        for r, (p, o) in enumerate(rows):
            # position len(p)-1+i predicts generated token i
            at = logp[r, len(p) - 1:len(p) - 1 + len(o)]      # (n, V)
            gap = np.asarray(jnp.max(at, axis=-1) - at[
                jnp.arange(len(o)), jnp.asarray(o, jnp.int32)])
            exact += int(np.sum(gap == 0.0))
            total += len(o)
            worst = max(worst, float(gap.max()))
    if worst > LOGPROB_TOL or exact < MIN_EXACT * total:
        raise AssertionError(
            f"engine tokens disagree with the full causal forward: "
            f"{exact}/{total} are its argmax (need {MIN_EXACT:.0%}), "
            f"worst log-prob gap {worst:.4f} nats (tolerance "
            f"{LOGPROB_TOL})")
    return exact, total, worst


def phase_lm_serve(mod, toks, cfg, serve, ctx, seed,
                   kernel_marker=KERNEL):
    t0 = time.time()
    eng = make_engine(serving_params(mod), cfg, serve, ctx=ctx)
    prompts = make_prompts(toks, serve, seed, serve["requests"])
    outs = generate(eng, prompts, serve["new_tokens"])
    serve_s = time.time() - t0
    stats = eng.stats()
    decode_keys = [k for k in eng.compiles if k[0] == "decode"]
    has_kernel = all([check_kernel(eng.executable_text(k), kernel_marker,
                                   f"decode executable {k}")
                      for k in decode_keys])
    eng.close()
    if not decode_keys:
        raise AssertionError("no decode executable was compiled")
    exact, total, worst = reference_check(mod, cfg, ctx, prompts, outs)
    emit("lm_serve", requests=len(prompts),
         prompt_tokens=[int(len(p)) for p in prompts],
         new_tokens_each=serve["new_tokens"],
         tokens_generated=int(sum(len(o) for o in outs)),
         engine_tokens=stats["tokens"], decode_steps=stats["steps"],
         executables=sorted(map(list, eng.compiles)),
         executables_compiled=len(eng.compiles),
         kv_block=stats["kv_block"],
         pool_pages=1 + serve["max_streams"]
         * (cfg["seq_len"] // stats["kv_block"]),
         pool_bytes=stats["pool_bytes_per_device"],
         setup_compile_serve_s=round(serve_s, 2),
         paged_kernel_in_decode=has_kernel,
         reference="Module.forward of the training symbol, "
                   "teacher-forced",
         tokens_equal_reference_argmax=f"{exact}/{total}",
         worst_logprob_gap=round(worst, 5), logprob_tol=LOGPROB_TOL,
         peak_bytes_in_use=peak_bytes(ctx.jax_device()))
    return prompts, outs


# ---------------------------------------------------------------------------
# phase 3: ResNet-50 (Module's fused step on a convolutional net)
# ---------------------------------------------------------------------------

def phase_resnet_train(cfg, ctx, seed):
    import jax.numpy as jnp
    from mxnet_tpu import models

    t0 = time.time()
    batch, size = cfg["batch"], cfg["image"]
    sym = models.resnet(num_classes=cfg["classes"],
                        num_layers=cfg["layers"],
                        image_shape=(3, size, size), stem="s2d")
    rng = np.random.RandomState(seed)
    X = mx.nd.array(rng.rand(batch, 3, size, size).astype(np.float32)
                    .astype(jnp.bfloat16), ctx=ctx)
    y = rng.randint(0, cfg["classes"], size=batch)
    data = mx.io.DataBatch([X], [mx.nd.array(y.astype(np.float32),
                                             ctx=ctx)])
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[mx.io.DataDesc("data", (batch, 3, size, size),
                                         dtype=jnp.bfloat16)],
             label_shapes=[mx.io.DataDesc("softmax_label", (batch,))],
             for_training=True)
    mx.random.seed(seed)
    mod.init_params(mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.34))
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.005,
                                         "momentum": 0.9})
    losses = []
    for _ in range(cfg["steps"]):
        mod.forward_backward(data)
        mod.update()
        probs = np.asarray(mod.get_outputs()[0].asnumpy(), np.float32)
        if probs.shape != (batch, cfg["classes"]):
            raise AssertionError(f"ResNet output shape {probs.shape}")
        losses.append(float(-np.mean(np.log(np.maximum(
            probs[np.arange(batch), y], 1e-12)))))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"ResNet loss not finite: {losses}")
    emit("resnet_train", layers=cfg["layers"], batch=batch,
         image=size, dtype="bfloat16", steps=cfg["steps"],
         setup_compile_steps_s=round(time.time() - t0, 2),
         losses=[round(v, 4) for v in losses],
         peak_bytes_in_use=peak_bytes(ctx.jax_device()))


# ---------------------------------------------------------------------------
# --chips 4: the paths that exist only across chips
# ---------------------------------------------------------------------------

def bytes_in_use(devices):
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if stats is None else stats.get("bytes_in_use"))
    return out


def phase_mesh_train(cfg, mesh_cfg, ctx0, devices, seed,
                     kernel_marker=KERNEL):
    """dp=2 x tp=2 + ZeRO-1 beside one chip, same seed, same steps."""
    from mxnet_tpu import hlo, parallel
    from mxnet_tpu.models import transformer

    toks = lm_tokens(cfg, seed)
    opt = {"learning_rate": cfg["lr"]}
    steps = mesh_cfg["steps"]

    t0 = time.time()
    mod = lm_module(cfg, ctx0, seed)
    mod.set_mesh_plan(parallel.MeshPlan(
        devices, dp=2, tp=2, rules=transformer.lm_partition_rules()))
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params=opt)
    batch = lm_batch(toks, ctx0)
    mesh_losses = fit_steps(mod, batch, toks, steps)
    mesh_s = time.time() - t0
    spread = bytes_in_use(devices)
    text = mod.fused_hlo_text()
    has_kernel = check_kernel(text, kernel_marker, "dp2 x tp2 fused step")
    overlap = hlo.overlap_report(text)

    t0 = time.time()
    one = lm_module(cfg, ctx0, seed)
    one.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params=opt)
    one_losses = fit_steps(one, batch, toks, steps)
    one_s = time.time() - t0
    del one
    gc.collect()

    check_losses(mesh_losses)
    diff = float(np.max(np.abs(np.subtract(mesh_losses, one_losses))))
    if diff > mesh_cfg["loss_tol"]:
        raise AssertionError(
            f"mesh losses {mesh_losses} vs one chip {one_losses}: "
            f"differ by {diff:.4f} (tolerance {mesh_cfg['loss_tol']})")
    if None not in spread:
        # tp=2 halves the parameters a device holds, ZeRO-1 over dp=2
        # halves its optimizer state: nothing may pile up on device 0
        if min(spread) <= 0 or spread[0] > 0.4 * sum(spread):
            raise AssertionError(
                f"state is not spread over the mesh: bytes_in_use "
                f"{spread}")
    # train on so the served continuations are confident (see lm_tokens)
    more = fit_steps(mod, batch, toks, mesh_cfg["extra_steps"])
    emit("mesh_train", mesh="dp=2 x tp=2", kvstore="tpu (ZeRO-1)",
         steps=steps, mesh_losses=[round(v, 4) for v in mesh_losses],
         one_chip_losses=[round(v, 4) for v in one_losses],
         max_loss_diff=round(diff, 5), loss_tol=mesh_cfg["loss_tol"],
         bytes_in_use_per_device=spread, kernel_in_fused_step=has_kernel,
         overlap_report=overlap, mesh_setup_compile_steps_s=round(mesh_s, 2),
         one_chip_setup_compile_steps_s=round(one_s, 2),
         loss_after_extra_steps=round(more[-1], 4))
    return mod, toks


def phase_mesh_serve(mod, toks, cfg, serve, mesh_cfg, ctx0, seed):
    """DecodeEngine(tp=2) beside the one-chip engine, same requests.

    Sharding the matmuls over tp changes how the chip tiles them, so
    in bf16 the two engines may part at a near-tie (the first
    four-chip run: 3 of 4 requests token-identical, one not).  Each
    engine's tokens are therefore held to the SAME reference and the
    same tolerance as the one-chip serving phase — the full causal
    forward of the training symbol, here in a one-device Module that
    is given the mesh-trained parameters — and token equality between
    the engines is reported per request."""
    params = serving_params(mod)
    prompts = make_prompts(toks, serve, seed, mesh_cfg["requests"])
    t0 = time.time()
    eng = make_engine(params, cfg, serve, tp=2, devices=[0, 1])
    tp_outs = generate(eng, prompts, serve["new_tokens"])
    eng.close()
    tp_s = time.time() - t0
    t0 = time.time()
    eng = make_engine(params, cfg, serve, ctx=ctx0)
    one_outs = generate(eng, prompts, serve["new_tokens"])
    eng.close()
    one_s = time.time() - t0
    ref = lm_module(cfg, ctx0, seed)
    ref.set_params(*({k: mx.nd.array(v, ctx=ctx0) for k, v in part.items()}
                     for part in mod.get_params()))  # off the mesh
    checks = {}
    for name, outs in (("tp2", tp_outs), ("one_chip", one_outs)):
        exact, total, worst = reference_check(ref, cfg, ctx0, prompts,
                                              outs)
        checks[name] = {"equal_reference_argmax": f"{exact}/{total}",
                        "worst_logprob_gap": round(worst, 5)}
    emit("mesh_serve", engine="DecodeEngine(tp=2, devices=[0, 1])",
         requests=len(prompts),
         prompt_tokens=[int(len(p)) for p in prompts],
         new_tokens_each=serve["new_tokens"],
         tokens_equal_per_request=[
             bool(np.array_equal(a, b)) for a, b in zip(tp_outs, one_outs)],
         reference="one-device Module.forward of the training symbol "
                   "with the mesh-trained parameters, teacher-forced",
         vs_reference=checks, logprob_tol=LOGPROB_TOL,
         tp2_setup_compile_serve_s=round(tp_s, 2),
         one_chip_setup_compile_serve_s=round(one_s, 2))


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths, on all four "
                         "chips of one host (default: the one-chip "
                         "phases)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax reports {dev.platform!r} "
              f"devices — refusing to run on anything else",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from mxnet_tpu.config import place_compile_cache

    emit("start", device_kind=dev.device_kind, devices=len(devices),
         chips=args.chips, seed=args.seed, jax=jax.__version__,
         compile_cache_dir=place_compile_cache())

    ctx = mx.tpu(0)
    t0 = time.time()
    if args.chips == 1:
        mod, toks = phase_lm_train(LM, ctx, args.seed)
        phase_lm_serve(mod, toks, LM, SERVE, ctx, args.seed)
        del mod
        gc.collect()
        phase_resnet_train(RESNET, ctx, args.seed)
    else:
        mod, toks = phase_mesh_train(LM, MESH, ctx, devices[:4],
                                     args.seed)
        phase_mesh_serve(mod, toks, LM, SERVE, MESH, ctx, args.seed)
    from mxnet_tpu import _native

    # the native record-IO library (built from native/recordio.cc, or a
    # .so left on disk) is not on this path: lib() was never asked
    emit("done", total_s=round(time.time() - t0, 2),
         native_io_lib_asked_for=_native._tried)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
