"""The plain reference for the ``granitemoehybrid`` family, and its
seeded weights.

Granite-4.0-H as its public ``config.json`` describes it, cut to one
chip's share of a stated deployment (the configuration file says which):
pre-norm residual blocks, RMSNorm (``rms_norm_eps``), no biases but the
convolution's, no positions (``position_embedding_type: nope``), four
scalar multipliers and a tied head.  With d = ``hidden_size``:

* ``x_0 = embedding_multiplier * E[token]``; each layer ``x += r *
  Mixer(RMSNorm(x))`` then ``x += r * FFN(RMSNorm(x))``, r =
  ``residual_multiplier``; ``logits = E RMSNorm(x_L) / logits_scaling``
  (``tie_word_embeddings``);
* layers whose ``layer_types`` entry is ``attention`` —
  ``num_attention_heads`` query heads over ``num_key_value_heads`` KV
  heads of ``head_dim``, query head ``i`` on KV head ``i // (heads /
  kv_heads)``, ``softmax(q k^T * attention_multiplier)`` causal (the
  multiplier in place of ``head_dim^-1/2``), no rotation, ``W_o``;
* the ``mamba`` layers — Mamba-2: ``[z | xBC | dt] = W_in u`` of widths
  d_inner | d_inner + 2 G N | H (d_inner = ``mamba_expand`` d = H heads
  of P = ``mamba_d_head``; N = ``mamba_d_state``, G =
  ``mamba_n_groups``); ``xBC <- SiLU(conv_K(xBC) + b)`` depthwise,
  causal, K = ``mamba_d_conv`` taps, tap K-1 on the current token;
  ``Delta_t = softplus(dt_t + dt_bias)`` and ``a_t = exp(-Delta_t
  exp(A_log))`` a head; ``S_t = a_t S_{t-1} + Delta_t x_t B_t^T`` (a
  head's state (P, N), B and C of a group shared by its heads), ``y_t =
  S_t C_t + D x_t`` — a ``lax.scan``, token by token; ``y <- RMSNorm(y
  * SiLU(z))`` over all d_inner channels, times its gain; ``W_out y``;
* every layer's FFN — ``r = W_r u`` over all the published experts
  (float32), the ``num_experts_per_tok`` largest LOGITS, weights a
  softmax over those alone; ``E(u) = W_down (SiLU(W_gate u) * W_up u)``
  of width ``intermediate_size``; ``FFN(u) = E_shared(u)`` (width
  ``shared_intermediate_size``) ``+ sum over the chosen experts HELD
  HERE of g_e E_e(u)``: a loop over the experts held.  What the experts
  on other chips would add is left out, here as in the program, and the
  partial sum goes on to the next layer.

Departures from the published description are in the configuration
file (``assumed``, ``departures``); the initialisation, which a speed
and agreement benchmark needs only to be seeded, is in :func:`_draw`.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
chunk form, no cache, no batching.  It imports nothing of ``mxnet_tpu``
but the spec class (:func:`spec` describes the model to the engine;
nothing of the program's arithmetic is used).  Weights are HELD as
drawn and cast to float32 where they are multiplied, an expert at a
time.

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (experts and the
tied head included; the router stays float32, as in the program) in
e4m3 with one scale per tensor; ``bf16_state`` rounds the Mamba-2 state
to bfloat16 after every token; ``bfloat16`` multiplies in bfloat16
(what the program's own precision would pick: used to count unstable
top-k sets).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what no family changes — the key from a seed, the linear layers'
# arithmetic by precision, the norm, the gated FFN, the program's names
# for the drawn leaves — is the first hybrid reference's, imported
from benchmark.reference.solar_open2 import (  # noqa: F401
    HI, gated_ffn, lin, mm, program_names, rms, seed_key, to_float32)


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def sizes(cfg):
    L = int(cfg["num_hidden_layers"])
    d = int(cfg["hidden_size"])
    H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if H * P != int(cfg["mamba_expand"]) * d:
        raise ValueError(f"mamba_n_heads x mamba_d_head = {H * P} is not "
                         f"mamba_expand x hidden_size")
    Hq = int(cfg["num_attention_heads"])
    return dict(
        L=L, d=d, V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]),
        # the published list, of which this cut holds the first L
        kinds=tuple("attention" if t == "attention" else "mamba2"
                    for t in cfg["layer_types"][:L]),
        Hq=Hq, Hkv=int(cfg["num_key_value_heads"]),
        D=int(cfg.get("head_dim") or d // Hq),
        att_scale=float(cfg["attention_multiplier"]),
        H=H, P=P, N=int(cfg["mamba_d_state"]),
        G=int(cfg["mamba_n_groups"]), K=int(cfg["mamba_d_conv"]),
        conv_bias=bool(cfg["mamba_conv_bias"]),
        E=int(cfg.get("num_local_experts_published",
                      cfg["num_local_experts"])),
        held=int(cfg["num_local_experts"]),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        w=int(cfg["intermediate_size"]),
        ws=int(cfg["shared_intermediate_size"]),
        embed=float(cfg["embedding_multiplier"]),
        resid=float(cfg["residual_multiplier"]),
        logits=1.0 / float(cfg["logits_scaling"]),
        std=float(cfg.get("initializer_range", 0.02)),
        L_pub=int(cfg.get("num_hidden_layers_published", L)))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it."""
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    z = sizes(cfg)
    ffn = {"kind": "moe", "experts": z["E"], "top_k": z["top_k"],
           "width": z["w"], "shared": 1, "shared_width": z["ws"],
           "score": "softmax_topk", "experts_held": z["held"],
           "first_expert": z["first"]}
    mixers = {
        "attention": {"kind": "attention", "heads": z["Hq"],
                      "kv_heads": z["Hkv"], "head_dim": z["D"],
                      "scale": z["att_scale"]},
        "mamba2": {"kind": "mamba2", "heads": z["H"], "head_dim": z["P"],
                   "d_state": z["N"], "groups": z["G"], "conv": z["K"],
                   "conv_bias": z["conv_bias"]}}
    layers = [{"mixer": dict(mixers[kind]), "ffn": dict(ffn)}
              for kind in z["kinds"]]
    return HybridSpec(z["V"], z["d"], layers, norm_eps=z["eps"],
                      embed_scale=z["embed"], residual_scale=z["resid"],
                      logits_scale=z["logits"], tied_head=True)


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

def _layer_shapes(z, kind):
    d, w, ws = z["d"], z["w"], z["ws"]
    out = {"norm1_gamma": (d,), "norm2_gamma": (d,)}
    if kind == "attention":
        hd, kd = z["Hq"] * z["D"], z["Hkv"] * z["D"]
        out.update(q_weight=(hd, d), k_weight=(kd, d), v_weight=(kd, d),
                   o_weight=(d, hd))
    else:
        di = z["H"] * z["P"]
        conv = di + 2 * z["G"] * z["N"]
        out.update(in_weight=(di + conv + z["H"], d),
                   conv_weight=(conv, z["K"]), a_log=(z["H"],),
                   dt_bias=(z["H"],), d_skip=(z["H"],),
                   onorm_gamma=(di,), out_weight=(d, di))
        if z["conv_bias"]:
            out["conv_bias"] = (conv,)
    out.update(router_weight=(z["E"], d),
               experts_gate_weight=(z["held"], d, w),
               experts_up_weight=(z["held"], d, w),
               experts_down_weight=(z["held"], w, d),
               shared_gate_weight=(ws, d), shared_up_weight=(ws, d),
               shared_down_weight=(d, ws))
    return out


FLOAT32_LEAVES = ("router_weight", "a_log", "dt_bias", "d_skip")
RESIDUAL_OUT = ("o_weight", "out_weight", "experts_down_weight",
                "shared_down_weight")


@functools.partial(jax.jit, static_argnames=("static", "kind",
                                             "embed_dtype", "dtype"))
def _draw(key, static, kind, embed_dtype, dtype):
    """One program makes the tensors of one layer (``kind``: its mixer)
    or of the top (``kind`` None: the tied table and the last norm) on
    the device — a layer at a time, so that the float32 draws never lie
    side by side: N(0, std) matrices (the projections back into the
    residual stream scaled by 1/sqrt(2 x published depth)), unit gains
    and skips, and the state-space constants as the mechanism's authors
    initialise them (exp(A) uniform in 1..16; the step log-uniform in
    0.001..0.1; conv taps and bias uniform in +-K^-1/2); each rounded to
    the type it is held in."""
    z = dict(static)
    resid = 1.0 / math.sqrt(2.0 * z["L_pub"])

    def make(name, shape, k):
        if name.endswith("_gamma") or name == "d_skip":
            x = jnp.ones(shape, jnp.float32)
        elif name == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
        elif name in ("conv_weight", "conv_bias"):
            b = z["K"] ** -0.5
            x = jax.random.uniform(k, shape, jnp.float32, -b, b)
        else:
            scale = z["std"] * (resid if name in RESIDUAL_OUT else 1.0)
            x = scale * jax.random.normal(k, shape, jnp.float32)
        if name in FLOAT32_LEAVES:
            return x
        return x.astype(embed_dtype if name == "tok_embed_weight"
                        else dtype)

    shapes = _layer_shapes(z, kind) if kind else {
        "tok_embed_weight": (z["V"], z["d"]), "final_norm_gamma": (z["d"],)}
    return {n: make(n, s, k) for (n, s), k in
            zip(shapes.items(), jax.random.split(key, len(shapes)))}


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights, ``{"layers": [{leaf: array}, ...], top
    leaves}``, in the types the program serves them in (the router and
    the state-space constants float32)."""
    static = _static(cfg)
    kinds = sizes(cfg)["kinds"]
    keys = jax.random.split(seed_key(seed), len(kinds) + 1)
    out = _draw(keys[-1], static, None, embed_dtype, dtype)
    out["layers"] = [_draw(k, static, kind, embed_dtype, dtype)
                     for kind, k in zip(kinds, keys)]
    return out


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

def attention(p, u, z, precision):
    T = u.shape[0]
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    q = lin(u, p["q_weight"], precision).reshape(T, Hq, D)
    k = lin(u, p["k_weight"], precision).reshape(T, Hkv, D)
    v = lin(u, p["v_weight"], precision).reshape(T, Hkv, D)
    G = Hq // Hkv                  # query head i reads KV head i // G
    causal = jnp.tril(jnp.ones((T, T), bool))

    def group(xs):                 # one KV head and its G query heads
        qg, kg, vg = xs            # (G, T, D), (T, D), (T, D)
        s = jnp.einsum("gtd,sd->gts", qg, kg, precision=HI) * z["att_scale"]
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, axis=-1), vg,
                          precision=HI)

    a = lax.map(group, (q.reshape(T, Hkv, G, D).transpose(1, 2, 0, 3),
                        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    a = a.transpose(2, 0, 1, 3).reshape(T, Hq * D)   # (Hkv, G, T, D) ->
    return lin(a, p["o_weight"], precision)


def mamba2(p, u, z, precision, n=None):
    """(the layer's output (T, d), the state (H, P, N) after the first
    ``n`` tokens: all of them where ``n`` is None)."""
    T = u.shape[0]
    H, P, N, G, K = z["H"], z["P"], z["N"], z["G"], z["K"]
    di = H * P
    proj = lin(u, p["in_weight"], precision)
    gate, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * G * N],
                     proj[:, 2 * di + 2 * G * N:])
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], axis=0)
    cw = p["conv_weight"].astype(jnp.float32)
    c = sum(xp[j:j + T] * cw[:, j] for j in range(K))
    if z["conv_bias"]:
        c = c + p["conv_bias"].astype(jnp.float32)
    c = jax.nn.silu(c)
    x = c[:, :di].reshape(T, H, P)
    # a group's B and C serve its H / G heads
    bm = jnp.repeat(c[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    cm = jnp.repeat(c[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    delta = jax.nn.softplus(dt + p["dt_bias"])                 # (T, H)
    a = jnp.exp(-delta * jnp.exp(p["a_log"]))

    def one(S0, xs):                      # S (H, P, N)
        xt, bt, ct, at, dlt, live = xs
        S = at[:, None, None] * S0 \
            + (dlt[:, None] * xt)[:, :, None] * bt[:, None, :]
        if precision == "bf16_state":
            # (reduce_precision: a convert to bfloat16 and back is
            # removed by the compiler, which may keep excess precision)
            S = lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        # past the n-th token the state stands still (padding)
        return jnp.where(live, S, S0), \
            jnp.einsum("hpn,hn->hp", S, ct, precision=HI)

    live = jnp.arange(T) < (T if n is None else n)
    last, y = lax.scan(one, jnp.zeros((H, P, N), jnp.float32),
                       (x, bm, cm, a, delta, live))
    y = y + p["d_skip"][None, :, None] * x
    y = rms(y.reshape(T, di) * jax.nn.silu(gate), p["onorm_gamma"],
            z["eps"])
    return lin(y, p["out_weight"], precision), last


def route(p, u, z):
    """(chosen experts (T, k), their weights (T, k)), float32: the k
    largest logits, a softmax over those alone."""
    r = jnp.dot(u, p["router_weight"].T, precision=HI)
    topv, topi = lax.top_k(r, z["top_k"])
    return topi, jax.nn.softmax(topv, axis=-1)


def moe(p, u, z, precision):
    """(the FFN's output, the chosen experts): the shared expert plus
    the held experts' part of the routed sum, an expert at a time."""
    topi, wts = route(p, u, z)
    # coef[t, j]: token t's weight for held expert j (0 if not chosen)
    held = z["first"] + jnp.arange(z["held"])
    coef = jnp.sum(jnp.where(topi[:, :, None] == held[None, None, :],
                             wts[:, :, None], 0.0), axis=1)

    def one(acc, xs):
        wg, wu, wd, c = xs
        y = mm(jax.nn.silu(mm(u, wg, precision)) * mm(u, wu, precision),
               wd, precision)
        return acc + c[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (p["experts_gate_weight"], p["experts_up_weight"],
                       p["experts_down_weight"], coef.T))
    out = out + gated_ffn(u, p["shared_gate_weight"], p["shared_up_weight"],
                          p["shared_down_weight"], precision)
    return out, jnp.sort(topi, axis=-1)


def hidden(w, tokens, z, precision="float32", n=None):
    """tokens (T,) -> (the last block's output (T, d), the chosen
    experts of every layer (L, T, k), sorted, and the mamba layers'
    states (one (H, P, N) a mamba layer) after the first ``n``
    tokens)."""
    x = z["embed"] * w["tok_embed_weight"].astype(jnp.float32)[tokens]
    chosen, states = [], []
    for kind, p in zip(z["kinds"], w["layers"]):
        u = rms(x, p["norm1_gamma"], z["eps"])
        if kind == "attention":
            x = x + z["resid"] * attention(p, u, z, precision)
        else:
            y, last = mamba2(p, u, z, precision, n)
            states.append(last)
            x = x + z["resid"] * y
        u = rms(x, p["norm2_gamma"], z["eps"])
        y, topi = moe(p, u, z, precision)
        chosen.append(topi)
        x = x + z["resid"] * y
    return x, jnp.stack(chosen), states


def logits(w, rows, z, precision="float32"):
    """The tied head: the token table once more."""
    return z["logits"] * lin(rms(rows, w["final_norm_gamma"], z["eps"]),
                             w["tok_embed_weight"], precision)


def forward(cfg, w, tokens, precision="float32"):
    """Logits (T, V) of one sequence: the whole model, for the tests."""
    z = sizes(cfg)
    h, _, _ = hidden(w, jnp.asarray(tokens), z, precision)
    return logits(w, h, z, precision)


@functools.partial(jax.jit, static_argnames=("static", "precision",
                                             "n_out"))
def _served_gaps(w, tokens, start, served, static, precision, n_out):
    z = dict(static)
    h, chosen, _ = hidden(w, tokens, z, "float32")
    rows = lax.dynamic_slice_in_dim(h, start, n_out, axis=0)
    zf = logits(w, rows, z, "float32")
    best = jnp.max(zf, axis=-1)
    gap_served = best - jnp.take_along_axis(zf, served[:, None], -1)[:, 0]
    if precision == "float32":
        return gap_served, jnp.zeros_like(gap_served), \
            jnp.zeros((n_out,), bool)
    hl, chosen_l, _ = hidden(w, tokens, z, precision)
    rl = lax.dynamic_slice_in_dim(hl, start, n_out, axis=0)
    first = jnp.argmax(logits(w, rl, z, precision), axis=-1)
    gap_low = best - jnp.take_along_axis(zf, first[:, None], -1)[:, 0]
    differ = jnp.any(chosen != chosen_l, axis=(0, 2))          # (T,)
    return gap_served, gap_low, \
        lax.dynamic_slice_in_dim(differ, start, n_out, axis=0)


def served_gaps(cfg, w, tokens, start, served, precision, n_out):
    """One request, teacher-forced.  ``tokens`` (T,): prompt + served
    tokens, padded; ``start``: index of the position that predicts the
    first served token; ``served`` (n_out,): the served tokens, padded.

    Returns, per served position: the float32 reference's best logit
    minus its logit of the served token; minus its logit of the token
    that ``precision`` puts first there (zeros for float32); and whether
    any layer's top-k expert SET differs between float32 and
    ``precision`` at that position."""
    return _served_gaps(w, tokens, start, served, _static(cfg), precision,
                        n_out)


@functools.partial(jax.jit, static_argnames=("static", "precision"))
def _final_states(w, tokens, n, static, precision):
    return hidden(w, tokens, dict(static), precision, n)[2]


def final_states(cfg, w, tokens, n, precision="float32"):
    """What a stream's slot must hold once the first ``n`` of
    ``tokens`` (T,) (padded) have been fed: ``{"layer<i>_state":
    (H, N, P)}``, a mamba layer each — the scan's last state, a head's
    matrix turned as the runner turns the program's ((H, P, N) in the
    pools and in the scan above)."""
    states = _final_states(w, tokens, n, _static(cfg), precision)
    at = [i for i, kind in enumerate(sizes(cfg)["kinds"])
          if kind == "mamba2"]
    return {f"layer{i}_state": s.transpose(0, 2, 1)
            for i, s in zip(at, states)}
