"""The plain reference for the ``solar_open2`` family, and its seeded
weights.

Solar-Open2 as its public ``config.json`` describes it, cut to one
chip's share of a stated deployment (the configuration file says which):
pre-norm residual blocks, RMSNorm (eps from the config), no biases, no
positions (``use_rope: false``).  With ``h = RMSNorm(x)``:

* layers in ``gqa_layers`` — softmax attention, ``num_attention_heads``
  query heads over ``num_key_value_heads`` KV heads of ``head_dim``,
  query head ``i`` on KV head ``i // (heads / kv_heads)``, scaled by
  ``head_dim^-1/2``, causal, no rotation, and an output gate:
  ``x += W_o (sigmoid(W_g h) * a)``;
* the other layers — KDA, the gated delta rule with a per-channel
  decay, ``linear_attn_config.num_heads`` heads of ``head_dim``:
  ``q, k, v = SiLU(conv_K(W h))`` (depthwise, causal, kernel
  ``short_conv_kernel_size``, tap K-1 on the current token); q, k
  L2-normalised, q scaled by ``d_k^-1/2``;
  ``alpha_t = exp(-exp(A) softplus(W_a_up W_a_down h + dt_bias))`` per
  channel, ``beta_t = 2 sigmoid(W_beta h)`` per head
  (``kda_allow_neg_eigval``);
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t`` — a ``lax.scan``, token by token;
  ``x += W_o (RMSNorm_head(o_t) * sigmoid(W_g_up W_g_down h))``;
* every layer's FFN — ``s = sigmoid(W_r h)`` over all the published
  experts (float32), the ``num_experts_per_tok`` largest, weights
  ``s_e / sum_top s`` times ``routed_scaling_factor``;
  ``E(h) = W_down (SiLU(W_gate h) * W_up h)``;
  ``x += E_shared(h) + sum over the chosen experts HELD HERE of w_e
  E_e(h)``: a loop over the experts held.  What the experts on other
  chips would add is left out, here as in the program, and the partial
  sum goes on to the next layer.

Departures from the published description (each also under ``assumed``
in the configuration file): the router's score function (sigmoid, no
selection bias), the rank of the low-rank maps (``head_dim``), the
shared expert's width (``moe_intermediate_size * n_shared_experts``),
the gate's form, no q/k norm in the attention layers — the config does
not give them; and the initialisation, which a speed and agreement
benchmark needs only to be seeded.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
cache, no batching.  It imports nothing of ``mxnet_tpu`` but the spec
class (:func:`spec` describes the model to the engine; nothing of the
program's arithmetic is used).  Weights are HELD as drawn (bfloat16
holds the drawn values exactly) and cast to float32 where they are
multiplied, an expert at a time: 3.3B parameters in float32 would not
fit the chip beside anything.

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (experts and head
included; the router stays float32, as in the program) in e4m3 with one
scale per tensor; ``bf16_state`` rounds the KDA state to bfloat16 after
every token; ``bfloat16`` multiplies in bfloat16 (what the program's
own precision would pick: used to count unstable top-k sets).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def sizes(cfg):
    lin = cfg["linear_attn_config"]
    L = int(cfg["num_hidden_layers"])
    return dict(
        L=L, d=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]),
        kinds=tuple("attention" if i in cfg["gqa_layers"] else "kda"
                    for i in range(L)),
        Hq=int(cfg["num_attention_heads"]),
        Hkv=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
        gate=bool(cfg["use_gqa_gate"]),
        Hl=int(lin["num_heads"]), Dl=int(lin["head_dim"]),
        K=int(lin["short_conv_kernel_size"]),
        neg=bool(cfg["kda_allow_neg_eigval"]),
        E=int(cfg["n_routed_experts_published"]),
        held=int(cfg["n_routed_experts"]),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        w=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        scaling=float(cfg["routed_scaling_factor"]),
        std=float(cfg.get("initializer_range", 0.02)),
        L_pub=int(cfg["num_hidden_layers_published"]))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it."""
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    z = sizes(cfg)
    ffn = {"kind": "moe", "experts": z["E"], "top_k": z["top_k"],
           "width": z["w"], "shared": z["shared"],
           "experts_held": z["held"], "first_expert": z["first"]}
    layers = []
    for kind in z["kinds"]:
        if kind == "attention":
            mixer = {"kind": "attention", "heads": z["Hq"],
                     "kv_heads": z["Hkv"], "head_dim": z["D"],
                     "gate": z["gate"]}
        else:
            mixer = {"kind": "kda", "heads": z["Hl"], "head_dim": z["Dl"],
                     "conv": z["K"], "neg_eigval": z["neg"]}
        layers.append({"mixer": mixer, "ffn": dict(ffn)})
    return HybridSpec(z["V"], z["d"], layers, norm_eps=z["eps"])


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def _layer_shapes(z, kind):
    d, w = z["d"], z["w"]
    out = {"norm1_gamma": (d,), "norm2_gamma": (d,)}
    if kind == "attention":
        hd, kd = z["Hq"] * z["D"], z["Hkv"] * z["D"]
        out.update(q_weight=(hd, d), k_weight=(kd, d), v_weight=(kd, d),
                   o_weight=(d, hd))
        if z["gate"]:
            out["gate_weight"] = (hd, d)
    else:
        H, D = z["Hl"], z["Dl"]
        out.update(qkv_weight=(3 * H * D, d), conv_weight=(3 * H * D, z["K"]),
                   a_down_weight=(D, d), a_up_weight=(H * D, D),
                   dt_bias=(H * D,), a_log=(H,), beta_weight=(H, d),
                   g_down_weight=(D, d), g_up_weight=(H * D, D),
                   onorm_gamma=(D,), o_weight=(d, H * D))
    out.update(router_weight=(z["E"], d),
               experts_gate_weight=(z["held"], d, w),
               experts_up_weight=(z["held"], d, w),
               experts_down_weight=(z["held"], w, d))
    if z["shared"]:
        ws = w * z["shared"]
        out.update(shared_gate_weight=(ws, d), shared_up_weight=(ws, d),
                   shared_down_weight=(d, ws))
    return out


FLOAT32_LEAVES = ("router_weight", "a_log", "dt_bias")
RESIDUAL_OUT = ("o_weight", "experts_down_weight", "shared_down_weight")


@functools.partial(jax.jit, static_argnames=("static", "kind",
                                             "embed_dtype", "dtype"))
def _draw(key, static, kind, embed_dtype, dtype):
    """One program makes the tensors of one layer (``kind``: its mixer)
    or of the top (``kind`` None: table, last norm, head) on the device
    — a layer at a time, so that the float32 draws of 3.3B parameters
    never lie side by side: N(0, std) matrices (the projections back
    into the residual stream scaled by 1/sqrt(2 x published depth)),
    unit gains, and the KDA gates' constants as the mechanism's authors
    initialise them (exp(A) uniform in 1..16; a decay step log-uniform
    in 0.001..0.1; conv taps uniform in +-K^-1/2); each rounded to the
    type it is held in."""
    z = dict(static)
    resid = 1.0 / math.sqrt(2.0 * z["L_pub"])

    def make(name, shape, k):
        if name.endswith("_gamma"):
            x = jnp.ones(shape, jnp.float32)
        elif name == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
        elif name == "conv_weight":
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
        "tok_embed_weight": (z["V"], z["d"]),
        "final_norm_gamma": (z["d"],), "head_weight": (z["V"], z["d"])}
    return {n: make(n, s, k) for (n, s), k in
            zip(shapes.items(), jax.random.split(key, len(shapes)))}


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights, ``{"layers": [{leaf: array}, ...], top
    leaves}``, in the types the program serves them in (the router and
    the KDA gates' constants float32)."""
    static = _static(cfg)
    kinds = sizes(cfg)["kinds"]
    keys = jax.random.split(seed_key(seed), len(kinds) + 1)
    out = _draw(keys[-1], static, None, embed_dtype, dtype)
    out["layers"] = [_draw(k, static, kind, embed_dtype, dtype)
                     for kind, k in zip(kinds, keys)]
    return out


def program_names(drawn):
    """The drawn weights by the program's parameter names
    (``layer1_qkv_weight`` ...)."""
    out = {k: v for k, v in drawn.items() if k != "layers"}
    for i, layer in enumerate(drawn["layers"]):
        out.update({f"layer{i}_{k}": v for k, v in layer.items()})
    return out


def to_float32(drawn):
    """What the reference computes on: the same values.  Kept as drawn:
    every product below casts its operands to float32 first, the experts
    one at a time (module doc)."""
    return drawn


# ---------------------------------------------------------------------
# arithmetic of the linear layers, by precision
# ---------------------------------------------------------------------

def _fq(x):
    """Round to fp8 e4m3 with one scale per tensor (max |x| -> 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(x, w, precision):
    """``x @ w`` — x (..., K), w (K, N) — in ``precision``."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fq(x), _fq(w)
    elif precision == "bfloat16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, w, precision=HI)


def lin(x, w, precision):
    """A linear layer held (out, in): ``x @ w.T``."""
    return mm(x, w.T, precision)


def rms(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

def attention(p, h, z, precision):
    T = h.shape[0]
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    q = lin(h, p["q_weight"], precision).reshape(T, Hq, D)
    k = lin(h, p["k_weight"], precision).reshape(T, Hkv, D)
    v = lin(h, p["v_weight"], precision).reshape(T, Hkv, D)
    G = Hq // Hkv                  # query head i reads KV head i // G
    causal = jnp.tril(jnp.ones((T, T), bool))

    def group(xs):                 # one KV head and its G query heads
        qg, kg, vg = xs            # (G, T, D), (T, D), (T, D)
        s = jnp.einsum("gtd,sd->gts", qg, kg, precision=HI) * (D ** -0.5)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, axis=-1), vg,
                          precision=HI)

    a = lax.map(group, (q.reshape(T, Hkv, G, D).transpose(1, 2, 0, 3),
                        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    a = a.transpose(2, 0, 1, 3).reshape(T, Hq * D)   # (Hkv, G, T, D) ->
    if z["gate"]:
        a = a * jax.nn.sigmoid(lin(h, p["gate_weight"], precision))
    return lin(a, p["o_weight"], precision)


def kda(p, h, z, precision, n=None):
    """(the layer's output (T, H·D -> d), the state (H, d_k, d_v) after
    the first ``n`` tokens: all of them where ``n`` is None)."""
    T = h.shape[0]
    H, D, K = z["Hl"], z["Dl"], z["K"]
    x = lin(h, p["qkv_weight"], precision)                   # (T, 3HD)
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x], axis=0)
    cw = p["conv_weight"].astype(jnp.float32)
    c = jax.nn.silu(sum(xp[j:j + T] * cw[:, j] for j in range(K)))
    q, k, v = (t.reshape(T, H, D) for t in jnp.split(c, 3, axis=-1))

    def unit(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) * (D ** -0.5), unit(k)
    a = lin(lin(h, p["a_down_weight"], precision), p["a_up_weight"],
            precision) + p["dt_bias"]
    alpha = jnp.exp(-jnp.exp(p["a_log"])[None, :, None]
                    * jax.nn.softplus(a.reshape(T, H, D)))
    beta = jax.nn.sigmoid(lin(h, p["beta_weight"], precision))
    if z["neg"]:
        beta = 2.0 * beta

    def one(S0, xs):                      # S (H, d_k, d_v)
        qt, kt, vt, at, bt, live = xs
        S = at[:, :, None] * S0                             # Diag(alpha) S
        kS = jnp.einsum("hk,hkv->hv", kt, S, precision=HI)  # k^T S
        S = S + bt[:, None, None] * kt[:, :, None] * (vt - kS)[:, None, :]
        if precision == "bf16_state":
            # (reduce_precision: a convert to bfloat16 and back is
            # removed by the compiler, which may keep excess precision)
            S = lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        # past the n-th token the state stands still (padding)
        return jnp.where(live, S, S0), \
            jnp.einsum("hkv,hk->hv", S, qt, precision=HI)

    live = jnp.arange(T) < (T if n is None else n)
    last, o = lax.scan(one, jnp.zeros((H, D, D), jnp.float32),
                       (q, k, v, alpha, beta, live))
    o = rms(o, p["onorm_gamma"], z["eps"])                  # per head
    g = lin(lin(h, p["g_down_weight"], precision), p["g_up_weight"],
            precision)
    return lin(o.reshape(T, H * D) * jax.nn.sigmoid(g), p["o_weight"],
               precision), last


def gated_ffn(h, wg, wu, wd, precision):
    return lin(jax.nn.silu(lin(h, wg, precision)) * lin(h, wu, precision),
               wd, precision)


def route(p, h, z):
    """(chosen experts (T, k), their weights (T, k)), float32."""
    s = jax.nn.sigmoid(jnp.dot(h, p["router_weight"].T, precision=HI))
    topv, topi = lax.top_k(s, z["top_k"])
    return topi, z["scaling"] * topv / jnp.sum(topv, axis=-1, keepdims=True)


def moe(p, h, z, precision):
    """(the FFN's output, the chosen experts): the shared expert plus
    the held experts' part of the routed sum, an expert at a time."""
    topi, wts = route(p, h, z)
    # coef[t, j]: token t's weight for held expert j (0 if not chosen)
    held = z["first"] + jnp.arange(z["held"])
    coef = jnp.sum(jnp.where(topi[:, :, None] == held[None, None, :],
                             wts[:, :, None], 0.0), axis=1)

    def one(acc, xs):
        wg, wu, wd, c = xs
        y = mm(jax.nn.silu(mm(h, wg, precision)) * mm(h, wu, precision),
               wd, precision)
        return acc + c[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h),
                      (p["experts_gate_weight"], p["experts_up_weight"],
                       p["experts_down_weight"], coef.T))
    if z["shared"]:
        out = out + gated_ffn(h, p["shared_gate_weight"],
                              p["shared_up_weight"],
                              p["shared_down_weight"], precision)
    return out, jnp.sort(topi, axis=-1)


def hidden(w, tokens, z, precision="float32", n=None):
    """tokens (T,) -> (the last block's output (T, d), the chosen
    experts of every layer (L, T, k), sorted, and the kda layers'
    states (one (H, d_k, d_v) a kda layer) after the first ``n``
    tokens)."""
    x = w["tok_embed_weight"].astype(jnp.float32)[tokens]
    chosen, states = [], []
    for kind, p in zip(z["kinds"], w["layers"]):
        h = rms(x, p["norm1_gamma"], z["eps"])
        if kind == "attention":
            x = x + attention(p, h, z, precision)
        else:
            y, last = kda(p, h, z, precision, n)
            states.append(last)
            x = x + y
        h = rms(x, p["norm2_gamma"], z["eps"])
        y, topi = moe(p, h, z, precision)
        chosen.append(topi)
        x = x + y
    return x, jnp.stack(chosen), states


def logits(w, rows, z, precision="float32"):
    return lin(rms(rows, w["final_norm_gamma"], z["eps"]),
               w["head_weight"], precision)


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
    (H, d_k, d_v)}``, a kda layer each — the scan's last state.  (The
    program's pools hold a head's matrix transposed, (d_v, d_k).)"""
    states = _final_states(w, tokens, n, _static(cfg), precision)
    kda_layers = [i for i, kind in enumerate(sizes(cfg)["kinds"])
                  if kind == "kda"]
    return {f"layer{i}_state": s for i, s in zip(kda_layers, states)}
