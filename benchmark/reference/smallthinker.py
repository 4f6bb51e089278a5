"""The plain reference for the ``smallthinker`` family, and its seeded
weights.

SmallThinker-21BA3B as its public ``config.json`` describes it, cut to
the first layers of the stack (the configuration file says which):
pre-norm residual blocks, RMSNorm (``rms_norm_eps``), no biases, no
qk-norm, an untied head.  For layer ``l`` on the residual stream ``x``
(T, d), d = ``hidden_size``:

* ``r = x W_r^T`` (T, E), float32: the router reads the block's INPUT,
  before the norm and before attention;
* ``h = RMSNorm(x)``; ``q, k, v = h W_q, h W_k, h W_v`` —
  ``num_attention_heads`` query heads over ``num_key_value_heads`` KV
  heads of ``head_dim``, query head ``i`` on KV head ``i // (heads /
  kv_heads)``;
* where ``rope_layout[l]`` is 1, q and k are rotated by their position
  ``t``: the whole head, pairs ``(i, i + D/2)`` turned by ``t *
  rope_theta^(-2i/D)`` (rotate-half); where it is 0 the layer has NO
  positions;
* ``s_ij = q_i . k_j / sqrt(D)`` for ``j <= i`` and, where
  ``sliding_window_layout[l]`` is 1, only for ``i - j <
  sliding_window_size``; ``a = softmax_j(s) v``; ``x' = x + a W_o``;
* ``h2 = RMSNorm(x')``; the ``moe_num_active_primary_experts`` largest
  of ``r`` a token, weights a softmax over those alone (= the softmax
  over all, renormalised over the chosen); ``y = sum_e w_e W_down,e
  (relu(W_gate,e h2) * W_up,e h2)`` over ``moe_num_primary_experts``
  experts of ``moe_ffn_hidden_size``, no shared expert: a loop over
  the experts; ``x_next = x' + y``;
* ``logits = RMSNorm(x_L) W_head^T``.

Departures from the published description and what the config does not
say are in the configuration file (``assumed``, ``departures``); the
initialisation, which a speed and agreement benchmark needs only to be
seeded, is in :func:`_draw`.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
cache, no batching; attention a block of queries at a time (the scores
of a whole long prompt do not fit), nothing else regrouped.  It imports
nothing of ``mxnet_tpu`` but the spec class (:func:`spec`).  Weights are
HELD as drawn and cast to float32 where they are multiplied, an expert
at a time.

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (experts and head
included; the router stays float32, as in the program) in e4m3 with one
scale per tensor; ``bfloat16`` multiplies in bfloat16 (counts unstable
top-k sets).  And it names a MECHANISM left out or got wrong, each in
float32 — what a program without it would serve: ``no_window`` (every
layer sees every key), ``window_minus_one`` (a key too few),
``no_rotation`` (no layer rotates), ``rotate_all`` (the global layers
rotate too), ``router_on_ffn_input`` (the router reads ``h2``),
``silu`` (SiLU for ReLU).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.solar_open2 import (  # noqa: F401
    HI, lin, mm, program_names, rms, seed_key, to_float32)

# mechanisms a control leaves out or gets wrong (module doc)
MECHANISMS = ("no_window", "window_minus_one", "no_rotation", "rotate_all",
              "router_on_ffn_input", "silu")
QUERY_BLOCK = 512


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def sizes(cfg):
    L = int(cfg["num_hidden_layers"])
    d = int(cfg["hidden_size"])
    return dict(
        L=L, d=d, V=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
        Hq=int(cfg["num_attention_heads"]),
        Hkv=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
        # the published lists, of which this cut holds the first L
        rope=tuple(int(v) for v in cfg["rope_layout"][:L]),
        windowed=tuple(int(v) for v in cfg["sliding_window_layout"][:L]),
        W=int(cfg["sliding_window_size"]), theta=float(cfg["rope_theta"]),
        E=int(cfg["moe_num_primary_experts"]),
        top_k=int(cfg["moe_num_active_primary_experts"]),
        w=int(cfg["moe_ffn_hidden_size"]),
        std=float(cfg.get("initializer_range", 0.02)),
        attn_std=float(cfg.get("attention_initializer_range",
                               cfg.get("initializer_range", 0.02))),
        L_pub=int(cfg.get("num_hidden_layers_published", L)))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it.
    Raises at once on a program whose layer list has no window, rotation,
    ReLU gate or early router: it would take the keys for noise and
    serve another model."""
    from mxnet_tpu.models import hybrid_lm

    known = getattr(hybrid_lm, "MIXERS", None)
    if not isinstance(known, dict) or not {"window", "rope_theta"} <= set(
            known.get("attention", ())) or not {"act", "router_input"} \
            <= set(getattr(hybrid_lm, "FFNS", {}).get("moe", ())):
        raise NotImplementedError(
            "this program's HybridSpec has no attention keys 'window', "
            "'rope_theta' and no moe keys 'act', 'router_input': the "
            "smallthinker family cannot be served by it")
    z = sizes(cfg)
    ffn = {"kind": "moe", "experts": z["E"], "top_k": z["top_k"],
           "width": z["w"], "score": "softmax_topk", "act": "relu",
           "router_input": "block"}
    layers = []
    for rope, windowed in zip(z["rope"], z["windowed"]):
        mixer = {"kind": "attention", "heads": z["Hq"],
                 "kv_heads": z["Hkv"], "head_dim": z["D"]}
        if rope:
            mixer["rope_theta"] = z["theta"]
        if windowed:
            mixer["window"] = z["W"]
        layers.append({"mixer": mixer, "ffn": dict(ffn)})
    return hybrid_lm.HybridSpec(z["V"], z["d"], layers, norm_eps=z["eps"])


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

ATTENTION_IN = ("q_weight", "k_weight", "v_weight")
RESIDUAL_OUT = ("o_weight", "experts_down_weight")


def _layer_shapes(z):
    d, w = z["d"], z["w"]
    hd, kd = z["Hq"] * z["D"], z["Hkv"] * z["D"]
    return dict(norm1_gamma=(d,), norm2_gamma=(d,), q_weight=(hd, d),
                k_weight=(kd, d), v_weight=(kd, d), o_weight=(d, hd),
                router_weight=(z["E"], d),
                experts_gate_weight=(z["E"], d, w),
                experts_up_weight=(z["E"], d, w),
                experts_down_weight=(z["E"], w, d))


@functools.partial(jax.jit, static_argnames=("static", "top", "embed_dtype",
                                             "dtype"))
def _draw(key, static, top, embed_dtype, dtype):
    """One program makes the tensors of one layer, or of the top
    (``top``: table, last norm, head), on the device — a layer at a
    time, so that the float32 draws never lie side by side: N(0, std)
    matrices (the attention maps N(0, attn_std); the projections back
    into the residual stream scaled by 1/sqrt(2 x published depth)) and
    unit gains, each rounded to the type it is held in (the router
    float32)."""
    z = dict(static)
    resid = 1.0 / math.sqrt(2.0 * z["L_pub"])

    def make(name, shape, k):
        if name.endswith("_gamma"):
            return jnp.ones(shape, jnp.float32).astype(dtype)
        std = z["attn_std"] if name in ATTENTION_IN + ("o_weight",) \
            else z["std"]
        x = std * (resid if name in RESIDUAL_OUT else 1.0) \
            * jax.random.normal(k, shape, jnp.float32)
        if name == "router_weight":
            return x
        return x.astype(embed_dtype if name == "tok_embed_weight"
                        else dtype)

    shapes = _layer_shapes(z) if not top else {
        "tok_embed_weight": (z["V"], z["d"]),
        "final_norm_gamma": (z["d"],), "head_weight": (z["V"], z["d"])}
    return {n: make(n, s, k) for (n, s), k in
            zip(shapes.items(), jax.random.split(key, len(shapes)))}


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights, ``{"layers": [{leaf: array}, ...], top
    leaves}``, in the types the program serves them in."""
    static = _static(cfg)
    L = sizes(cfg)["L"]
    keys = jax.random.split(seed_key(seed), L + 1)
    out = _draw(keys[-1], static, True, embed_dtype, dtype)
    out["layers"] = [_draw(k, static, False, embed_dtype, dtype)
                     for k in keys[:L]]
    return out


# ---------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------

def rotate(x, theta):
    """x (T, heads, D), row t at position t: pairs (i, i + D/2) of every
    head turned by ``t * theta^(-2i/D)``."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(p, h, z, precision, rope, window):
    """``rope``: rotate q and k; ``window``: keys a query sees (0: every
    key up to its own)."""
    T = h.shape[0]
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    q = lin(h, p["q_weight"], precision).reshape(T, Hq, D)
    k = lin(h, p["k_weight"], precision).reshape(T, Hkv, D)
    v = lin(h, p["v_weight"], precision).reshape(T, Hkv, D)
    if rope:
        q, k = rotate(q, z["theta"]), rotate(k, z["theta"])
    G = Hq // Hkv                  # query head i reads KV head i // G
    bq = math.gcd(T, QUERY_BLOCK)  # queries a block: the scores of one
    j = jnp.arange(T)[None, :]     # block, (G, bq, T), fit

    def group(xs):                 # one KV head and its G query heads
        qg, kg, vg = xs            # (G, T, D), (T, D), (T, D)

        def block(ys):
            qb, i = ys             # (G, bq, D), the block's positions
            s = jnp.einsum("gtd,sd->gts", qb, kg, precision=HI) * D ** -0.5
            see = j <= i[:, None]
            if window:
                see &= i[:, None] - j < window
            return jnp.einsum("gts,sd->gtd", jax.nn.softmax(
                jnp.where(see, s, -jnp.inf), axis=-1), vg, precision=HI)

        out = lax.map(block, (
            qg.reshape(G, T // bq, bq, D).transpose(1, 0, 2, 3),
            jnp.arange(T).reshape(T // bq, bq)))
        return out.transpose(1, 0, 2, 3).reshape(G, T, D)

    a = lax.map(group, (q.reshape(T, Hkv, G, D).transpose(1, 2, 0, 3),
                        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    a = a.transpose(2, 0, 1, 3).reshape(T, Hq * D)   # (Hkv, G, T, D) ->
    return lin(a, p["o_weight"], precision)


def route(p, u, z):
    """(chosen experts (T, k), their weights (T, k)), float32: the k
    largest logits of ``u`` (T, d), a softmax over those alone."""
    r = jnp.dot(u, p["router_weight"].T, precision=HI)
    topv, topi = lax.top_k(r, z["top_k"])
    return topi, jax.nn.softmax(topv, axis=-1)


def moe(p, h2, routed, z, precision, act):
    """(the experts' weighted sum over ``h2``, the chosen experts):
    ``routed`` is what the router reads; an expert at a time."""
    topi, wts = route(p, routed, z)
    # coef[t, e]: token t's weight for expert e (0 if not chosen)
    coef = jnp.sum(jnp.where(
        topi[:, :, None] == jnp.arange(z["E"])[None, None, :],
        wts[:, :, None], 0.0), axis=1)

    def one(acc, xs):
        wg, wu, wd, c = xs
        y = mm(act(mm(h2, wg, precision)) * mm(h2, wu, precision), wd,
               precision)
        return acc + c[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h2),
                      (p["experts_gate_weight"], p["experts_up_weight"],
                       p["experts_down_weight"], coef.T))
    return out, jnp.sort(topi, axis=-1)


def _arithmetic(precision):
    """A mechanism left out is computed in float32."""
    return "float32" if precision in MECHANISMS else precision


def hidden(w, tokens, z, precision="float32"):
    """tokens (T,) -> (the last block's output (T, d), the chosen
    experts of every layer (L, T, k), sorted)."""
    wrong = precision if precision in MECHANISMS else None
    precision = _arithmetic(precision)
    x = w["tok_embed_weight"].astype(jnp.float32)[tokens]
    chosen = []
    for rope, windowed, p in zip(z["rope"], z["windowed"], w["layers"]):
        window = z["W"] * windowed
        if wrong == "no_window":
            window = 0
        elif wrong == "window_minus_one" and window:
            window -= 1
        rope = {"no_rotation": 0, "rotate_all": 1}.get(wrong, rope)
        h = rms(x, p["norm1_gamma"], z["eps"])
        x_in, x = x, x + attention(p, h, z, precision, rope, window)
        h2 = rms(x, p["norm2_gamma"], z["eps"])
        y, topi = moe(p, h2, h2 if wrong == "router_on_ffn_input" else x_in,
                      z, precision,
                      jax.nn.silu if wrong == "silu" else jax.nn.relu)
        chosen.append(topi)
        x = x + y
    return x, jnp.stack(chosen)


def logits(w, rows, z, precision="float32"):
    return lin(rms(rows, w["final_norm_gamma"], z["eps"]), w["head_weight"],
               _arithmetic(precision))


def forward(cfg, w, tokens, precision="float32"):
    """Logits (T, V) of one sequence: the whole model, for the tests."""
    z = sizes(cfg)
    h, _ = hidden(w, jnp.asarray(tokens), z, precision)
    return logits(w, h, z, precision)


@functools.partial(jax.jit, static_argnames=("static", "precision",
                                             "n_out"))
def _served_gaps(w, tokens, start, served, static, precision, n_out):
    z = dict(static)
    h, chosen = hidden(w, tokens, z, "float32")
    rows = lax.dynamic_slice_in_dim(h, start, n_out, axis=0)
    zf = logits(w, rows, z, "float32")
    best = jnp.max(zf, axis=-1)
    gap_served = best - jnp.take_along_axis(zf, served[:, None], -1)[:, 0]
    if precision == "float32":
        return gap_served, jnp.zeros_like(gap_served), \
            jnp.zeros((n_out,), bool)
    hl, chosen_l = hidden(w, tokens, z, precision)
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
    Logits are computed at the ``n_out`` served positions only.

    Returns, per served position: the float32 reference's best logit
    minus its logit of the served token; minus its logit of the token
    that ``precision`` puts first there (zeros for float32); and whether
    any layer's top-k expert SET differs between float32 and
    ``precision`` at that position."""
    return _served_gaps(w, tokens, start, served, _static(cfg), precision,
                        n_out)
