"""The plain reference for the ``brumby`` family, and its seeded weights.

Brumby-14B-Base as its public ``config.json``, its release note and the
power-retention paper ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239) give it, cut to one stage of a stated pipeline (the
configuration file says which, and lists what the config does not fix
under ``assumed``): pre-norm residual blocks, RMSNorm (``rms_norm_eps``),
no bias on any matrix but the gate's, an untied head; EVERY layer's
mixer is a power-retention layer and its FFN dense.  On the residual
stream ``x`` (T, d), d = ``hidden_size``; H / J = ``num_attention_heads``
/ ``num_key_value_heads`` heads of D = ``head_dim``:

* ``h = RMSNorm(x; g1)``; ``q, k, v = h W_q (H x D), h W_k (J x D),
  h W_v (J x D)``;
* ``q^h, k^j = RMSNorm_D(q^h; g_q), RMSNorm_D(k^j; g_k)``: over each
  head's D lanes, ONE gain of D for all heads, each; then rotated by
  position ``t``: the whole head, pairs ``(i, i + D/2)`` turned by ``t *
  rope_theta^(-2i/D)`` (rotate-half);
* the log-gate, one a token and KV head, float32: ``gamma_t = log
  sigmoid(h_t W_g + b_g)``, ``Gamma_t = sum_{u <= t} gamma_u``;
* THE ATTENTION FORM, query head i on KV head ``j = i // (H/J)``, degree
  p = 2: ``a_ts = (q_t^i . k_s^j / sqrt(D))^p exp(Gamma_t^j -
  Gamma_s^j)`` for ``s <= t``; ``y_t^i = sum_s a_ts v_s^j / (sum_s a_ts
  + eps)``, eps 1e-6;
* ``x' = x + concat_i(y^i) W_o``; ``h2 = RMSNorm(x'; g2)``; ``x'' = x' +
  W_down(silu(W_gate h2) * W_up h2)`` of ``intermediate_size``;
* ``logits = RMSNorm(x_L; g_f) W_head^T``.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
state, no chunking, no batching; the attention form a block of queries
at a time against every key (the weights of a whole prompt do not fit at
once), nothing else regrouped.  It imports nothing of ``mxnet_tpu`` but
the spec class (:func:`spec`).  Weights are HELD as drawn and cast to
float32 where they are multiplied.

What a stream's slot must hold is another computation, used for nothing
else (:func:`final_states`): the RECURRENT form of the same layer, a
float32 ``lax.scan`` token by token over the symmetric square in its
textbook order — ``phi(x) = (x_a x_b (1 if a = b else sqrt 2))_{a <= b}``,
D (D + 1) / 2 = 8,256 rows; ``S_t = e^gamma_t S_(t-1) + phi(k_t) v_t^T``,
``z_t = e^gamma_t z_(t-1) + phi(k_t)``, q and k each scaled by D^-1/4 —
whose last state :func:`pack` then lays out as the program's slot
documents its own (``mxnet_tpu/models/hybrid_lm.py``, mixer
``retention``), and whose last ``z`` :func:`unpack_z` spreads into the
slot's normaliser matrix: departures of LAYOUT only, noted there.

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (head included; the
gate's bias stays float32) in e4m3 with one scale per tensor;
``bfloat16`` multiplies in bfloat16; ``bf16_state`` computes the layer
in its recurrent form with S and z rounded to bfloat16 after every
token.  And it names ONE mechanism left out or changed, each in float32
(:data:`MECHANISMS`) — what a program without it would serve.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what no family changes — the key from a seed, the linear layers'
# arithmetic by precision, the norm, the gated FFN, the program's names
# for the drawn leaves — is the first hybrid reference's, imported
from benchmark.reference.solar_open2 import (  # noqa: F401
    HI, gated_ffn, lin, program_names, rms, seed_key, to_float32)
# rotate-half over the whole head, row t at position t: the family of
# rotary window layers wrote it down first
from benchmark.reference.afmoe import rotate

# forms of the reference that leave one mechanism out (or change it)
MECHANISMS = ("no_gate", "no_division", "no_rotation", "no_qk_norm",
              "degree4")
QUERY_BLOCK = 256       # queries whose weights are formed at once
EPS = 1e-6              # the division's (assumed: the config has no key)


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def sizes(cfg):
    L = int(cfg["num_hidden_layers"])
    Hq, Hkv = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    std = float(cfg.get("initializer_range", 0.02))
    return dict(
        L=L, d=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]), Hq=Hq, Hkv=Hkv,
        D=int(cfg["head_dim"]), w=int(cfg["intermediate_size"]),
        theta=float(cfg["rope_theta"]),
        degree=int(cfg.get("retention_degree", 2)),
        std=std,
        gate_lo=float(cfg.get("gate_forget_min", 5e-4)),
        gate_hi=float(cfg.get("gate_forget_max", 2e-2)),
        L_pub=int(cfg.get("num_hidden_layers_published", L)))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it."""
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    z = sizes(cfg)
    layer = {"mixer": {"kind": "retention", "heads": z["Hq"],
                       "kv_heads": z["Hkv"], "head_dim": z["D"],
                       "degree": z["degree"], "rope_theta": z["theta"],
                       "qk_norm": True},
             "ffn": {"kind": "dense", "width": z["w"]}}
    return HybridSpec(z["V"], z["d"], [layer] * z["L"], norm_eps=z["eps"])


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

def _layer_shapes(z):
    d, w, D = z["d"], z["w"], z["D"]
    hd, kd = z["Hq"] * D, z["Hkv"] * D
    return {"norm1_gamma": (d,), "norm2_gamma": (d,),
            "q_weight": (hd, d), "k_weight": (kd, d), "v_weight": (kd, d),
            "q_norm_gamma": (D,), "k_norm_gamma": (D,),
            "g_weight": (z["Hkv"], d), "g_bias": (z["Hkv"],),
            "o_weight": (d, hd), "ffn_gate_weight": (w, d),
            "ffn_up_weight": (w, d), "ffn_down_weight": (d, w)}


FLOAT32_LEAVES = ("g_bias",)
RESIDUAL_OUT = ("o_weight", "ffn_down_weight")


@functools.partial(jax.jit, static_argnames=("static", "top",
                                             "embed_dtype", "dtype"))
def _draw(key, static, top, embed_dtype, dtype):
    """One program makes the tensors of one layer, or of the top (``top``:
    table, last norm, head), on the device — a layer at a time, so that
    the float32 draws never lie side by side: N(0, std) matrices (the
    projections back into the residual stream scaled by 1/sqrt(2 x
    published depth)), unit gains, and the
    gate's bias such that ``1 - sigmoid(b_g)``, the share a token
    forgets, is log-uniform in ``gate_lo .. gate_hi`` (a memory of 50 to
    2,000 tokens); each rounded to the type it is held in."""
    z = dict(static)
    resid = 1.0 / math.sqrt(2.0 * z["L_pub"])

    def make(name, shape, k):
        if name.endswith("_gamma"):
            x = jnp.ones(shape, jnp.float32)
        elif name == "g_bias":
            f = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(z["gate_lo"]),
                math.log(z["gate_hi"])))
            x = jnp.log1p(-f) - jnp.log(f)            # sigmoid^-1(1 - f)
        else:
            scale = z["std"] * (resid if name in RESIDUAL_OUT else 1.0)
            x = scale * jax.random.normal(k, shape, jnp.float32)
        if name in FLOAT32_LEAVES:
            return x
        return x.astype(embed_dtype if name == "tok_embed_weight"
                        else dtype)

    shapes = {"tok_embed_weight": (z["V"], z["d"]),
              "final_norm_gamma": (z["d"],),
              "head_weight": (z["V"], z["d"])} if top else _layer_shapes(z)
    return {n: make(n, s, k) for (n, s), k in
            zip(shapes.items(), jax.random.split(key, len(shapes)))}


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights, ``{"layers": [{leaf: array}, ...], top
    leaves}``, in the types the program serves them in (the gate's bias
    float32)."""
    static = _static(cfg)
    L = sizes(cfg)["L"]
    keys = jax.random.split(seed_key(seed), L + 1)
    out = _draw(keys[-1], static, True, embed_dtype, dtype)
    out["layers"] = [_draw(k, static, False, embed_dtype, dtype)
                     for k in keys[:L]]
    return out


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

def _arith(precision):
    """The linear layers' arithmetic under ``precision``."""
    return precision if precision in ("fp8", "bfloat16") else "float32"


def qkvg(p, u, z, precision):
    """(q (T, H, D), k, v (T, J, D), gamma (T, J)): normalised, rotated,
    q and k each scaled by D^-1/4; the log-gate float32."""
    T = u.shape[0]
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    ar = _arith(precision)
    q = lin(u, p["q_weight"], ar).reshape(T, Hq, D)
    k = lin(u, p["k_weight"], ar).reshape(T, Hkv, D)
    v = lin(u, p["v_weight"], ar).reshape(T, Hkv, D)
    if precision != "no_qk_norm":
        q = rms(q, p["q_norm_gamma"], z["eps"])
        k = rms(k, p["k_norm_gamma"], z["eps"])
    if precision != "no_rotation":
        q, k = rotate(q, z["theta"]), rotate(k, z["theta"])
    gamma = jax.nn.log_sigmoid(lin(u, p["g_weight"], ar)
                               + p["g_bias"].astype(jnp.float32))
    if precision == "no_gate":
        gamma = jnp.zeros_like(gamma)
    s = float(D) ** -0.25
    return q * s, k * s, v, gamma


def attend(q, k, v, gamma, power, divide):
    """THE ATTENTION FORM.  q (T, H, D), k, v (T, J, D), gamma (T, J) ->
    (T, H·D): a block of queries at a time against every key."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = math.gcd(T, QUERY_BLOCK)
    cum = jnp.cumsum(gamma, axis=0)                      # Gamma (T, J)
    keys = jnp.arange(T)

    def group(xs):                    # one KV head, its G query heads
        qg, kg, vg, cg = xs           # (G, T, D), (T, D), (T, D), (T,)

        def block(i):
            qb = lax.dynamic_slice_in_dim(qg, i * bq, bq, axis=1)
            cb = lax.dynamic_slice_in_dim(cg, i * bq, bq)
            s = jnp.einsum("gtd,sd->gts", qb, kg, precision=HI)
            seen = (i * bq + jnp.arange(bq))[:, None] >= keys[None, :]
            decay = jnp.exp(jnp.where(seen, cb[:, None] - cg[None, :],
                                      -jnp.inf))
            a = s ** power * decay
            num = jnp.einsum("gts,sd->gtd", a, vg, precision=HI)
            if not divide:
                return num
            return num / (jnp.sum(a, axis=-1)[..., None] + EPS)

        out = lax.map(block, jnp.arange(T // bq))        # (nb, G, bq, D)
        return out.transpose(1, 0, 2, 3).reshape(G, T, D)

    y = lax.map(group, (q.reshape(T, Hkv, G, D).transpose(1, 2, 0, 3),
                        k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                        cum.T))                          # (J, G, T, D)
    return y.transpose(2, 0, 1, 3).reshape(T, Hq * D)


def _pairs(D):
    """The symmetric square's textbook order: (a, b, weight), a <= b."""
    ia, ib = np.triu_indices(D)
    return ia, ib, np.where(ia == ib, 1.0, math.sqrt(2.0)).astype(np.float32)


def recur(q, k, v, gamma, n=None, round_state=False):
    """THE RECURRENT FORM, token by token (degree 2): (y (T, H·D), the
    state (J, D (D + 1) / 2, D) and z (J, D (D + 1) / 2) after the first
    ``n`` tokens: all of them where ``n`` is None)."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    ia, ib, wt = _pairs(D)
    phi = lambda x: x[..., ia] * x[..., ib] * wt

    def one(carry, xs):
        S0, z0 = carry
        qt, kt, vt, gt, live = xs     # (J, G, D), (J, D), (J, D), (J,)
        a = jnp.exp(gt)
        pk = phi(kt)                                     # (J, P)
        S = a[:, None, None] * S0 + pk[:, :, None] * vt[:, None, :]
        zz = a[:, None] * z0 + pk
        if round_state:
            # (reduce_precision: a convert to bfloat16 and back is
            # removed by the compiler, which may keep excess precision)
            S, zz = (lax.reduce_precision(t, exponent_bits=8,
                                          mantissa_bits=7) for t in (S, zz))
        pq = phi(qt)                                     # (J, G, P)
        y = jnp.einsum("jgp,jpd->jgd", pq, S, precision=HI) / (
            jnp.einsum("jgp,jp->jg", pq, zz, precision=HI)[..., None] + EPS)
        # past the n-th token the state stands still (padding)
        return (jnp.where(live, S, S0), jnp.where(live, zz, z0)), y

    P = len(ia)
    live = jnp.arange(T) < (T if n is None else n)
    (S, zz), y = lax.scan(
        one, (jnp.zeros((Hkv, P, D), jnp.float32),
              jnp.zeros((Hkv, P), jnp.float32)),
        (q.reshape(T, Hkv, G, D), k, v, gamma, live))
    return y.reshape(T, Hq * D), S, zz


def retention(p, u, z, precision, n=None, states=False):
    """(the layer's output (T, d), the recurrent form's last (state,
    z) or None).  The output is the attention form's — but under
    ``bf16_state``, which has no meaning without a state."""
    q, k, v, gamma = qkvg(p, u, z, precision)
    last = None
    if precision == "bf16_state" or states:
        y_rec, *last = recur(q, k, v, gamma, n,
                             round_state=precision == "bf16_state")
    if precision == "bf16_state":
        y = y_rec
    else:
        y = attend(q, k, v, gamma,
                   power=4 if precision == "degree4" else z["degree"],
                   divide=precision != "no_division")
    return lin(y, p["o_weight"], _arith(precision)), last


def hidden(w, tokens, z, precision="float32", n=None, states=False):
    """tokens (T,) -> (the last block's output (T, d), the layers'
    recurrent (state, z) after the first ``n`` tokens — where asked)."""
    x = w["tok_embed_weight"].astype(jnp.float32)[tokens]
    ar = _arith(precision)
    out = []
    for p in w["layers"]:
        y, last = retention(p, rms(x, p["norm1_gamma"], z["eps"]), z,
                            precision, n, states)
        out.append(last)
        x = x + y
        x = x + gated_ffn(rms(x, p["norm2_gamma"], z["eps"]),
                          p["ffn_gate_weight"], p["ffn_up_weight"],
                          p["ffn_down_weight"], ar)
    return x, out


def logits(w, rows, z, precision="float32"):
    return lin(rms(rows, w["final_norm_gamma"], z["eps"]),
               w["head_weight"], _arith(precision))


def forward(cfg, w, tokens, precision="float32"):
    """Logits (T, V) of one sequence: the whole model, for the tests."""
    z = sizes(cfg)
    h, _ = hidden(w, jnp.asarray(tokens), z, precision)
    return logits(w, h, z, precision)


@functools.partial(jax.jit, static_argnames=("static", "precision",
                                             "n_out"))
def _served_gaps(w, tokens, start, served, static, precision, n_out):
    z = dict(static)
    h, _ = hidden(w, tokens, z, "float32")
    rows = lax.dynamic_slice_in_dim(h, start, n_out, axis=0)
    zf = logits(w, rows, z, "float32")
    best = jnp.max(zf, axis=-1)
    gap_served = best - jnp.take_along_axis(zf, served[:, None], -1)[:, 0]
    none = jnp.zeros((n_out,), bool)      # no expert sets in this family
    if precision == "float32":
        return gap_served, jnp.zeros_like(gap_served), none
    hl, _ = hidden(w, tokens, z, precision)
    rl = lax.dynamic_slice_in_dim(hl, start, n_out, axis=0)
    first = jnp.argmax(logits(w, rl, z, precision), axis=-1)
    gap_low = best - jnp.take_along_axis(zf, first[:, None], -1)[:, 0]
    return gap_served, gap_low, none


def served_gaps(cfg, w, tokens, start, served, precision, n_out):
    """One request, teacher-forced.  ``tokens`` (T,): prompt + served
    tokens, padded; ``start``: index of the position that predicts the
    first served token; ``served`` (n_out,): the served tokens, padded.

    Returns, per served position: the float32 reference's best logit
    minus its logit of the served token; minus its logit of the token
    that ``precision`` puts first there (zeros for float32); and a row of
    False (the runner's count of unstable expert sets: none here)."""
    return _served_gaps(w, tokens, start, served, _static(cfg), precision,
                        n_out)


def pack(S, D):
    """A KV head's state in the textbook order (J, D (D + 1) / 2, D) as
    the program's slot lays it out, (J, (D / 2 + 1) * D, D): row ``delta
    * D + l``, lane ``a`` holds the entry of the pair ``{a, (a + delta) %
    D}`` against value lane ``l`` — under sqrt 2 for 0 < delta < D / 2,
    under 1 for a lane with itself and for the pairs half a head apart,
    which stand TWICE (once from each end), where the textbook order has
    them once under sqrt 2."""
    ia, ib, _ = _pairs(D)
    at = np.zeros((D, D), np.int32)
    at[ia, ib] = at[ib, ia] = np.arange(len(ia))
    a = np.arange(D)
    blocks = []
    for delta in range(D // 2 + 1):
        rows = S[:, at[a, (a + delta) % D], :]            # (J, a, l)
        if delta == D // 2:
            rows = rows / math.sqrt(2.0)
        blocks.append(rows.transpose(0, 2, 1))            # (J, l, a)
    return jnp.concatenate(blocks, axis=1)


def unpack_z(zz, D):
    """A KV head's normaliser in the textbook order (J, D (D + 1) / 2)
    as the program's slot holds it, the matrix ``Z = sum decay c k k^T``
    (J, D, D) with ``q^T Z q = phi(q) . z``: the entry of the pair
    ``{a, b}`` without its sqrt 2, at ``[a, b]`` and at ``[b, a]``."""
    ia, ib, wt = _pairs(D)
    plain = zz / wt
    Z = jnp.zeros((zz.shape[0], D, D), zz.dtype)
    return Z.at[:, ia, ib].set(plain).at[:, ib, ia].set(plain)


@functools.partial(jax.jit, static_argnames=("static", "precision"))
def _final_states(w, tokens, n, static, precision):
    z = dict(static)
    out = [(pack(S, z["D"]), unpack_z(zz, z["D"])) for S, zz in
           hidden(w, tokens, z, precision, n, states=True)[1]]
    if precision == "bf16_state":
        # a slot that holds bfloat16 holds it in its own layout: the
        # doubled pairs' and the normaliser's 1 / sqrt 2 are rounded
        # with the rest
        out = [tuple(lax.reduce_precision(t, exponent_bits=8,
                                          mantissa_bits=7) for t in pair)
               for pair in out]
    return out


def final_states(cfg, w, tokens, n, precision="float32"):
    """What a stream's slot must hold once the first ``n`` of
    ``tokens`` (T,) (padded) have been fed: ``{"layer<i>_state": (J, D,
    (D / 2 + 1) * D), "layer<i>_zsum": (J, D, D)}`` — the recurrent
    scan's last state in the slot's layout (:func:`pack`) and its last
    normaliser as the slot's matrix (:func:`unpack_z`), a head's matrix
    turned as the runner turns the program's."""
    states = _final_states(w, tokens, n, _static(cfg), precision)
    out = {}
    for i, (S, Z) in enumerate(states):
        out[f"layer{i}_state"] = S.transpose(0, 2, 1)
        out[f"layer{i}_zsum"] = Z.transpose(0, 2, 1)
    return out
