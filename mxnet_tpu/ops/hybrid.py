"""Ops of the hybrid language-model family (``models/hybrid_lm.py``):
RMS normalisation, grouped-query attention over the paged K/V cache,
multi-head latent attention over pages of ONE compressed row a token
(a prefill that up-projects it, a decode step that absorbs the
up-projection), the recurrent mixers over per-stream state SLOTS — the KDA
linear-attention layer (short convolution with a carried tail, the gated
delta rule with a per-channel decay) and the Mamba-2 state-space layer
(the same convolution with a bias, a per-head scalar decay, B and C
shared by a group's heads) — the sequence mixing and the head norm of
compressed convolutional attention (whose attention proper is the
grouped-query ops above, over K/V pages AND a tail in a slot), a router
that is a small float32 network carried from layer to layer, the
residual add under learned scales, and the routed-expert feed-forward
layer that is told which experts it holds.

Two kinds of per-stream state live side by side in a serving program:
K/V PAGES (``kv_cache.value_pool_shape`` — or ``latent_pool_shape``,
one pool a layer — addressed through a block table; attention layers) and SLOTS (``kv_cache.state_pool_shape`` /
``conv_tail_shape``, one row per live stream, row 0 scratch; KDA,
Mamba-2 and retention layers — and a cca layer's tail, beside its pages).  Every op here that touches a pool takes it in and hands it
back, so that a jitted step donates it and updates in place.

Forward only: training this family fits no chip the benchmark has, so no
op here defines a gradient of its kernels (the lax fallbacks
differentiate as any ``jax.numpy`` does).
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, attr_bool, attr_float, attr_int
from .registry import register

HI = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps, groups=1):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` over the last axis, or —
    ``groups`` > 1 — over each of its ``groups`` equal spans (a head's
    lanes), ``gamma`` one span long.  Float32 inside."""
    shape = x.shape
    xf = x.astype(jnp.float32)
    if groups > 1:
        xf = xf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * gamma.astype(jnp.float32)
    return y.reshape(shape).astype(x.dtype)


def _rms_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    g = attr_int(attrs.get("num_groups", 1), 1)
    return [tuple(d), (d[-1] // g,)], [tuple(d)], []


@register("RMSNorm", arg_names=("data", "gamma"), infer_shape=_rms_infer,
          doc="Root-mean-square normalisation over the last axis (no "
              "mean, no bias), float32 inside; attrs: eps (1e-5), "
              "num_groups (1; > 1 normalises each of that many equal "
              "spans of the last axis with one gamma of a span's length)")
def _rms_norm(op_ctx, attrs, inputs, aux):
    x, gamma = inputs
    return [rms_norm(x, gamma, attr_float(attrs.get("eps", 1e-5), 1e-5),
                     attr_int(attrs.get("num_groups", 1), 1))]


@register("GatedRMSNorm", arg_names=("data", "gate", "gamma"),
          infer_shape=lambda attrs, s: (
              [s[0], s[0], None if s[0] is None else (
                  s[0][-1] // attr_int(attrs.get("num_groups", 1), 1),)],
              [s[0]], []),
          doc="A recurrent layer's output norm under its gate.  "
              "gate='sigmoid' (default): RMSNorm(data) * sigmoid(gate), "
              "the gate AFTER the norm (KDA, per head); gate='silu_first': "
              "RMSNorm(data * SiLU(gate)), the gate BEFORE it (Mamba-2, "
              "over all channels).  Other attrs as RMSNorm")
def _gated_rms_norm(op_ctx, attrs, inputs, aux):
    x, gate, gamma = inputs
    eps = attr_float(attrs.get("eps", 1e-5), 1e-5)
    groups = attr_int(attrs.get("num_groups", 1), 1)
    form = str(attrs.get("gate", "sigmoid"))
    xf, gf = x.astype(jnp.float32), gate.astype(jnp.float32)
    if form == "silu_first":
        y = rms_norm(xf * jax.nn.silu(gf), gamma, eps, groups)
    elif form == "sigmoid":
        y = rms_norm(xf, gamma, eps, groups) * jax.nn.sigmoid(gf)
    else:
        raise MXNetError(f"GatedRMSNorm: gate {form!r} is neither "
                         f"'sigmoid' nor 'silu_first'")
    return [y.astype(x.dtype)]


# ---------------------------------------------------------------------------
# Grouped-query attention over the paged cache
# ---------------------------------------------------------------------------

def _repeat_heads(x, H, Hkv):
    """(..., Hkv·D) -> (..., H, D): KV head j serves the query heads
    j·G .. j·G + G - 1."""
    D = x.shape[-1] // Hkv
    x = x.reshape(x.shape[:-1] + (Hkv, D))
    return jnp.repeat(x, H // Hkv, axis=-2)


def _gqa_scaled(attrs, q, H):
    """``q`` such that the attention bodies' own ``head_dim^-1/2`` makes
    the scores ``scale * q.k``: the ``scale`` attr (0 = head_dim^-1/2,
    q as it is) is folded into q, in float32, once."""
    scale = attr_float(attrs.get("scale", 0.0), 0.0)
    if not scale:
        return q
    d = q.shape[-1] // H
    return (q.astype(jnp.float32) * (scale * float(d) ** 0.5)).astype(q.dtype)


def _gqa_heads(attrs, q, k):
    H = attr_int(attrs.get("num_heads", 1), 1)
    Hkv = attr_int(attrs.get("kv_heads", H), H)
    if H % Hkv or q.shape[-1] % H or \
            q.shape[-1] // H != k.shape[-1] // Hkv:
        raise MXNetError(
            f"grouped-query attention: query rows {q.shape[-1]} over "
            f"{H} heads and K/V rows {k.shape[-1]} over {Hkv} heads do "
            f"not share a head size, or {Hkv} does not divide {H}")
    return H, Hkv


def _gqa_infer(attrs, in_shapes):
    q, kp = in_shapes[0], in_shapes[3]
    if q is None or kp is None:
        return in_shapes, None, None
    return in_shapes, [tuple(q), tuple(kp), tuple(kp)], []


_GQA_ARGS = ("query", "key", "value", "k_pool", "v_pool", "block_table",
             "lengths")
_GQA_OUTS = ("output", "new_k_pool", "new_v_pool")
_GQA_ATTRS = (
    "attrs: num_heads, kv_heads, scale (the scores' multiplier; 0 = "
    "head_dim^-1/2), rope_theta (0 = no rotation; else an eighth input, "
    "positions (B, S) int32, and q and k are rotated by them — rotate-half "
    "pairs (i, i + D/2) over the whole head, base rope_theta, or — "
    "rotary_dim R > 0 — pairs (i, i + R/2) over a head's first R lanes "
    "alone — before the "
    "scores and before k goes into the pages), window (0 = every key up "
    "to the query; else the query's own key and the window - 1 before it: "
    "block_table is then the table of a WINDOWED pool, whose entries "
    "behind the window may be the scratch page)")


def _gqa_args(attrs):
    return _GQA_ARGS + (
        ("positions",) if attr_float(attrs.get("rope_theta", 0.0), 0.0)
        else ())


def rotate_half(x, positions, theta, heads, inv_freq=None, rotary_dim=0):
    """Rotary positions: x (B, S, heads·D) with each head's lanes in
    pairs (i, i + D/2), pair i turned by ``positions * theta^(-2i/D)``
    — or by ``positions * inv_freq[i]`` where the (D/2,) frequencies
    come as data (rescaled ones: :func:`yarn_inv_freq`); float32
    inside, x's type out.  ``rotary_dim`` R (0: the whole head): only
    the first R lanes of a head turn, in pairs (i, i + R/2) by
    ``theta^(-2i/R)``; the other D - R carry no position."""
    B, S, HD = x.shape
    D = HD // heads
    R = int(rotary_dim) or D
    if R % 2 or R > D:
        raise MXNetError(f"rotate_half: a span of {R} lanes of a head of "
                         f"{D} is rotated in pairs inside the head")
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, R, 2, dtype=jnp.float32) / R) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv        # (B, S, R/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xf = x.reshape(B, S, heads, D).astype(jnp.float32)
    x1, x2 = xf[..., :R // 2], xf[..., R // 2:R]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin]
                          + ([xf[..., R:]] if R < D else []), -1)
    return out.reshape(B, S, HD).astype(x.dtype)


def _gqa_rotated(attrs, inputs, H, Hkv):
    """(q, k) of the op's inputs, rotated where the op rotates."""
    q, k = inputs[0], inputs[1]
    theta = attr_float(attrs.get("rope_theta", 0.0), 0.0)
    if not theta:
        return q, k
    positions = inputs[7]
    span = attr_int(attrs.get("rotary_dim", 0), 0)
    return rotate_half(q, positions, theta, H, rotary_dim=span), \
        rotate_half(k, positions, theta, Hkv, rotary_dim=span)


@register("GQAPrefillAttention", arg_names=_gqa_args, out_names=_GQA_OUTS,
          infer_shape=_gqa_infer,
          doc="Causal grouped-query attention over a (padded) prompt "
              "that also writes its K/V rows into the paged pools: "
              "query (B, T, H*D), key/value (B, T, Hkv*D), pools "
              "(P, KVB, Hkv*D) -> output (B, T, H*D) + pools.  Query "
              "head i reads KV head i // (H / Hkv).  The write is "
              "ops.attention.paged_prefill_write's: in whole pages "
              "where the shapes allow, the LAST live page then holding "
              "the prompt's padding rows at its slots >= lengths[b] "
              "(no reader may depend on a slot at or past the length); "
              "a windowed pool's table holds 0 for the blocks behind "
              "the window, which are not written.  " + _GQA_ATTRS)
def _gqa_prefill(op_ctx, attrs, inputs, aux):
    from . import pallas_kernels as pk
    from .attention import (_blockwise_attention_partial_lax,
                            normalize_attention_state, paged_prefill_write)

    q, k, v, k_pool, v_pool, table, lengths = inputs[:7]
    H, Hkv = _gqa_heads(attrs, q, k)
    q, k = _gqa_rotated(attrs, inputs, H, Hkv)
    q = _gqa_scaled(attrs, q, H)
    window = attr_int(attrs.get("window", 0), 0)
    B, T, HD = q.shape
    D = HD // H
    if pk.enabled():
        # windowed or not, K and V go in at their own head count: the
        # kernel's index map gives a KV head to its query heads
        def heads_first(x, n):
            return x.reshape(B, T, n, D).transpose(0, 2, 1, 3) \
                .reshape(B * n, T, D)

        # the walk stops at the prompt's last row: a query tile of the
        # bucket's padding alone walks nothing and comes out zeros
        out = pk.flash_mha_window(
            heads_first(q, H), heads_first(k, Hkv), heads_first(v, Hkv),
            window, H, Hkv, lengths=lengths
        ).reshape(B, H, T, D).transpose(0, 2, 1, 3)
    else:       # the lax body, the window (0: none) a mask of its scan
        out = normalize_attention_state(
            *_blockwise_attention_partial_lax(
                q.reshape(B, T, H, D), _repeat_heads(k, H, Hkv),
                _repeat_heads(v, H, Hkv), True, 512, 0, window=window),
            q.dtype)
    pools = paged_prefill_write(k, v, k_pool, v_pool,
                                table.astype(jnp.int32),
                                lengths.astype(jnp.int32))
    return [out.reshape(B, T, HD), pools[0], pools[1]]


@register("GQAPagedDecode", arg_names=_gqa_args, out_names=_GQA_OUTS,
          infer_shape=_gqa_infer,
          doc="One decode step of grouped-query attention over the "
              "paged cache: query (B, 1, H*D), key/value (B, 1, Hkv*D) "
              "of the current token, pools (P, KVB, Hkv*D), lengths "
              "counting the token -> output (B, 1, H*D) + pools.  The "
              "paged kernel with query row i on KV span i // (H / Hkv) "
              "on TPU, a lax gather elsewhere.  " + _GQA_ATTRS)
def _gqa_paged_decode(op_ctx, attrs, inputs, aux):
    from . import pallas_kernels as pk
    from .attention import decode_attention, paged_cache_update

    q, k, v, k_pool, v_pool, table, lengths = inputs[:7]
    H, Hkv = _gqa_heads(attrs, q, k)
    if q.shape[1] != 1:
        raise MXNetError(f"GQAPagedDecode feeds ONE position a step; "
                         f"got query {tuple(q.shape)}")
    q, k = _gqa_rotated(attrs, inputs, H, Hkv)
    q = _gqa_scaled(attrs, q, H)
    window = attr_int(attrs.get("window", 0), 0)
    lengths = lengths.astype(jnp.int32)
    table = table.astype(jnp.int32)
    kp, vp = paged_cache_update(k_pool, v_pool, k, v, table, lengths)
    if pk.paged_enabled(kp.shape[2]):
        out = pk._paged_attention(q, kp, vp, (), table, lengths - 1, H,
                                  kv_heads=Hkv, window=window)
        return [out, kp, vp]
    B, MB = table.shape
    KVB = kp.shape[1]
    kg = _repeat_heads(kp[table].reshape(B, MB * KVB, -1), H, Hkv)
    vg = _repeat_heads(vp[table].reshape(B, MB * KVB, -1), H, Hkv)
    out = decode_attention(q.reshape(B, 1, H, -1), kg, vg, lengths, KVB,
                           window)
    return [out.reshape(q.shape), kp, vp]


# ---------------------------------------------------------------------------
# Multi-head latent attention: one compressed row a token, two paths
# ---------------------------------------------------------------------------
#
# A token leaves ONE row in a layer's cache, shared by every head: the
# normalised latent c (``kv_rank`` values) and the rotated positional key
# k_r (``rope_dim``), padded to whole lane tiles
# (``kv_cache.latent_pool_shape``).  A head's keys and values are
# up-projections of c by ``kv_up`` — rows [every head's k_n | every head's
# v] — and a query is [q_n (nope_dim) | q_r (rope_dim)] a head, laid out
# [every head's q_n | every head's q_r].  Scores: (q_n . k_n + q_r . k_r)
# x scale.
#
# * a PREFILL up-projects (the ``kv_up`` FullyConnected before the op) and
#   attends causally with qk width nope_dim + rope_dim and v width v_dim,
#   k_r one key for all heads (``MLAPrefillAttention``);
# * a DECODE step never up-projects the cache: W_uk is absorbed into the
#   query (``MLAAbsorb``: q~ = q_n W_uk, kv_rank wide), the scores are
#   [q~ | q_r] . [c | k_r] over the cached rows, the value is the row's
#   first kv_rank lanes again, and W_uv is applied to the attended latent
#   (``MLAAbsorb`` value=1) (``MLAPagedDecode``).

def yarn_inv_freq(dim, theta, factor=0.0, orig_len=0.0, beta_fast=32.0,
                  beta_slow=1.0):
    """The (dim/2,) inverse frequencies of a rotary span of ``dim``
    lanes, float32 numpy: ``theta^(-2i/dim)``, rescaled (YaRN,
    arXiv:2309.00071) where ``factor`` > 1: pair i keeps its frequency
    below ``low``, has it divided by ``factor`` above ``high`` and
    blends linearly between, ``low`` / ``high`` the pairs that turn
    ``beta_fast`` / ``beta_slow`` times over ``orig_len`` positions."""
    import math

    import numpy as np

    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not factor or float(factor) == 1.0:
        return f.astype(np.float32)

    def turns(beta):
        return dim * math.log(orig_len / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (f * ((1.0 - ramp) + ramp / float(factor))).astype(np.float32)


def mla_scale(nope_dim, rope_dim, rope_scaling=None):
    """The scores' multiplier: ``(nope_dim + rope_dim)^-1/2``, times
    ``(0.1 mscale_all_dim ln factor + 1)^2`` under rescaled positions."""
    import math

    scale = float(nope_dim + rope_dim) ** -0.5
    rs = rope_scaling or {}
    if rs.get("mscale_all_dim") and float(rs.get("factor", 1.0)) > 1.0:
        scale *= (0.1 * float(rs["mscale_all_dim"])
                  * math.log(float(rs["factor"])) + 1.0) ** 2
    return scale


_MLA_ATTRS = (
    "attrs: num_heads, nope_dim, rope_dim, v_dim, kv_rank, scale (the "
    "scores' multiplier), rope_theta and — rescaled positions — "
    "rope_factor, rope_orig_len, rope_beta_fast, rope_beta_slow "
    "(yarn_inv_freq)")


def _mla_dims(attrs):
    return tuple(attr_int(attrs.get(k, 0), 0) for k in
                 ("num_heads", "nope_dim", "rope_dim", "v_dim", "kv_rank"))


def _mla_rotate(attrs, x, positions, heads, rope):
    inv = yarn_inv_freq(
        rope, attr_float(attrs.get("rope_theta", 10000.0), 10000.0),
        attr_float(attrs.get("rope_factor", 0.0), 0.0),
        attr_float(attrs.get("rope_orig_len", 0.0), 0.0),
        attr_float(attrs.get("rope_beta_fast", 32.0), 32.0),
        attr_float(attrs.get("rope_beta_slow", 1.0), 1.0))
    return rotate_half(x, positions, 0.0, heads, inv_freq=inv)


def _mla_row(c, k_r, lanes):
    """What a token leaves in the cache: [c | k_r rotated | zeros]."""
    row = jnp.concatenate([c, k_r.astype(c.dtype)], axis=-1)
    return jnp.pad(row, ((0, 0), (0, 0), (0, lanes - row.shape[-1])))


def mla_causal(q_n, q_r, k_n, k_r, v, H, scale, block=256):
    """The plain body of a prefill's attention, a block of queries at a
    time: q_n, k_n (B, T, H·n), q_r (B, T, H·r), k_r (B, T, r) — ONE key
    for all heads — v (B, T, H·dv) -> (B, T, H·dv).  Float32 inside."""
    B, T, _ = q_n.shape
    f32 = jnp.float32
    prec = HI if q_n.dtype == jnp.float32 else None
    bq = block if T % block == 0 else T
    k4 = k_n.reshape(B, T, H, -1)
    v4 = v.reshape(B, T, H, -1)
    j = jnp.arange(T)

    def one(xs):
        qn, qr, i = xs                       # (B, bq, H, n), (B, bq, H, r)
        s = jnp.einsum("bqhn,bkhn->bhqk", qn, k4, precision=prec,
                       preferred_element_type=f32) \
            + jnp.einsum("bqhr,bkr->bhqk", qr, k_r, precision=prec,
                         preferred_element_type=f32)
        s = jnp.where(j[None, :] <= i[:, None], s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v4,
                          precision=prec, preferred_element_type=f32)

    nq = T // bq
    out = lax.map(one, (
        jnp.moveaxis(q_n.reshape(B, nq, bq, H, -1), 1, 0),
        jnp.moveaxis(q_r.reshape(B, nq, bq, H, -1), 1, 0),
        j.reshape(nq, bq)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, -1).astype(v.dtype)


def _mla_prefill_infer(attrs, in_shapes):
    q, pool = in_shapes[0], in_shapes[4]
    if q is None or pool is None:
        return in_shapes, None, None
    H, _, _, dv, _ = _mla_dims(attrs)
    return in_shapes, [(q[0], q[1], H * dv), tuple(pool)], []


@register("MLAPrefillAttention",
          arg_names=("query", "key_value", "latent", "rope_key", "pool",
                     "block_table", "lengths", "positions"),
          out_names=("output", "new_pool"), infer_shape=_mla_prefill_infer,
          doc="Causal multi-head latent attention over a (padded) prompt "
              "that also writes the prompt's cache rows: query (B, T, "
              "H*(nope+rope)) = [every head's q_n | every head's q_r]; "
              "key_value (B, T, H*(nope+v)) = [every head's k_n | every "
              "head's v], the latent's up-projection; latent (B, T, "
              "kv_rank), normalised; rope_key (B, T, rope), ONE positional "
              "key for all heads, unrotated; pool (P, KVB, lanes) -> "
              "output (B, T, H*v) + the pool.  q_r and rope_key are "
              "rotated by positions; scores (q_n . k_n + q_r . k_r) x "
              "scale; the row written is [latent | rotated rope_key | "
              "zeros], in whole pages where "
              "ops.attention.latent_prefill_write's rule allows.  "
              + _MLA_ATTRS)
def _mla_prefill(op_ctx, attrs, inputs, aux):
    from . import pallas_kernels as pk
    from .attention import latent_prefill_write

    q, kv, c, k_r, pool, table, lengths, positions = inputs
    H, n, r, dv, _ = _mla_dims(attrs)
    scale = attr_float(attrs.get("scale", 0.0), 0.0)
    q_r = _mla_rotate(attrs, q[..., H * n:], positions, H, r)
    k_r = _mla_rotate(attrs, k_r, positions, 1, r)
    if pk.mla_flash_enabled(H, n, r, dv):
        out = pk.mla_flash(q, q_r, kv, k_r, H, n, dv, scale,
                           lengths=lengths)
    else:
        out = mla_causal(q[..., :H * n], q_r, kv[..., :H * n], k_r,
                         kv[..., H * n:], H, scale)
    pool = latent_prefill_write(
        _mla_row(c, k_r, pool.shape[2]), pool, table.astype(jnp.int32),
        lengths.astype(jnp.int32))
    return [out, pool]


def _mla_absorb_infer(attrs, in_shapes):
    x = in_shapes[0]
    if x is None:
        return in_shapes, None, None
    H, _, _, dv, R = _mla_dims(attrs)
    wide = dv if attr_bool(attrs.get("value", False), False) else R
    return in_shapes, [tuple(x[:2]) + (H * wide,)], []


@register("MLAAbsorb", arg_names=("data", "weight"),
          infer_shape=_mla_absorb_infer,
          doc="The latent's up-projection kv_up, weight (H*(nope+v), "
              "kv_rank) = [every head's W_uk rows | every head's W_uv "
              "rows], applied a head at a time to the OTHER side of the "
              "attention, so that a decode step attends over the cached "
              "latents as they are.  value=0: data (B, S, H*nope), a "
              "head's q_n -> (B, S, H*kv_rank), q_n W_uk; value=1: data "
              "(B, S, H*kv_rank), a head's attended latent -> (B, S, "
              "H*v), W_uv of it.  attrs: num_heads, nope_dim, v_dim, "
              "kv_rank, value")
def _mla_absorb(op_ctx, attrs, inputs, aux):
    x, w = inputs
    H, n, _, dv, R = _mla_dims(attrs)
    B, S, _ = x.shape
    prec = HI if x.dtype == jnp.float32 else None
    if attr_bool(attrs.get("value", False), False):
        out = jnp.einsum("bshr,hvr->bshv", x.reshape(B, S, H, R),
                         w[H * n:].reshape(H, dv, R), precision=prec,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bshn,hnr->bshr", x.reshape(B, S, H, n),
                         w[:H * n].reshape(H, n, R), precision=prec,
                         preferred_element_type=jnp.float32)
    return [out.reshape(B, S, -1).astype(x.dtype)]


def _mla_decode_infer(attrs, in_shapes):
    qa, pool = in_shapes[0], in_shapes[4]
    if qa is None or pool is None:
        return in_shapes, None, None
    return in_shapes, [tuple(qa), tuple(pool)], []


@register("MLAPagedDecode",
          arg_names=("query_latent", "query_rope", "latent", "rope_key",
                     "pool", "block_table", "lengths", "positions"),
          out_names=("output", "new_pool"), infer_shape=_mla_decode_infer,
          doc="One decode step of multi-head latent attention over the "
              "cached rows: query_latent (B, 1, H*kv_rank), a head's q_n "
              "with W_uk absorbed (MLAAbsorb); query_rope (B, 1, H*rope), "
              "unrotated; latent (B, 1, kv_rank) and rope_key (B, 1, "
              "rope) of the current token; pool (P, KVB, lanes); lengths "
              "counting the token -> output (B, 1, H*kv_rank), the "
              "attended latent a head (MLAAbsorb value=1 makes values of "
              "it) + the pool.  The token's row [latent | rotated "
              "rope_key | zeros] is written first; a head's scores are "
              "[q~ | q_r] . row x scale and its value the row's first "
              "kv_rank lanes: the H heads of a stream are the rows of ONE "
              "matmul a chunk of pages (pallas_kernels.mla_paged_decode "
              "on TPU, a lax gather elsewhere).  " + _MLA_ATTRS)
def _mla_paged_decode(op_ctx, attrs, inputs, aux):
    from . import pallas_kernels as pk
    from .attention import latent_cache_update

    qa, q_r, c, k_r, pool, table, lengths, positions = inputs
    H, _, r, _, R = _mla_dims(attrs)
    if qa.shape[1] != 1:
        raise MXNetError(f"MLAPagedDecode feeds ONE position a step; got "
                         f"query {tuple(qa.shape)}")
    scale = attr_float(attrs.get("scale", 0.0), 0.0)
    B = qa.shape[0]
    lanes = pool.shape[2]
    lengths = lengths.astype(jnp.int32)
    table = table.astype(jnp.int32)
    q_r = _mla_rotate(attrs, q_r, positions, H, r)
    k_r = _mla_rotate(attrs, k_r, positions, 1, r)
    pool = latent_cache_update(pool, _mla_row(c, k_r, lanes), table,
                               lengths)
    # a head's whole query against a row: [q~ | q_r | zeros]
    qx = jnp.pad(jnp.concatenate(
        [qa.reshape(B, H, R), q_r.reshape(B, H, r)], axis=-1),
        ((0, 0), (0, 0), (0, lanes - R - r)))
    if pk.mla_paged_enabled(H, lanes, R):
        out = pk.mla_paged_decode(qx, pool, table, lengths - 1, R, scale)
    else:
        MB, KVB = table.shape[1], pool.shape[1]
        rows = pool[table].reshape(B, MB * KVB, lanes)
        prec = HI if qx.dtype == jnp.float32 else None
        s = jnp.einsum("bhw,btw->bht", qx, rows, precision=prec,
                       preferred_element_type=jnp.float32) * scale
        seen = jnp.arange(MB * KVB)[None, None, :] < lengths[:, None, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        # a padded batch row (length 0) sees nothing: zeros, not NaN
        p = jnp.where(seen, p, 0.0)
        out = jnp.einsum("bht,btr->bhr", p.astype(rows.dtype),
                         rows[..., :R], precision=prec,
                         preferred_element_type=jnp.float32)
    return [out.reshape(B, 1, H * R).astype(qa.dtype), pool]


# ---------------------------------------------------------------------------
# ShortConv: depthwise causal convolution with a carried tail
# ---------------------------------------------------------------------------

def short_conv(x, w, left, bias=None):
    """y[t, c] = SiLU(sum_j w[c, j] * xp[t + j, c] (+ bias[c])) with
    ``xp`` = the ``K - 1`` rows of ``left`` before ``x``: tap ``K - 1``
    is on the current token.  x (B, S, C); w (C, K); left (B, K - 1, C);
    bias (C,) or None.  Float32 inside."""
    K = w.shape[1]
    xp = jnp.concatenate([left.astype(jnp.float32),
                          x.astype(jnp.float32)], axis=1)
    S = x.shape[1]
    wf = w.astype(jnp.float32)
    y = sum(xp[:, j:j + S] * wf[:, j] for j in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y), xp


def _write_tails(pool, tail, slots):
    """pool (slots, 8, W) with row b of ``tail`` (B, run) — a stream's
    carried numbers back to back, padded to the slot's whole tiles —
    written to slot ``slots[b]``: one DMA a row where the kernels are on
    (``pallas_hybrid.slot_rows_write``), a scatter elsewhere."""
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    B, run = tail.shape
    rows = jnp.pad(tail.astype(pool.dtype),
                   ((0, 0), (0, pool.shape[1] * pool.shape[2] - run)))
    rows = rows.reshape((B,) + pool.shape[1:])
    if pk.enabled():
        return ph.slot_rows_write(pool, rows, slots)
    return pool.at[slots].set(rows)


def _conv_infer(attrs, in_shapes):
    d, tail = in_shapes[0], in_shapes[2]
    if d is None or tail is None:
        return in_shapes, None, None
    return in_shapes, [tuple(d), tuple(tail)], []


_CONV_ARGS = ("data", "weight", "tail_pool", "slots", "lengths")


@register("ShortConv",
          arg_names=lambda attrs: _CONV_ARGS + (
              ("bias",) if attr_bool(attrs.get("bias", False), False)
              else ()),
          out_names=("output", "new_tail_pool"), infer_shape=_conv_infer,
          doc="Depthwise causal convolution of kernel K with SiLU, its "
              "last K - 1 inputs carried per stream: data (B, S, C), "
              "weight (C, K), tail_pool (slots, 8, W) float32 "
              "(kv_cache.conv_tail_shape: a slot's rows back to back, "
              "in whole tiles), "
              "slots (B,) int32 (0 = scratch).  step=0 (prefill): the "
              "sequence starts from nothing and the K - 1 inputs before "
              "position lengths[b] are written to the slot; step=1 "
              "(decode, S = 1): the slot's tail precedes the token and "
              "is shifted by it.  bias=1: a sixth input, bias (C,), "
              "added before the SiLU.  -> output (B, S, C), the pool")
def _short_conv(op_ctx, attrs, inputs, aux):
    x, w, pool, slots, lengths = inputs[:5]
    bias = inputs[5] if len(inputs) > 5 else None
    step = attr_bool(attrs.get("step", False), False)
    K = w.shape[1]
    slots = slots.astype(jnp.int32)
    B, S, C = x.shape
    run = (K - 1) * C               # a slot's numbers, then padding
    if step:
        left = pool[slots].reshape(B, -1)[:, :run].reshape(B, K - 1, C)
        y, xp = short_conv(x, w, left, bias)
        tail = xp[:, 1:]
    else:
        y, xp = short_conv(x, w, jnp.zeros((B, K - 1, C), jnp.float32),
                           bias)
        n = lengths.astype(jnp.int32)
        tail = jax.vmap(lambda row, at: lax.dynamic_slice_in_dim(
            row, at, K - 1, axis=0))(xp, n)
    return [y.astype(x.dtype),
            _write_tails(pool, tail.reshape(B, run), slots)]


# ---------------------------------------------------------------------------
# KDA: the gated delta rule with a per-channel decay
# ---------------------------------------------------------------------------

def kda_gates(a_raw, b_raw, a_log, dt_bias, H, neg_eigval):
    """(alpha, beta, g): the per-channel decay ``alpha = exp(g)`` in
    (0, 1] with its logarithm ``g = -exp(A_h) * softplus(a_raw +
    dt_bias)`` as computed (the chunk kernels want g itself: an alpha
    that underflowed to 0 has no logarithm), both shaped (..., H, D),
    and the step ``sigmoid(b_raw)`` (doubled where negative eigenvalues
    are allowed: (0, 2)), shaped (..., H).  Float32."""
    a = a_raw.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    a = a.reshape(a.shape[:-1] + (H, a.shape[-1] // H))
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * jax.nn.softplus(a)
    beta = jax.nn.sigmoid(b_raw.astype(jnp.float32))
    return jnp.exp(g), (2.0 * beta if neg_eigval else beta), g


def kda_qkv(c, H):
    """The conv's output (..., 3·H·D) as q, k, v (..., H, D) float32:
    q and k L2-normalised per head, q scaled by D^-1/2."""
    D = c.shape[-1] // (3 * H)
    q, k, v = (t.reshape(t.shape[:-1] + (H, D)).astype(jnp.float32)
               for t in jnp.split(c, 3, axis=-1))

    def unit(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * (float(D) ** -0.5), unit(k), v


def kda_scan(q, k, v, alpha, beta, state):
    """The recurrence token by token (``lax.scan``), from ``state``.
    q, k, v, alpha (B, T, H, D), beta (B, T, H); state (B, H, D, D)
    float32, head states transposed (``pallas_hybrid``'s module doc).
    -> (o (B, T, H, D), the last state)."""
    def one(st, xs):
        qt, kt, vt, at, bt = xs                      # (B, H, D) / (B, H)
        st = st * at[:, :, None, :]
        u = jnp.sum(st * kt[:, :, None, :], axis=-1)
        st = st + (bt[..., None] * (vt - u))[..., None] * kt[:, :, None, :]
        return st, jnp.sum(st * qt[:, :, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta))
    state, o = lax.scan(one, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def _kda_infer(attrs, in_shapes):
    c, pool = in_shapes[0], in_shapes[5]
    if c is None or pool is None:
        return in_shapes, None, None
    return in_shapes, [(c[0], c[1], c[2] // 3), tuple(pool)], []


_KDA_ARGS = ("qkv", "decay", "beta", "a_log", "dt_bias", "state_pool",
             "slots", "lengths")
_KDA_OUTS = ("output", "new_state_pool")
_KDA_DOC = (
    "qkv (B, S, 3*H*D): the short convolution's output; decay "
    "(B, S, H*D) and beta (B, S, H): the raw gate projections; a_log "
    "(H,), dt_bias (H*D,); state_pool (slots, H, D, D) float32, a "
    "head's state transposed; slots (B,) int32 (0 = scratch) -> output "
    "(B, S, H*D) + the pool.  S_t = (I - beta k k^T) Diag(alpha) "
    "S_{t-1} + beta k v^T, o_t = S_t^T q_t, q and k L2-normalised, "
    "alpha = exp(-exp(a_log) softplus(decay + dt_bias)) per channel, "
    "beta = sigmoid (x 2 with neg_eigval).  attrs: num_heads, "
    "neg_eigval")


def _kda_inputs(attrs, inputs):
    c, a_raw, b_raw, a_log, dt_bias, pool, slots, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    neg = attr_bool(attrs.get("neg_eigval", False), False)
    q, k, v = kda_qkv(c, H)
    alpha, beta, g = kda_gates(a_raw, b_raw, a_log, dt_bias, H, neg)
    return (q, k, v, alpha, beta, g, pool, slots.astype(jnp.int32),
            lengths.astype(jnp.int32), c)


@register("KDAChunk", arg_names=_KDA_ARGS, out_names=_KDA_OUTS,
          infer_shape=_kda_infer,
          doc="KDA over a (padded) prompt from the zero state; the state "
              "after position lengths[b] - 1 is written to the slot.  The "
              "recurrence below token by token (lax.scan), or on TPU its "
              "chunk (WY) form in matrix products "
              "(pallas_hybrid.kda_chunk): the same float32 state and "
              "outputs.  " + _KDA_DOC)
def _kda_chunk(op_ctx, attrs, inputs, aux):
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    q, k, v, alpha, beta, g, pool, slots, n, c = _kda_inputs(attrs, inputs)
    B, T, H, D = q.shape
    # a padded position leaves the state as it is: decay 1, step 0
    live = jnp.arange(T)[None, :] < n[:, None]
    beta = jnp.where(live[..., None], beta, 0.0)
    if pk.enabled():
        # the chunk form of the same recurrence, from the conv's output
        # as it is and the log-decay
        g = jnp.where(live[..., None, None], g, 0.0)
        o, last = ph.kda_chunk(c, g.reshape(B, T, H * D), beta)
    else:
        alpha = jnp.where(live[..., None, None], alpha, 1.0)
        o, last = kda_scan(q, k, v, alpha, beta,
                           jnp.zeros((B, H, D, D), jnp.float32))
    return [o.reshape(B, T, H * D).astype(c.dtype),
            pool.at[slots].set(last.astype(pool.dtype))]


@register("KDAStep", arg_names=_KDA_ARGS, out_names=_KDA_OUTS,
          infer_shape=_kda_infer,
          doc="KDA for ONE token per stream against the slot's state, "
              "updated in place (S = 1; a padded row sits on slot 0).  "
              + _KDA_DOC)
def _kda_step(op_ctx, attrs, inputs, aux):
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    q, k, v, alpha, beta, _, pool, slots, _, c = _kda_inputs(attrs, inputs)
    B, S, H, D = q.shape
    if S != 1:
        raise MXNetError(f"KDAStep feeds ONE position a step; got qkv "
                         f"{tuple(c.shape)}")
    if pk.enabled():
        o, pool = ph.kda_step(
            q[:, 0], k[:, 0], alpha[:, 0], v[:, 0],
            jnp.broadcast_to(beta[:, 0, :, None], (B, H, D)),
            pool.astype(jnp.float32), slots)
        o = o[:, None]
    else:
        o, st = kda_scan(q, k, v, alpha, beta,
                         pool[slots].astype(jnp.float32))
        pool = pool.at[slots].set(st.astype(pool.dtype))
    return [o.reshape(B, 1, H * D).astype(c.dtype), pool]


# ---------------------------------------------------------------------------
# Mamba-2: a state-space layer with a per-head scalar decay
# ---------------------------------------------------------------------------

def mamba2_split(xbc, H, N):
    """The conv's output (..., H·P + 2·N) as x (..., H, P) and B, C
    (..., N), which every head shares (one group); in xbc's type."""
    di = xbc.shape[-1] - 2 * N
    if di <= 0 or di % H:
        raise MXNetError(
            f"Mamba-2: {xbc.shape[-1]} channels are not {H} heads of x "
            f"and 2 x {N} (x | B | C)")
    x = xbc[..., :di].reshape(xbc.shape[:-1] + (H, di // H))
    return x, xbc[..., di:di + N], xbc[..., di + N:]


def mamba2_gates(dt_raw, a_log, dt_bias):
    """(Delta, log a), float32, shaped (..., H): the step ``Delta =
    softplus(dt_raw + dt_bias)`` and the decay's logarithm ``-Delta *
    exp(a_log)`` <= 0 (``a = exp`` of it, a number a head and token)."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    return dt, -dt * jnp.exp(a_log.astype(jnp.float32))


def mamba2_chunked(dx, bm, cm, la):
    """The recurrence ``S_t = a_t S_{t-1} + dx_t B_t^T``, ``y_t = S_t
    C_t`` over a prompt from the zero state, regrouped in chunks of
    ``Q`` tokens (the SSD form): with l = the running sum of log a
    inside a chunk, ``Y = ((C B^T) . exp(l_i - l_j)[i >= j]) dX +
    exp(l_i) C_i S_prev``, a chunk's own state ``sum_j exp(l_Q - l_j)
    dx_j B_j^T``, carried on with ``exp(l_Q)``.  Every exponent is a sum
    of log-decays over a span of tokens, <= 0.

    dx (B, T, H, P) = Delta . x and bm, cm (B, T, N) in the model's
    type (the products run in it, sums float32); la (B, T, H) float32.
    -> (y (B, T, H, P) float32, the last state (B, H, P, N) float32)."""
    from .pallas_hybrid import MAMBA2_CHUNK as Q   # one chunk length

    B, T, H, P = dx.shape
    N = bm.shape[-1]
    pad = -T % Q
    if pad:       # a = 1, dx = 0: the state stands still
        dx = jnp.pad(dx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bm, cm, la = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                      for t in (bm, cm, la))
    nc = (T + pad) // Q
    f32 = jnp.float32
    prec = HI if dx.dtype == jnp.float32 else None
    # chunks first: (nc, B, Q, ...)
    dxc = jnp.moveaxis(dx.reshape(B, nc, Q, H, P), 1, 0)
    bc, cc = (jnp.moveaxis(t.reshape(B, nc, Q, N), 1, 0) for t in (bm, cm))
    cum = jnp.moveaxis(jnp.cumsum(la.reshape(B, nc, Q, H), axis=2), 1, 0)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk(S, xs):
        dxq, bq, cq, l = xs           # (B, Q, H, P), (B, Q, N), (B, Q, H)
        lh = jnp.moveaxis(l, 2, 1)                        # (B, H, Q)
        diff = lh[:, :, :, None] - lh[:, :, None, :]      # l_i - l_j
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        cb = jnp.einsum("bin,bjn->bij", cq, bq, precision=prec,
                        preferred_element_type=f32)       # all heads'
        y = jnp.einsum("bhij,bjhp->bihp",
                       (cb[:, None] * decay).astype(dxq.dtype), dxq,
                       precision=prec, preferred_element_type=f32)
        y = y + jnp.exp(l)[..., None] * jnp.einsum(
            "bin,bhpn->bihp", cq, S.astype(cq.dtype), precision=prec,
            preferred_element_type=f32)
        tot = l[:, -1]                                    # (B, H)
        wb = (jnp.exp(tot[:, None] - l)[..., None]
              * bq.astype(f32)[:, :, None, :]).astype(bq.dtype)
        S = jnp.exp(tot)[:, :, None, None] * S + jnp.einsum(
            "bjhp,bjhn->bhpn", dxq, wb, precision=prec,
            preferred_element_type=f32)
        return S, y

    last, y = lax.scan(chunk, jnp.zeros((B, H, P, N), f32),
                       (dxc, bc, cc, cum))
    return jnp.moveaxis(y, 0, 1).reshape(B, T + pad, H, P)[:, :T], last


def _mamba2_infer(attrs, in_shapes):
    c, pool = in_shapes[0], in_shapes[5]
    if c is None or pool is None:
        return in_shapes, None, None
    return in_shapes, [(c[0], c[1], pool[1] * pool[2]), tuple(pool)], []


_MAMBA2_ARGS = ("xbc", "dt", "a_log", "dt_bias", "d_skip", "state_pool",
                "slots", "lengths")
_MAMBA2_DOC = (
    "xbc (B, S, H*P + 2*N): the short convolution's output, x | B | C; "
    "dt (B, S, H): the raw step projection; a_log, dt_bias, d_skip "
    "(H,); state_pool (slots, H, P, N) float32; slots (B,) int32 (0 = "
    "scratch) -> output (B, S, H*P) + the pool.  Delta = softplus(dt + "
    "dt_bias), a = exp(-Delta exp(a_log)) a head; S_t = a_t S_{t-1} + "
    "Delta_t x_t B_t^T, y_t = S_t C_t + d_skip x_t; B and C serve "
    "every head (one group).  The state and every sum float32, the "
    "products in xbc's type.  attrs: num_heads, d_state")


def _mamba2_inputs(attrs, inputs):
    xbc, dt_raw, a_log, dt_bias, d_skip, pool, slots, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    N = attr_int(attrs.get("d_state", 1), 1)
    x, bm, cm = mamba2_split(xbc, H, N)
    if tuple(pool.shape[1:]) != (H, x.shape[-1], N):
        raise MXNetError(
            f"Mamba-2: a slot of the state pool {tuple(pool.shape)} is "
            f"not ({H}, {x.shape[-1]}, {N}) (heads, head_dim, d_state)")
    dt, la = mamba2_gates(dt_raw, a_log, dt_bias)
    skip = d_skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return (x, bm, cm, dt, la, skip, pool, slots.astype(jnp.int32),
            lengths.astype(jnp.int32))


@register("Mamba2Chunk", arg_names=_MAMBA2_ARGS, out_names=_KDA_OUTS,
          infer_shape=_mamba2_infer,
          doc="Mamba-2 over a (padded) prompt from the zero state, in "
              "the chunk (SSD) form of the recurrence — matrix products "
              "over chunks of tokens (mamba2_chunked; on TPU the kernel "
              "pallas_hybrid.mamba2_chunk); the state after position "
              "lengths[b] - 1 is written to the slot.  " + _MAMBA2_DOC)
def _mamba2_chunk(op_ctx, attrs, inputs, aux):
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    x, bm, cm, dt, la, skip, pool, slots, n = _mamba2_inputs(attrs, inputs)
    B, T, H, P = x.shape
    # a padded position leaves the state as it is: a = 1, dx = 0
    live = (jnp.arange(T)[None, :] < n[:, None])[..., None]
    la = jnp.where(live, la, 0.0)
    dx = (jnp.where(live, dt, 0.0)[..., None]
          * x.astype(jnp.float32)).astype(x.dtype)
    if pk.enabled():
        y, last = ph.mamba2_chunk(dx.reshape(B, T, H * P), inputs[0], la)
        y = y.reshape(B, T, H, P)
    else:
        y, last = mamba2_chunked(dx, bm, cm, la)
    y = (y.astype(jnp.float32) + skip).reshape(B, T, H * P)
    return [y.astype(inputs[0].dtype),
            pool.at[slots].set(last.astype(pool.dtype))]


@register("Mamba2Step", arg_names=_MAMBA2_ARGS, out_names=_KDA_OUTS,
          infer_shape=_mamba2_infer,
          doc="Mamba-2 for ONE token per stream against the slot's "
              "state, updated in place (S = 1; a padded row sits on slot "
              "0).  " + _MAMBA2_DOC)
def _mamba2_step(op_ctx, attrs, inputs, aux):
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    x, bm, cm, dt, la, skip, pool, slots, _ = _mamba2_inputs(attrs, inputs)
    B, S, H, P = x.shape
    if S != 1:
        raise MXNetError(f"Mamba2Step feeds ONE position a step; got xbc "
                         f"{tuple(inputs[0].shape)}")
    f32 = jnp.float32
    # the products in the model's type, as the chunk form has them
    dx = (dt[..., None] * x.astype(f32)).astype(x.dtype).astype(f32)[:, 0]
    a = jnp.exp(la[:, 0])
    b_row, c_row = bm[:, 0].astype(f32), cm[:, 0].astype(f32)   # (B, N)
    if pk.enabled():
        y, pool = ph.mamba2_step(dx, a, b_row, c_row, pool.astype(f32),
                                 slots)
    else:
        st = pool[slots].astype(f32) * a[:, :, None, None] \
            + dx[..., None] * b_row[:, None, None, :]
        y = jnp.sum(st * c_row[:, None, None, :], axis=-1)
        pool = pool.at[slots].set(st.astype(pool.dtype))
    y = (y[:, None] + skip).reshape(B, 1, H * P)
    return [y.astype(inputs[0].dtype), pool]


# ---------------------------------------------------------------------------
# Power retention: gated degree-2 linear attention over grouped KV heads
# ---------------------------------------------------------------------------

RETENTION_EPS = 1e-6    # added to a query's summed weights


def retention_rows(D):
    """Rows of a KV head's packed state: ``(D / 2 + 1) * D`` — 8,320 at
    D = 128, where the symmetric square of D lanes needs D (D + 1) / 2 =
    8,256 and the Kronecker form would take D^2 = 16,384."""
    if D % 2:
        raise MXNetError(f"power retention: head_dim {D} is packed in "
                         f"pairs of lanes half a head apart; it is odd")
    return (D // 2 + 1) * D


def retention_weights(D):
    """(D / 2 + 1,) float32: what multiplies block ``delta`` of
    :func:`retention_phi` — 1 where the block holds every pair twice or
    a lane with itself (``delta`` D / 2 and 0), sqrt 2 between."""
    w = jnp.full((D // 2 + 1,), 2.0 ** 0.5, jnp.float32)
    return w.at[0].set(1.0).at[D // 2].set(1.0)


def retention_phi(x):
    """The symmetric-power expansion of degree 2, PACKED BY LANE ROLLS:
    x (..., D) -> (..., D / 2 + 1, D) float32 with ``phi[delta, a] =
    w[delta] * x[a] * x[(a + delta) % D]``.  Every unordered pair of
    lanes less than half a head apart stands once under sqrt 2, a lane
    with itself once under 1 and the pairs exactly half a head apart
    twice under 1, so ``sum(phi(x) * phi(y)) = (x . y)^2`` over all
    (D / 2 + 1) * D entries, the D / 2 doubled ones included: the block
    ``delta`` is one roll of the lanes and one product, which is what a
    vector unit does well, for 64 entries more than the least."""
    D = x.shape[-1]
    xf = x.astype(jnp.float32)
    rolled = jnp.stack([jnp.roll(xf, -d, axis=-1)
                        for d in range(D // 2 + 1)], axis=-2)
    return xf[..., None, :] * rolled * retention_weights(D)[:, None]


def retention_chunked(q, k, v, la):
    """Power retention over a prompt from the zero state, regrouped in
    chunks of ``Q`` tokens.  With l the running sum of the log-gate
    inside a chunk and c = D^-1/2: inside the chunk the attention form,
    ``A = (c Q K^T)^2 . exp(l_i - l_j)[i >= j]``; from the chunks before
    it the state, ``exp(l_i) phi(q_i) S_prev`` over ``exp(l_i) q_i^T
    Z_prev q_i``; ``y = (A V + ...) / (rowsum A + ... + eps)``; a
    chunk's own state ``sum_j exp(l_Q - l_j) phi(k_j) v_j^T`` and
    normaliser ``sum_j exp(l_Q - l_j) c k_j k_j^T``, carried on with
    ``exp(l_Q)``.  Every exponent is a sum of log-gates over a span of
    tokens, <= 0.

    q (B, T, Hkv, G, D), k, v (B, T, Hkv, D) in the model's type (the
    products run in it, sums float32), dead positions' k and v zero; la
    (B, T, Hkv) float32, 0 at dead positions -> (y (B, T, Hkv, G, D)
    float32, the last state (B, Hkv, R, D) float32 — row delta * D + l,
    lane a: the packed block delta TRANSPOSED, value lane l by key lane
    a — and the last normaliser (B, Hkv, D, D) float32)."""
    from .pallas_hybrid import RETENTION_CHUNK as Q

    B, T, Hkv, G, D = q.shape
    R = retention_rows(D)
    c = float(D) ** -0.5
    pad = -T % Q
    if pad:        # gate 1, k = v = 0: the state stands still
        q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
        k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                for t in (k, v))
        la = jnp.pad(la, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // Q
    f32, mm = jnp.float32, q.dtype
    prec = HI if mm == jnp.float32 else None
    qc = jnp.moveaxis(q.reshape(B, nc, Q, Hkv, G, D), 1, 0)
    kc, vc = (jnp.moveaxis(t.reshape(B, nc, Q, Hkv, D), 1, 0)
              for t in (k, v))
    cum = jnp.moveaxis(jnp.cumsum(la.reshape(B, nc, Q, Hkv), axis=2), 1, 0)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk(carry, xs):
        S, Z = carry                  # (B, Hkv, R, D), (B, Hkv, D, D)
        qq, kq, vq, l = xs
        lh = jnp.moveaxis(l, 2, 1)                        # (B, Hkv, Q)
        decay = jnp.exp(jnp.where(
            causal, lh[..., :, None] - lh[..., None, :], -jnp.inf))
        s = c * jnp.einsum("bijgd,bsjd->bjgis", qq, kq, precision=prec,
                           preferred_element_type=f32)
        a = s * s * decay[:, :, None]
        num = jnp.einsum("bjgis,bsjd->bijgd", a.astype(mm), vq,
                         precision=prec, preferred_element_type=f32)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)     # (B, Q, Hkv, G)
        # what the chunks before left: phi(q) S and q^T Z q, decayed
        qf = qq.astype(f32)
        pq = (c * retention_phi(qf)).astype(mm)
        St = S.reshape(B, Hkv, R // D, D, D).astype(mm)   # [delta, l, a]
        el = jnp.exp(l)[..., None]                        # (B, Q, Hkv, 1)
        num = num + el[..., None] * jnp.einsum(
            "bijgra,bjrla->bijgl", pq, St, precision=prec,
            preferred_element_type=f32)
        den = den + el * c * jnp.einsum(
            "bijgd,bjde,bijge->bijg", qf, Z, qf, precision=HI)
        tot = l[:, -1]                                    # (B, Hkv)
        wk = jnp.exp(tot[:, None] - l)[..., None]         # (B, Q, Hkv, 1)
        kf = kq.astype(f32)
        pk_ = (c * retention_phi(kf)).astype(mm)          # (B,Q,Hkv,r,D)
        vd = (wk * vq.astype(f32)).astype(mm)
        e = jnp.exp(tot)[:, :, None, None]
        S = e * S + jnp.einsum(
            "bsjl,bsjra->bjrla", vd, pk_, precision=prec,
            preferred_element_type=f32).reshape(B, Hkv, R, D)
        Z = e * Z + c * jnp.einsum("bsjd,bsje->bjde", wk * kf, kf,
                                   precision=HI)
        return (S, Z), num / (den[..., None] + RETENTION_EPS)

    (S, Z), y = lax.scan(
        chunk, (jnp.zeros((B, Hkv, R, D), f32),
                jnp.zeros((B, Hkv, D, D), f32)), (qc, kc, vc, cum))
    y = jnp.moveaxis(y, 0, 1).reshape(B, T + pad, Hkv, G, D)
    return y[:, :T], S, Z


def retention_step(q, k, v, a, S, Z):
    """One token of the recurrence, float32 throughout: q (B, Hkv, G,
    D), k, v (B, Hkv, D), a (B, Hkv) the gate; S (B, Hkv, R, D) and Z
    (B, Hkv, D, D) as :func:`retention_chunked` leaves them -> (y (B,
    Hkv, G, D), S, Z).  ``S <- a S + v (x) phi(c^1/2 k)``, ``Z <- a Z +
    c k k^T``, ``y = phi(c^1/2 q) S / (c q^T Z q + eps)``."""
    B, Hkv, G, D = q.shape
    c = float(D) ** -0.5
    pk_ = c * retention_phi(k)                            # (B, Hkv, r, D)
    St = S.reshape(B, Hkv, -1, D, D)                      # [delta, l, a]
    St = a[:, :, None, None, None] * St \
        + v[:, :, None, :, None] * pk_[:, :, :, None, :]
    Z = a[:, :, None, None] * Z + c * k[..., :, None] * k[..., None, :]
    num = jnp.einsum("bjgra,bjrla->bjgl", c * retention_phi(q), St,
                     precision=HI)
    den = c * jnp.einsum("bjgd,bjde,bjge->bjg", q, Z, q, precision=HI)
    return num / (den[..., None] + RETENTION_EPS), St.reshape(S.shape), Z


_RETENTION_ARGS = ("query", "key", "value", "gate", "gate_bias",
                   "state_pool", "norm_pool", "slots", "lengths",
                   "positions")


def _retention_infer(attrs, in_shapes):
    q, pool, norm = in_shapes[0], in_shapes[5], in_shapes[6]
    if q is None or pool is None or norm is None:
        return in_shapes, None, None
    return in_shapes, [tuple(q), tuple(pool), tuple(norm)], []


_RETENTION_OUTS = ("output", "new_state_pool", "new_norm_pool")
_RETENTION_DOC = (
    "query (B, S, H*D), key, value (B, S, Hkv*D): q and k already "
    "normalised where the model normalises them, rotated in here by "
    "positions (B, S) under rope_theta (rotate-half); gate (B, S, "
    "Hkv): the raw gate projection, gamma = log sigmoid(gate + "
    "gate_bias) in float32 (gate_bias (Hkv,) float32); state_pool "
    "(slots, Hkv, (D/2 + 1) * D, D) float32, a KV "
    "head's packed symmetric state, block delta transposed "
    "(retention_phi, retention_chunked); norm_pool (slots, Hkv, D, D) "
    "float32: Z = sum of the decayed c k k^T, whose q^T Z q is the sum "
    "of a query's weights; slots (B,) int32 (0 = scratch) -> output "
    "(B, S, H*D) + both pools.  Query head i reads KV head i // "
    "(H / Hkv).  With c = D^-1/2: a_ts = (c q_t.k_s)^2 exp(Gamma_t - "
    "Gamma_s), y_t = sum_s a_ts v_s / (sum_s a_ts + eps); as a "
    "recurrence S_t = e^gamma_t S_(t-1) + phi(k_t) v_t^T, y_t = "
    "phi(q_t)^T S_t / (q_t^T Z_t q_t + eps), eps = RETENTION_EPS.  The "
    "state and every sum float32.  attrs: num_heads, kv_heads, "
    "rope_theta")


def _retention_inputs(attrs, inputs):
    """(q (B, S, Hkv, G, D), k, v (B, S, Hkv, D) rotated, in the model's
    type; gamma (B, S, Hkv) float32; both pools; slots; lengths)."""
    q, k, v, g, bias, pool, norm, slots, lengths, positions = inputs
    H, Hkv = _gqa_heads(attrs, q, k)
    theta = attr_float(attrs["rope_theta"], 0.0)
    q = rotate_half(q, positions, theta, H)
    k = rotate_half(k, positions, theta, Hkv)
    B, S, _ = q.shape
    D = q.shape[-1] // H
    if tuple(pool.shape[1:]) != (Hkv, retention_rows(D), D) \
            or tuple(norm.shape[1:]) != (Hkv, D, D):
        raise MXNetError(
            f"power retention: a slot of the state pool "
            f"{tuple(pool.shape)} is not ({Hkv}, {retention_rows(D)}, "
            f"{D}) (KV heads, packed rows, head_dim), or one of the "
            f"normaliser's {tuple(norm.shape)} not ({Hkv}, {D}, {D})")
    gamma = jax.nn.log_sigmoid(g.astype(jnp.float32)
                               + bias.astype(jnp.float32))
    return (q.reshape(B, S, Hkv, H // Hkv, D), k.reshape(B, S, Hkv, D),
            v.reshape(B, S, Hkv, D), gamma, pool, norm,
            slots.astype(jnp.int32), lengths.astype(jnp.int32))


@register("RetentionChunk", arg_names=_RETENTION_ARGS,
          out_names=_RETENTION_OUTS, infer_shape=_retention_infer,
          doc="Power retention over a (padded) prompt from the zero "
              "state, in the chunk form (retention_chunked; on TPU the "
              "kernel pallas_hybrid.retention_chunk, which stops at "
              "lengths[b]); the state and the normaliser after position "
              "lengths[b] - 1 are written to the slot.  "
              + _RETENTION_DOC)
def _retention_chunk(op_ctx, attrs, inputs, aux):
    from . import pallas_hybrid as ph

    q, k, v, la, pool, norm, slots, n = _retention_inputs(attrs, inputs)
    B, T, Hkv, G, D = q.shape
    # a padded position leaves the state as it is: gate 1, k = v = 0
    live = (jnp.arange(T)[None, :] < n[:, None])[..., None]
    la = jnp.where(live, la, 0.0)
    k = jnp.where(live[..., None], k, jnp.zeros((), k.dtype))
    v = jnp.where(live[..., None], v, jnp.zeros((), v.dtype))
    if ph.retention_enabled(D):
        y, last, z = ph.retention_chunk(
            q.reshape(B, T, Hkv * G * D), k.reshape(B, T, Hkv * D),
            v.reshape(B, T, Hkv * D), la, n)
    else:
        y, last, z = retention_chunked(q, k, v, la)
    return [y.reshape(B, T, Hkv * G * D).astype(inputs[0].dtype),
            pool.at[slots].set(last.astype(pool.dtype)),
            norm.at[slots].set(z.astype(norm.dtype))]


@register("RetentionStep", arg_names=_RETENTION_ARGS,
          out_names=_RETENTION_OUTS, infer_shape=_retention_infer,
          doc="Power retention for ONE token per stream against the "
              "slot's state and normaliser, updated in place (S = 1; a "
              "padded row sits on slot 0).  " + _RETENTION_DOC)
def _retention_step(op_ctx, attrs, inputs, aux):
    from . import pallas_hybrid as ph

    q, k, v, la, pool, norm, slots, _ = _retention_inputs(attrs, inputs)
    B, S, Hkv, G, D = q.shape
    if S != 1:
        raise MXNetError(f"RetentionStep feeds ONE position a step; got "
                         f"query {tuple(inputs[0].shape)}")
    f32 = jnp.float32
    qf, kf, vf = (t[:, 0].astype(f32) for t in (q, k, v))
    a = jnp.exp(la[:, 0])
    if ph.retention_enabled(D):
        y, pool, norm = ph.retention_step(
            qf, kf, vf, a, pool.astype(f32), norm.astype(f32), slots)
    else:
        y, st, z = retention_step(qf, kf, vf, a, pool[slots].astype(f32),
                                  norm[slots].astype(f32))
        pool = pool.at[slots].set(st.astype(pool.dtype))
        norm = norm.at[slots].set(z.astype(norm.dtype))
    return [y.reshape(B, 1, Hkv * G * D).astype(inputs[0].dtype), pool,
            norm]


# ---------------------------------------------------------------------------
# Compressed convolutional attention: the latent's mixing, and its norm
# ---------------------------------------------------------------------------
#
# The attention runs INSIDE a latent: ``heads`` query heads and
# ``kv_heads`` KV heads of ``head_dim``, both projected straight from the
# block's input, u = [q~ | k~] side by side as heads of D lanes.  Before
# the scores the latent is mixed along the sequence by two causal
# convolutions of two taps — depthwise ``c_t = a0 . u_t + a1 . u_(t-1)``,
# then grouped by head ``e_t[j] = B0_j c_t[j] + B1_j c_(t-1)[j]`` — and
# the q-k mean of the rows BEFORE the convolutions is added: ``q = e_q +
# (q~ + k~ of its KV head) / 2``, ``k = e_k + (the mean of its query heads'
# q~ + k~) / 2``.  Half of the value lanes come from the PREVIOUS token
# (``value_prev``: the op hands the row back shifted by one).  What a
# stream keeps beside its K/V pages to take one more token is its TAIL:
# u_(t-1), c_(t-1) and value_prev_(t-1), float32, one slot a stream
# (``kv_cache.conv_tail_shape(slots, 2, channels)``).

def cca_mix(q, k, w0, w1, left_u, left_c, H, Hkv):
    """The mixing of :class:`CCAMix` over S rows that follow ``left_u``,
    ``left_c`` (B, C) float32 — the row before the first, before and
    after the depthwise convolution (zeros where nothing came before).
    q (B, S, H·D), k (B, S, Hkv·D); w0 (C, 2), w1 (H + Hkv, 2, D, D) =
    [head, tap, out, in], tap 1 on the current token.  -> (q, k mixed,
    in q's type; u and c (B, S + 1, C) float32 with the left rows
    first).  The depthwise taps and the mean in float32; the grouped
    matmul's operands in q's type, its sums float32."""
    f32 = jnp.float32
    B, S, HD = q.shape
    D = HD // H
    G = H // Hkv
    mm = q.dtype
    prec = HI if mm == f32 else None
    u = jnp.concatenate([left_u.astype(f32)[:, None], jnp.concatenate(
        [q, k], axis=-1).astype(f32)], axis=1)              # (B, S + 1, C)
    w0f = w0.astype(f32)
    c = u[:, 1:] * w0f[:, 1] + u[:, :-1] * w0f[:, 0]
    c = jnp.concatenate([left_c.astype(f32)[:, None], c], axis=1)
    ch = c.astype(mm).reshape(B, S + 1, H + Hkv, D)
    e = sum(jnp.einsum("bsji,joi->bsjo", ch[:, tap:S + tap],
                       w1[:, tap].astype(mm), precision=prec,
                       preferred_element_type=f32) for tap in (0, 1))
    q4 = u[:, 1:, :HD].reshape(B, S, Hkv, G, D)
    k4 = u[:, 1:, HD:].reshape(B, S, Hkv, D)
    mq = 0.5 * (q4 + k4[:, :, :, None])
    mk = 0.5 * (jnp.mean(q4, axis=3) + k4)
    qo = e[:, :, :H].reshape(B, S, HD) + mq.reshape(B, S, HD)
    ko = e[:, :, H:].reshape(B, S, Hkv * D) + mk.reshape(B, S, Hkv * D)
    return qo.astype(mm), ko.astype(mm), u, c


def _cca_mix_infer(attrs, in_shapes):
    q, k, v, pool = (in_shapes[i] for i in (0, 1, 2, 5))
    if q is None or k is None or v is None or pool is None:
        return in_shapes, None, None
    return in_shapes, [tuple(q), tuple(k), tuple(v), tuple(pool)], []


@register("CCAMix",
          arg_names=("query", "key", "value_prev", "conv0_weight",
                     "conv1_weight", "tail_pool", "slots", "lengths"),
          out_names=("query_mixed", "key_mixed", "value_shifted",
                     "new_tail_pool"),
          infer_shape=_cca_mix_infer,
          doc="The sequence mixing of compressed convolutional attention, "
              "with the rows it needs of the previous token carried per "
              "stream: query (B, S, H*D) and key (B, S, Hkv*D), the "
              "latent u = [query | key] as H + Hkv heads of D; conv0_weight "
              "(C, 2), depthwise, c_t = w[:, 1] u_t + w[:, 0] u_(t-1); "
              "conv1_weight (H + Hkv, 2, D, D) = [head, tap, out, in], "
              "e_t[j] = W[j, 1] c_t[j] + W[j, 0] c_(t-1)[j]; the q-k mean "
              "of the rows before the convolutions added: query_mixed = "
              "e_q + (query + its KV head's key) / 2, key_mixed = e_k + "
              "(the mean of its query heads + key) / 2.  value_prev "
              "(B, S, Wv): the value lanes taken from the previous token, "
              "handed back shifted by one row.  tail_pool (slots, 8, W) "
              "float32 (kv_cache.conv_tail_shape(slots, 2, 2 C + Wv): a "
              "slot holds u_(t-1) | c_(t-1) | value_prev_(t-1)), slots "
              "(B,) int32 (0 = scratch).  step=0 (prefill): the sequence "
              "starts from nothing (zeros before its first row) and the "
              "rows at position lengths[b] - 1 — the prompt's TRUE last, "
              "not the bucket's — are written to the slot; step=1 "
              "(decode, S = 1): the slot's rows precede the token and are "
              "replaced by its own.  -> the three rows and the pool.  "
              "attrs: num_heads, kv_heads, step")
def _cca_mix(op_ctx, attrs, inputs, aux):
    q, k, v2, w0, w1, pool, slots, lengths = inputs
    H, Hkv = _gqa_heads(attrs, q, k)
    step = attr_bool(attrs.get("step", False), False)
    B, S, _ = q.shape
    C, Wv = q.shape[-1] + k.shape[-1], v2.shape[-1]
    run = 2 * C + Wv                # a slot's numbers, then padding
    if tuple(w0.shape) != (C, 2) or tuple(w1.shape) != (
            H + Hkv, 2, C // (H + Hkv), C // (H + Hkv)) \
            or pool.shape[1] * pool.shape[2] < run:
        raise MXNetError(
            f"CCAMix: conv0 {tuple(w0.shape)} / conv1 {tuple(w1.shape)} "
            f"are not two taps over {C} channels as {H + Hkv} heads, or a "
            f"slot of the tail pool {tuple(pool.shape)} holds fewer than "
            f"{run} numbers (u | c | value_prev)")
    slots = slots.astype(jnp.int32)
    f32 = jnp.float32
    if step:
        if S != 1:
            raise MXNetError(f"CCAMix step=1 feeds ONE position a step; "
                             f"got query {tuple(q.shape)}")
        left = pool[slots].reshape(B, -1).astype(f32)
        left_u, left_c, left_v = (left[:, :C], left[:, C:2 * C],
                                  left[:, 2 * C:run])
    else:
        left_u = left_c = jnp.zeros((B, C), f32)
        left_v = jnp.zeros((B, Wv), f32)
    qo, ko, u, c = cca_mix(q, k, w0, w1, left_u, left_c, H, Hkv)
    v = jnp.concatenate([left_v[:, None], v2.astype(f32)], axis=1)
    if step:
        last = jnp.ones((B,), jnp.int32)
    else:       # rows 0 .. S of u, c, v: row n is position n - 1
        last = jnp.clip(lengths.astype(jnp.int32), 0, S)
    at = last[:, None, None]
    tail = jnp.concatenate(
        [jnp.take_along_axis(t, at, axis=1)[:, 0] for t in (u, c, v)],
        axis=-1)
    return [qo, ko, v[:, :S].astype(v2.dtype),
            _write_tails(pool, tail, slots)]


CCA_NORM_EPS = 1e-6     # under the root of a head's summed squares


def qk_l2_norm(x, heads, temperature=None):
    """Each head of x (..., heads·D) scaled to length sqrt(D): ``sqrt(D)
    x / |x|_2`` — times ``temperature`` (heads,), one learned positive
    number a head, where given.  Float32 inside, x's type out."""
    D = x.shape[-1] // heads
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (heads, D))
    y = xf * (lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                        + CCA_NORM_EPS) * float(D) ** 0.5)
    if temperature is not None:
        y = y * temperature.astype(jnp.float32)[:, None]
    return y.reshape(x.shape).astype(x.dtype)


@register("QKL2Norm", arg_names=("query", "key", "temperature"),
          out_names=("query_normed", "key_normed"),
          infer_shape=lambda attrs, s: (
              [s[0], s[1], (attr_int(attrs.get("kv_heads", 1), 1),)],
              [s[0], s[1]], []),
          doc="L2 normalisation of each head of query (B, S, H*D) and key "
              "(B, S, Hkv*D) to length sqrt(D), the keys times a learned "
              "temperature (Hkv,) float32, one a KV head: the scores' "
              "head_dim^-1/2 then makes q.k the cosine times sqrt(D) "
              "times the temperature.  Float32 inside.  attrs: num_heads, "
              "kv_heads")
def _qk_l2_norm(op_ctx, attrs, inputs, aux):
    q, k, temp = inputs
    H, Hkv = _gqa_heads(attrs, q, k)
    return [qk_l2_norm(q, H), qk_l2_norm(k, Hkv, temp)]


# ---------------------------------------------------------------------------
# A router that is a small network, carried from layer to layer; the
# residual stream under learned scales
# ---------------------------------------------------------------------------

ROUTER_ACTS = ("", "gelu")


@register("RouterLinear", arg_names=("data", "weight"),
          infer_shape=lambda attrs, s: (
              s, [None if s[0] is None or s[1] is None
                  else tuple(s[0][:-1]) + (s[1][0],)], []),
          doc="One linear map of an expert router that is a small network: "
              "data (B, S, K) in any type, weight (N, K) -> (B, S, N) "
              "FLOAT32 at float32 precision (a router's choice must not "
              "hang on the products' rounding), then act: '' (default) or "
              "'gelu' (the exact one, by erf)")
def _router_linear(op_ctx, attrs, inputs, aux):
    x, w = inputs
    act = str(attrs.get("act", ""))
    if act not in ROUTER_ACTS:
        raise MXNetError(f"RouterLinear: act {act!r} is none of "
                         f"{ROUTER_ACTS}")
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32).T,
                precision=HI)
    return [jax.nn.gelu(y, approximate=False) if act else y]


@register("RouterCarry", arg_names=("data", "carried", "gamma"),
          infer_shape=lambda attrs, s: (
              [s[0], s[0], None if s[0] is None else (s[0][-1],)],
              [s[0]], []),
          doc="A router's hidden row joined by the row the router of the "
              "layer before left: data + gamma * carried, gamma one "
              "learned number a channel; float32")
def _router_carry(op_ctx, attrs, inputs, aux):
    r, prev, gamma = (t.astype(jnp.float32) for t in inputs)
    return [r + gamma * prev]


@register("ScaledResidual", arg_names=("data", "branch", "scales"),
          infer_shape=lambda attrs, s: (
              [s[0], s[0], None if s[0] is None else (4, s[0][-1])],
              [s[0]], []),
          doc="The residual add under learned per-channel scales: scales "
              "(4, d) = rows a_r, b_r, a_o, b_o -> (a_r * data + b_r) + "
              "(a_o * branch + b_o); float32 inside, data's type out")
def _scaled_residual(op_ctx, attrs, inputs, aux):
    x, out, sc = inputs
    a_r, b_r, a_o, b_o = sc.astype(jnp.float32)
    y = (a_r * x.astype(jnp.float32) + b_r) \
        + (a_o * out.astype(jnp.float32) + b_o)
    return [y.astype(x.dtype)]


# ---------------------------------------------------------------------------
# MoEFFN: routed experts, the share held here
# ---------------------------------------------------------------------------

MOE_COUNTERS = ("moe_pairs_here", "moe_pairs_elsewhere", "moe_experts_hit",
                "moe_load_max")


ROUTER_SCORES = ("sigmoid", "softmax_topk", "softmax")


def moe_route(x2, router_w, top_k, score="sigmoid", select_bias=None,
              groups=0, top_groups=0, routed_scale=1.0, logits=None):
    """The ``top_k`` experts of each token and their weights, float32,
    from the router's logits over ALL experts.  ``score``: ``sigmoid`` —
    sigmoid scores, the largest, weights ``s_e / sum_top s``;
    ``softmax_topk`` — the largest LOGITS, weights a softmax over those
    ``top_k`` alone; ``softmax`` — a softmax over ALL experts, the
    largest (of p + ``select_bias`` where given: the choice alone),
    weights the chosen probabilities as they are, NOT normalised over
    the chosen (a top-1 weight is p_e, not 1), times ``routed_scale``.
    x2 (N, d); router_w (E, d) — or ``logits`` (N, E), the router's own
    where it is not one matrix (x2 and router_w are then not read).

    Under ``sigmoid`` the CHOICE may be moved without moving the
    weights: ``select_bias`` (E,) is added to the scores it is made by;
    ``groups`` > 0 limits it to the ``top_groups`` groups (of E / groups
    consecutive experts) whose two largest biased scores sum highest;
    the weights stay the UNBIASED scores of the chosen, normalised, times
    ``routed_scale``.  None of the three given: the plain form above,
    unchanged."""
    if score not in ROUTER_SCORES:
        raise MXNetError(f"router score {score!r} is none of "
                         f"{ROUTER_SCORES}")
    logits = jnp.dot(x2.astype(jnp.float32),
                     router_w.astype(jnp.float32).T, precision=HI) \
        if logits is None else logits.astype(jnp.float32)
    moved = select_bias is not None or groups or routed_scale != 1.0
    if (moved and score == "softmax_topk") or (groups
                                               and score != "sigmoid"):
        raise MXNetError(
            f"router score {score!r} takes no group limit"
            + ("" if score == "softmax" else ", selection bias or scale")
            + "; 'sigmoid' does")
    if score == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
        topi = lax.top_k(p if select_bias is None else
                         p + select_bias.astype(jnp.float32), top_k)[1]
        return topi, jnp.take_along_axis(p, topi, axis=-1) \
            * jnp.float32(routed_scale)
    if score == "softmax_topk":
        topv, topi = lax.top_k(logits, top_k)
        return topi, jax.nn.softmax(topv, axis=-1)
    if not moved:
        topv, topi = lax.top_k(jax.nn.sigmoid(logits), top_k)
        return topi, topv / jnp.sum(topv, axis=-1, keepdims=True)
    s = jax.nn.sigmoid(logits)
    choice = s if select_bias is None else \
        s + select_bias.astype(jnp.float32)
    if groups:
        N, E = s.shape
        if E % groups or not 0 < top_groups <= groups or E // groups < 2:
            raise MXNetError(
                f"router: {E} experts in {groups} groups of which "
                f"{top_groups} are kept (a group's score is the sum of "
                f"its two largest)")
        by_group = choice.reshape(N, groups, E // groups)
        best = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)    # (N, groups)
        kept = lax.top_k(best, top_groups)[1]                 # (N, top_g)
        in_kept = jnp.any(
            kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
        choice = jnp.where(in_kept[:, :, None], by_group,
                           -jnp.inf).reshape(N, E)
    topi = lax.top_k(choice, top_k)[1]
    topv = jnp.take_along_axis(s, topi, axis=-1)
    return topi, topv / jnp.sum(topv, axis=-1, keepdims=True) \
        * jnp.float32(routed_scale)


def _tile_rows(n_pairs, held=0, step=True):
    """Rows of a tile of the grouped matmul: a decode batch's few rows
    an expert want small tiles, a prompt's want the MXU's.  A tile
    streams its expert's three matrices whatever its rows, so a PROMPT
    (``step`` false) whose ``held`` experts average two 16-row tiles or
    more each — 32 pairs an expert: a prompt of 512 at one expert a
    token over 16 held, far under the line of 4,096 pairs — takes the
    MXU's tiles too: in 16-row tiles every expert's weights would
    stream once a tile, 4-5 times over at 1,024 tokens."""
    if n_pairs >= 4096 or (not step and held and n_pairs >= 32 * held):
        return 128
    return 16


def _prompt_tiles(tm, d):
    """Does the dispatch round run a prompt's form — output rows left
    as slabs that ``moe_gmm_combine`` copies, weighs and adds
    (``pallas_hybrid``'s moe_gmm section)?  Tiles of 128 rows, rows of
    whole lane tiles, compiled kernels.  A decode step's 16-row tiles
    keep (M, d) rows and k small gathers: there the kernels stream their
    experts' weights, and row copies in front of a tile are latency, not
    bandwidth (PERF.md section 6, PR 39's kernel-alone table)."""
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    return pk.enabled() and tm == 128 and ph.slab_rows(d) > 0


def _by_index(tm, d, held, experts):
    """Does ``moe_gmm_gate_up`` fetch a prompt's rows itself, by index?
    Where at most half the experts are held here: the gather it saves
    writes the worst case (every pair here), a row copy costs about
    twice a gathered row, and with every expert held the worst case IS
    the case (the same table: mixed loses a millisecond a layer by
    index, longctx gains one)."""
    return _prompt_tiles(tm, d) and 2 * held <= experts


def moe_dispatch(topi, valid, first, held, tm):
    """Lay the token-expert pairs whose expert is held here
    (``first <= e < first + held``, token valid) out in rows sorted by
    expert, each expert's run padded to whole tiles of ``tm``.

    Returns ``here`` (N, k) bool; ``pair_row`` (N, k) the row of each
    pair (meaningless where not ``here``); ``row_token`` (M,) the token
    a row holds (0 on padding); ``tile_expert`` (M // tm,); ``n_used``
    (1,) tiles that hold rows; ``sizes`` (held,) pairs per expert.  M
    covers every pair landing here: nothing is dropped.

    Two sorts, comparisons and ONE scatter: on the chip a scatter or a
    gather of single numbers runs an element at a time (5-9 ns each),
    a sort of 65,536 pairs takes 0.05 ms — the scatter-add, the four
    single-number gathers and the three scatters this replaced took
    1.7-2.1 ms a layer at a long prompt's size, this takes 0.36-0.42
    (PERF.md section 6, PR 39)."""
    N, k = topi.shape
    local = topi - first
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    P = N * k
    M = -(-N * min(k, held) // tm) * tm + held * tm
    ids = jnp.arange(held, dtype=jnp.int32)
    sizes = jnp.sum((key[:, None] == ids[None, :]).astype(jnp.int32), axis=0)
    padded = -(-sizes // tm) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    plain = jnp.cumsum(sizes) - sizes
    # pairs in expert order (stable: a run keeps its tokens' order); the
    # pair at sorted place i lies at row i + (its run's start - its
    # expert's first place); back in pair order by a second sort
    skey, order = lax.sort_key_val(key, jnp.arange(P, dtype=jnp.int32))
    shift = jnp.sum(jnp.where(skey[:, None] == ids[None, :],
                              (starts - plain)[None, :], 0), axis=1)
    row = jnp.where(skey < held, jnp.arange(P, dtype=jnp.int32) + shift, M)
    pair_row = lax.sort_key_val(order, row)[1]
    # the expert whose padded run holds a tile's first row: the runs
    # that END at or before it (a comparison, not a search: a search is
    # a loop of tiny programs on the chip)
    first_row = jnp.arange(M // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.sum((ends[None, :] <= first_row[:, None]).astype(jnp.int32),
                axis=1), held - 1)
    n_used = (ends[-1:] // tm).astype(jnp.int32)
    row_token = jnp.zeros((M,), jnp.int32).at[row].set(order // k,
                                                       mode="drop")
    return (here, pair_row.reshape(N, k), row_token, tile_expert, n_used,
            sizes)


EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def moe_experts(x2, w_gate, w_up, w_down, row_token, tile_expert, n_used,
                tm, act="silu", by_index=False):
    """The held experts on the dispatched rows, float32 (rows of unused
    tiles hold anything): (M, d) — or, for a prompt's tiles
    (``_prompt_tiles``), the (M, R, 128) slabs ``moe_combine`` reads.
    ``act``: the gate's activation; ``by_index``: the kernel fetches
    its rows itself (``_by_index``)."""
    from . import pallas_hybrid as ph
    from . import pallas_kernels as pk

    if by_index:
        h = ph.moe_gmm_gate_up(x2, w_gate, w_up, tile_expert, n_used, tm,
                               act, row_token=row_token)
        return ph.moe_gmm_down(h, w_down, tile_expert, n_used, tm,
                               slabs=True)
    xs = x2[row_token]
    if pk.enabled():
        h = ph.moe_gmm_gate_up(xs, w_gate, w_up, tile_expert, n_used, tm,
                               act)
        return ph.moe_gmm_down(h, w_down, tile_expert, n_used, tm,
                               slabs=_prompt_tiles(tm, x2.shape[1]))
    e = jnp.repeat(tile_expert, tm)

    def mm(a, w):
        return jnp.einsum("mk,mkn->mn", a, w[e],
                          preferred_element_type=jnp.float32)

    h = (EXPERT_ACTS[act](mm(xs, w_gate)) * mm(xs, w_up)).astype(xs.dtype)
    return mm(h, w_down)


def moe_combine(ys, pair_row, here, wts, d, dtype):
    """Token n's output: its rows ``ys[pair_row[n, j]]`` times their
    routing weights ``wts[n, j]`` over the pairs that are ``here``,
    added in j's order, float32 -> (N, d) in ``dtype``.  A row that is
    not here is masked, never multiplied (an unused tile's rows hold
    anything)."""
    from . import pallas_hybrid as ph

    if ys.ndim == 3:
        return ph.moe_gmm_combine(ys, pair_row, here, wts, d, dtype)
    at = jnp.minimum(pair_row, ys.shape[0] - 1)
    y = jnp.zeros((pair_row.shape[0], d), jnp.float32)
    for j in range(pair_row.shape[1]):
        y = y + jnp.where(here[:, j, None], ys[at[:, j]] * wts[:, j, None],
                          0.0)
    return y.astype(dtype)


def _moe_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, None, None
    return in_shapes, [tuple(d), (len(MOE_COUNTERS),)], []


_MOE_ARGS = ("data", "router_weight", "gate_weight", "up_weight",
             "down_weight", "lengths", "counters")


def _moe_args(attrs):
    # the second input is what the router gives: its matrix, or — a
    # router that is a network of its own — the logits themselves
    second = ("router_logits",) if attr_bool(
        attrs.get("router_logits", False), False) else _MOE_ARGS[1:2]
    return _MOE_ARGS[:1] + second + _MOE_ARGS[2:] + tuple(
        k for k in ("router_data", "select_bias")
        if attr_bool(attrs.get(k, False), False))


@register("MoEFFN", arg_names=_moe_args,
          out_names=("output", "new_counters"), infer_shape=_moe_infer,
          doc="The routed experts' part of a mixture-of-experts layer, "
              "for the experts HELD here: data (B, S, d); router_weight "
              "(experts, d) float32 over ALL experts; gate/up_weight "
              "(held, d, w), down_weight (held, w, d): experts "
              "first_expert .. first_expert + held - 1.  score='sigmoid' "
              "(default): sigmoid scores, the top_k largest, weights s_e / "
              "sum_top s; score='softmax_topk': the top_k largest logits, "
              "weights their softmax; score='softmax': a softmax over ALL "
              "experts, the top_k largest, weights those probabilities "
              "un-normalised (float32 all three); router_logits=1: the "
              "second input is router_logits (B, S, experts) float32, a "
              "router's own output, in place of router_weight; "
              "output = sum over a token's chosen experts that are held "
              "here of w_e E_e(x), E_e = W_down (act(W_gate x) * W_up "
              "x), act='silu' (default) or 'relu'.  router_data=1: an "
              "eighth input, router_data (B, S, d), is what the router "
              "scores in place of data (a router that reads the block's "
              "input, before attention).  select_bias=1: a further input, "
              "select_bias (experts,) float32, added to the sigmoid scores "
              "the CHOICE is made by; groups / top_groups: the choice is "
              "limited to the top_groups of groups groups of consecutive "
              "experts (by the sum of a group's two largest biased "
              "scores); routed_scale multiplies the weights, which stay "
              "the unbiased scores normalised (moe_route).  "
              "What the other experts would "
              "add belongs to other "
              "chips and is left out.  No pair is dropped.  lengths "
              "(B,) masks padding (step=1: rows with lengths 0; step=0: "
              "positions >= lengths).  counters (4,) int32 — pairs "
              "computed here, pairs left elsewhere, held experts hit, "
              "the largest expert's load — is added to where count=1.  "
              "attrs: top_k, first_expert, step, count, score, act, "
              "router_data, router_logits, select_bias, groups, "
              "top_groups, routed_scale")
def _moe_ffn(op_ctx, attrs, inputs, aux):
    from .. import profiler

    x, router_w, w_gate, w_up, w_down, lengths, counters = inputs[:7]
    act = str(attrs.get("act", "silu"))
    if act not in EXPERT_ACTS:
        raise MXNetError(f"MoEFFN: act {act!r} is none of "
                         f"{tuple(EXPERT_ACTS)}")
    top_k = attr_int(attrs.get("top_k", 1), 1)
    first = attr_int(attrs.get("first_expert", 0), 0)
    step = attr_bool(attrs.get("step", False), False)
    count = attr_bool(attrs.get("count", False), False)
    B, S, d = x.shape
    held = w_gate.shape[0]
    logits = None
    if attr_bool(attrs.get("router_logits", False), False):
        logits = router_w.reshape(B * S, -1)
    experts = router_w.shape[0] if logits is None else logits.shape[1]
    if first < 0 or first + held > experts:
        raise MXNetError(
            f"MoEFFN holds experts {first}..{first + held - 1} of the "
            f"{experts} the router scores")
    n = lengths.astype(jnp.int32)
    valid = (jnp.broadcast_to(n[:, None] > 0, (B, S)) if step
             else jnp.arange(S)[None, :] < n[:, None]).reshape(-1)
    x2 = x.reshape(B * S, d)
    extra = dict(zip(_moe_args(attrs)[7:], inputs[7:]))
    routed = extra["router_data"].reshape(B * S, d) \
        if "router_data" in extra else x2
    topi, wts = moe_route(
        routed, router_w, top_k, str(attrs.get("score", "sigmoid")),
        select_bias=extra.get("select_bias"),
        groups=attr_int(attrs.get("groups", 0), 0),
        top_groups=attr_int(attrs.get("top_groups", 0), 0),
        routed_scale=attr_float(attrs.get("routed_scale", 1.0), 1.0),
        logits=logits)
    tm = _tile_rows(B * S * min(top_k, held), held, step)
    here, pair_row, row_token, tile_expert, n_used, sizes = moe_dispatch(
        topi, valid, first, held, tm)
    by_index = _by_index(tm, d, held, experts)
    profiler.inc_counter("moe.nodes_indexed" if by_index
                         else "moe.nodes_gathered")
    ys = moe_experts(x2, w_gate, w_up, w_down, row_token, tile_expert,
                     n_used, tm, act, by_index)
    y = moe_combine(ys, pair_row, here, wts, d, x.dtype)
    if count:
        pairs = jnp.sum(here.astype(jnp.int32))
        counters = counters + jnp.stack([
            pairs, jnp.sum(valid.astype(jnp.int32)) * top_k - pairs,
            jnp.sum((sizes > 0).astype(jnp.int32)), jnp.max(sizes)])
    return [y.reshape(B, S, d), counters]
