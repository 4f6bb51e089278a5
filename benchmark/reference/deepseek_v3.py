"""The plain reference for the ``deepseek_v3`` family, and its seeded
weights.

DeepSeek-V3 as its public ``config.json`` describes it, cut to a share
of one pipeline stage (the configuration file says which): pre-norm
residual blocks, RMSNorm (``rms_norm_eps``), no biases, an untied head.
For a layer on the residual stream ``x`` (T, d), d = ``hidden_size``,
H = ``num_attention_heads``, n / r / dv = ``qk_nope_head_dim`` /
``qk_rope_head_dim`` / ``v_head_dim``:

* ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_dq)`` (``q_lora_rank``);
  ``q = c_q W_uq`` -> H heads of ``[q_n (n) | q_r (r)]``;
* ``c, k_r = split(h W_dkv)`` (``kv_lora_rank`` | r); ``c = RMSNorm(c)``;
  ``k_r`` is ONE key, shared by the H heads;
* ``q_r`` and ``k_r`` are rotated by their position ``t``: pairs
  ``(i, i + r/2)`` turned by ``t * inv_freq_i`` (rotate-half), the
  frequencies ``theta^(-2i/r)`` rescaled as ``rope_scaling`` says
  (:func:`inv_freq`: YaRN, arXiv:2309.00071);
* ``k_n^h, v^h = c W_ukv`` -> H heads of (n | dv);
* ``s_ij^h = (q_n,i^h . k_n,j^h + q_r,i^h . k_r,j) * sigma`` for
  ``j <= i``, ``sigma = (n + r)^-1/2 * (0.1 mscale_all_dim ln factor +
  1)^2``; ``a^h = softmax_j(s^h) v^h``; ``x' = x + concat_h(a^h) W_o``;
* ``h2 = RMSNorm(x')``; the first ``first_k_dense_replace`` layers (one
  of them is held): ``x' + W_down(silu(W_gate h2) * W_up h2)`` of
  ``intermediate_size``;
* the others: ``s = sigmoid(h2 W_r^T)`` over ALL ``n_routed_experts``
  published, float32; ``s' = s + b`` (``e_score_correction_bias``, for
  the CHOICE only); G = the ``topk_group`` of the ``n_group`` groups of
  consecutive experts with the largest sum of a group's 2 largest
  ``s'``; E = the ``num_experts_per_tok`` largest ``s'`` inside G;
  ``w_e = routed_scaling_factor * s_e / sum_E s``; ``y = sum_{e in E,
  held here} w_e W_down,e(silu(W_gate,e h2) * W_up,e h2) + shared(h2)``,
  widths ``moe_intermediate_size``; what the experts held elsewhere
  would add is left out (the configuration file's ``departures``);
* ``logits = RMSNorm(x_L) W_head^T``.

This is the FIRST form of the attention only: keys and values
up-projected for every position, no cache, no absorption of ``W_ukv``
into the query — prefill then decode through the cache agreeing with it
is what shows the program's two paths equal.

The weights are held in the program's layout: ``q_up``'s rows are
[every head's q_n | every head's q_r] and ``kv_up``'s [every head's k_n
| every head's v] — a fixed permutation of the published per-head rows,
which a random draw does not tell apart (``assumed`` in the
configuration file).

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
batching; attention a group of heads and a block of queries at a time
(the scores of a whole long prompt do not fit), nothing else regrouped.
It imports nothing of ``mxnet_tpu`` but the spec class (:func:`spec`).

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (experts and head
included; the router stays float32) in e4m3 with one scale per tensor;
``fp8_latent`` rounds what a token would leave in the cache (c and the
rotated k_r) to e4m3; ``bfloat16`` multiplies in bfloat16.  And it names
a MECHANISM left out or got wrong, each in float32 (:data:`MECHANISMS`).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.solar_open2 import (  # noqa: F401
    HI, _fq, gated_ffn, lin, mm, program_names, rms, seed_key, to_float32)

# mechanisms a control leaves out or gets wrong
MECHANISMS = (
    "no_rotation",        # q_r and k_r are not rotated
    "rotate_all",         # the whole head (n + r lanes) is rotated
    "plain_freq",         # theta^(-2i/r), not rescaled
    "no_mscale",          # sigma without the rescaling's factor
    "no_kv_norm",         # the latent's norm left out
    "unrotated_key",      # k_r cached (used) unrotated, q_r rotated
    "no_select_bias",     # the choice made by s, not s + b
    "bias_in_weights",    # the weights from s + b too
    "no_group_limit",     # the top k over all experts
    "no_renorm",          # weights s_e, not s_e / sum
    "no_routed_scale",    # the factor on the weights left out
    "swap_kv_up",         # W_uk and W_uv exchanged
)
QUERY_BLOCK = 512
HEAD_GROUP = 8


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def _frozen(d):
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str))))


def sizes(cfg):
    L = int(cfg["num_hidden_layers"])
    dense = int(cfg.get("dense_layers_held", cfg["first_k_dense_replace"]))
    return dict(
        L=L, d=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]), dense=min(dense, L),
        H=int(cfg["num_attention_heads"]), Rq=int(cfg["q_lora_rank"]),
        R=int(cfg["kv_lora_rank"]), n=int(cfg["qk_nope_head_dim"]),
        r=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        theta=float(cfg["rope_theta"]),
        scaling=_frozen(cfg.get("rope_scaling") or {}),
        wd=int(cfg["intermediate_size"]),
        E=int(cfg.get("n_routed_experts_published",
                      cfg["n_routed_experts"])),
        held=int(cfg["n_routed_experts"]),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        groups=int(cfg["n_group"]), top_groups=int(cfg["topk_group"]),
        w=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        std=float(cfg.get("initializer_range", 0.02)),
        bias_std=float(cfg.get("selection_bias_std", 0.05)),
        L_pub=int(cfg.get("num_hidden_layers_published", L)))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it.
    Raises at once on a program whose layer list has no latent
    attention and no group-limited router: it could not build the
    model."""
    from mxnet_tpu.models import hybrid_lm

    mixers = getattr(hybrid_lm, "MIXERS", None)
    if not isinstance(mixers, dict) or "mla" not in mixers \
            or not {"groups", "select_bias"} <= set(
                getattr(hybrid_lm, "FFNS", {}).get("moe", ())):
        raise NotImplementedError(
            "this program's HybridSpec has no mixer kind 'mla' and no moe "
            "keys 'groups', 'select_bias': the deepseek_v3 family cannot "
            "be served by it")
    z = sizes(cfg)
    mixer = {"kind": "mla", "heads": z["H"], "q_rank": z["Rq"],
             "kv_rank": z["R"], "nope_dim": z["n"], "rope_dim": z["r"],
             "v_dim": z["dv"], "rope_theta": z["theta"]}
    if cfg.get("rope_scaling"):
        mixer["rope_scaling"] = dict(cfg["rope_scaling"])
    moe = {"kind": "moe", "experts": z["E"], "top_k": z["top_k"],
           "width": z["w"], "shared": z["shared"],
           "experts_held": z["held"], "first_expert": z["first"],
           "groups": z["groups"], "top_groups": z["top_groups"],
           "routed_scale": z["routed_scale"], "select_bias": True}
    layers = [{"mixer": dict(mixer),
               "ffn": {"kind": "dense", "width": z["wd"]}
               if i < z["dense"] else dict(moe)} for i in range(z["L"])]
    return hybrid_lm.HybridSpec(z["V"], z["d"], layers, norm_eps=z["eps"])


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

FLOAT32_LEAVES = ("router_weight", "router_bias")
RESIDUAL_OUT = ("o_weight", "ffn_down_weight", "experts_down_weight",
                "shared_down_weight")


def _layer_shapes(z, dense):
    d, H = z["d"], z["H"]
    out = dict(
        norm1_gamma=(d,), norm2_gamma=(d,),
        q_down_weight=(z["Rq"], d), q_norm_gamma=(z["Rq"],),
        q_up_weight=(H * (z["n"] + z["r"]), z["Rq"]),
        kv_down_weight=(z["R"] + z["r"], d), kv_norm_gamma=(z["R"],),
        kv_up_weight=(H * (z["n"] + z["dv"]), z["R"]),
        o_weight=(d, H * z["dv"]))
    if dense:
        out.update(ffn_gate_weight=(z["wd"], d), ffn_up_weight=(z["wd"], d),
                   ffn_down_weight=(d, z["wd"]))
        return out
    w = z["w"]
    out.update(router_weight=(z["E"], d), router_bias=(z["E"],),
               experts_gate_weight=(z["held"], d, w),
               experts_up_weight=(z["held"], d, w),
               experts_down_weight=(z["held"], w, d))
    if z["shared"]:
        ws = w * z["shared"]
        out.update(shared_gate_weight=(ws, d), shared_up_weight=(ws, d),
                   shared_down_weight=(d, ws))
    return out


@functools.partial(jax.jit, static_argnames=("static", "kind",
                                             "embed_dtype", "dtype"))
def _draw(key, static, kind, embed_dtype, dtype):
    """One program makes the tensors of one layer (``kind``: ``dense``
    or ``moe``) or of the top (``kind`` None: table, last norm, head) on
    the device — a layer at a time, so that the float32 draws never lie
    side by side: N(0, std) matrices (the projections back into the
    residual stream scaled by 1/sqrt(2 x published depth)), unit gains,
    the selection bias N(0, bias_std); each rounded to the type it is
    held in (the router and its bias float32)."""
    z = dict(static)
    resid = 1.0 / math.sqrt(2.0 * z["L_pub"])

    def make(name, shape, k):
        if name.endswith("_gamma"):
            x = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            x = z["bias_std"] * jax.random.normal(k, shape, jnp.float32)
        else:
            x = z["std"] * (resid if name in RESIDUAL_OUT else 1.0) \
                * jax.random.normal(k, shape, jnp.float32)
        if name in FLOAT32_LEAVES:
            return x
        return x.astype(embed_dtype if name == "tok_embed_weight"
                        else dtype)

    shapes = _layer_shapes(z, kind == "dense") if kind else {
        "tok_embed_weight": (z["V"], z["d"]),
        "final_norm_gamma": (z["d"],), "head_weight": (z["V"], z["d"])}
    return {n: make(n, s, k) for (n, s), k in
            zip(shapes.items(), jax.random.split(key, len(shapes)))}


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights, ``{"layers": [{leaf: array}, ...], top
    leaves}``, in the types the program serves them in."""
    static = _static(cfg)
    z = sizes(cfg)
    keys = jax.random.split(seed_key(seed), z["L"] + 1)
    out = _draw(keys[-1], static, None, embed_dtype, dtype)
    out["layers"] = [
        _draw(k, static, "dense" if i < z["dense"] else "moe", embed_dtype,
              dtype) for i, k in enumerate(keys[:z["L"]])]
    return out


# ---------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------

def inv_freq(z, plain=False, dim=None):
    """The (dim/2,) frequencies of the rotary lanes, numpy float32: the
    published ``rope_scaling`` (YaRN) over ``theta^(-2i/dim)``."""
    dim = dim or z["r"]
    f = z["theta"] ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = dict(z["scaling"])
    if plain or float(rs.get("factor", 1.0)) <= 1.0:
        return f.astype(np.float32)

    def turns(beta):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (2 * math.pi * beta)) \
            / (2 * math.log(z["theta"]))

    low = max(math.floor(turns(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(turns(rs.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (f * ((1.0 - ramp) + ramp / rs["factor"])).astype(np.float32)


def sigma(z, mscale=True):
    rs = dict(z["scaling"])
    s = float(z["n"] + z["r"]) ** -0.5
    if mscale and rs.get("mscale_all_dim") \
            and float(rs.get("factor", 1.0)) > 1.0:
        s *= (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"])
              + 1.0) ** 2
    return s


def rotate(x, inv):
    """x (T, heads, D), row t at position t: pairs (i, i + D/2) turned
    by ``t * inv[i]``."""
    T, _, D = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.asarray(inv)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(p, h, z, precision, wrong=None):
    T = h.shape[0]
    H, n, r, dv, R = z["H"], z["n"], z["r"], z["dv"], z["R"]
    c_q = rms(lin(h, p["q_down_weight"], precision), p["q_norm_gamma"],
              z["eps"])
    q = lin(c_q, p["q_up_weight"], precision)
    q_n = q[:, :H * n].reshape(T, H, n)
    q_r = q[:, H * n:].reshape(T, H, r)
    kv = lin(h, p["kv_down_weight"], precision)
    c, k_r = kv[:, :R], kv[:, R:].reshape(T, 1, r)
    if wrong != "no_kv_norm":
        c = rms(c, p["kv_norm_gamma"], z["eps"])
    if wrong not in ("no_rotation", "rotate_all"):
        inv = inv_freq(z, plain=(wrong == "plain_freq"))
        q_r = rotate(q_r, inv)
        if wrong != "unrotated_key":
            k_r = rotate(k_r, inv)
    if precision == "fp8_latent":       # what a token leaves in the cache
        c, k_r = _fq(c), _fq(k_r)
    up = lin(c, p["kv_up_weight"], precision)
    k_n = up[:, :H * n].reshape(T, H, n)
    v = up[:, H * n:].reshape(T, H, dv)
    if wrong == "swap_kv_up" and n == dv:
        k_n, v = v, k_n
    qf = jnp.concatenate([q_n, q_r], -1)                    # (T, H, n + r)
    kf = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (T, H, r))], -1)
    if wrong == "rotate_all":
        whole = inv_freq(z, plain=True, dim=n + r)
        qf, kf = rotate(qf, whole), rotate(kf, whole)
    scale = sigma(z, mscale=(wrong != "no_mscale"))
    G = math.gcd(H, HEAD_GROUP)         # heads a step
    bq = math.gcd(T, QUERY_BLOCK)       # queries a block: (G, bq, T) fit
    j = jnp.arange(T)[None, :]

    def group(xs):
        qg, kg, vg = xs                 # (G, T, n + r), same, (G, T, dv)

        def block(ys):
            qb, i = ys                  # (G, bq, n + r), its positions
            s = jnp.einsum("gtd,gsd->gts", qb, kg, precision=HI) * scale
            return jnp.einsum("gts,gsd->gtd", jax.nn.softmax(
                jnp.where(j <= i[:, None], s, -jnp.inf), axis=-1), vg,
                precision=HI)

        out = lax.map(block, (
            qg.reshape(G, T // bq, bq, n + r).transpose(1, 0, 2, 3),
            jnp.arange(T).reshape(T // bq, bq)))
        return out.transpose(1, 0, 2, 3).reshape(G, T, dv)

    def grouped(x):                     # (T, H, D) -> (H / G, G, T, D)
        return x.transpose(1, 0, 2).reshape(H // G, G, T, x.shape[-1])

    a = lax.map(group, (grouped(qf), grouped(kf), grouped(v)))
    a = a.reshape(H, T, dv).transpose(1, 0, 2).reshape(T, H * dv)
    return lin(a, p["o_weight"], precision)


def route(p, h2, z, wrong=None):
    """(chosen experts (T, k), their weights (T, k)), float32."""
    s = jax.nn.sigmoid(jnp.dot(h2, p["router_weight"].T, precision=HI))
    choice = s if wrong == "no_select_bias" else s + p["router_bias"]
    T, E = s.shape
    if wrong != "no_group_limit":
        g = z["groups"]
        by_group = choice.reshape(T, g, E // g)
        best = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
        kept = lax.top_k(best, z["top_groups"])[1]
        in_kept = jnp.any(kept[:, :, None] == jnp.arange(g)[None, None, :],
                          axis=1)
        choice = jnp.where(in_kept[:, :, None], by_group,
                           -jnp.inf).reshape(T, E)
    topi = lax.top_k(choice, z["top_k"])[1]
    topv = jnp.take_along_axis(
        choice if wrong == "bias_in_weights" else s, topi, axis=-1)
    if wrong != "no_renorm":
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if wrong != "no_routed_scale":
        topv = topv * z["routed_scale"]
    return topi, topv


def routed(p, h2, z, precision, wrong=None):
    """(the held experts' part of the routed sum, the chosen experts),
    an expert at a time."""
    topi, wts = route(p, h2, z, wrong)
    # coef[t, j]: token t's weight for held expert j (0 if not chosen)
    held = z["first"] + jnp.arange(z["held"])
    coef = jnp.sum(jnp.where(topi[:, :, None] == held[None, None, :],
                             wts[:, :, None], 0.0), axis=1)

    def one(acc, xs):
        wg, wu, wd, c = xs
        y = mm(jax.nn.silu(mm(h2, wg, precision)) * mm(h2, wu, precision),
               wd, precision)
        return acc + c[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h2),
                      (p["experts_gate_weight"], p["experts_up_weight"],
                       p["experts_down_weight"], coef.T))
    return out, jnp.sort(topi, axis=-1)


def shared(p, h2, z, precision):
    return gated_ffn(h2, p["shared_gate_weight"], p["shared_up_weight"],
                     p["shared_down_weight"], precision)


def _arithmetic(precision):
    """A mechanism left out is computed in float32; so are the linear
    layers around rounded latents."""
    return "float32" if precision in MECHANISMS + ("fp8_latent",) \
        else precision


def hidden(w, tokens, z, precision="float32"):
    """tokens (T,) -> (the last block's output (T, d), the chosen
    experts of every expert layer (L - dense, T, k), sorted)."""
    wrong = precision if precision in MECHANISMS else None
    attn_precision = precision if precision == "fp8_latent" \
        else _arithmetic(precision)
    precision = _arithmetic(precision)
    x = w["tok_embed_weight"].astype(jnp.float32)[tokens]
    chosen = []
    for i, p in enumerate(w["layers"]):
        h = rms(x, p["norm1_gamma"], z["eps"])
        x = x + attention(p, h, z, attn_precision, wrong)
        h2 = rms(x, p["norm2_gamma"], z["eps"])
        if i < z["dense"]:
            x = x + gated_ffn(h2, p["ffn_gate_weight"], p["ffn_up_weight"],
                              p["ffn_down_weight"], precision)
            continue
        y, topi = routed(p, h2, z, precision, wrong)
        if z["shared"]:
            y = y + shared(p, h2, z, precision)
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
