"""The plain reference for the ``afmoe`` family, and its seeded weights.

Trinity-Large-Preview as its public ``config.json`` and the family's
description give it, cut to a share of one pipeline stage (the
configuration file says which, and lists what the config does not fix
under ``assumed``): residual blocks with SANDWICH norms — RMSNorm
(``rms_norm_eps``) before a branch and another, with a gain of its own,
on the branch's OUTPUT before it is added — no biases, an untied head.
On the residual stream ``x`` (T, d), d = ``hidden_size``; ``x_0 =
E[token] * sqrt(d)`` (``mup_enabled``); H / J = ``num_attention_heads`` /
``num_key_value_heads`` heads of D = ``head_dim``:

* ``h = RMSNorm(x; g1)``; ``q, k, v = h W_q (H x D), h W_k (J x D),
  h W_v (J x D)``;
* ``q^h, k^j = RMSNorm_D(q^h; g_q), RMSNorm_D(k^j; g_k)``: over each
  head's D lanes, ONE gain of D for all heads, each;
* where ``layer_types[l]`` is ``sliding_attention`` q and k are rotated
  by their position ``t``: the whole head, pairs ``(i, i + D/2)``
  turned by ``t * rope_theta^(-2i/D)`` (rotate-half), AFTER the norm;
  a ``full_attention`` layer has NO positions;
* ``s_ts^h = q_t^h . k_s^(h // (H/J)) / sqrt(D)`` for ``s <= t`` and,
  in a sliding layer, ``t - s < sliding_window``; ``a^h = softmax_s(s^h)
  v^(h // (H/J))``;
* ``o = (concat_h(a^h) * sigmoid(h W_g)) W_o``: the gate is element-wise
  over all H x D lanes and reads the NORMALISED input;
* ``x' = x + RMSNorm(o; g1')``; ``h2 = RMSNorm(x'; g2)``;
* the first ``num_dense_layers`` layers: ``y = W_down(silu(W_gate h2) *
  W_up h2)`` of ``intermediate_size``;
* the others: ``s = sigmoid(h2 W_r^T)`` over ALL ``num_experts``
  published, float32; E = the ``num_experts_per_tok`` largest of ``s +
  b`` (``expert_bias``: the CHOICE only, one group); ``w_e =
  route_scale * s_e / sum_E s`` (``route_norm``; s, not s + b); ``y =
  sum_{e in E, held here} w_e W_down,e(silu(W_gate,e h2) * W_up,e h2) +
  shared(h2)``, widths ``moe_intermediate_size``; what the experts held
  elsewhere would add is left out (the configuration's ``departures``);
* ``x'' = x' + RMSNorm(y; g2')``; ``logits = RMSNorm(x_L; g_f)
  W_head^T``.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
cache, no batching; attention a block of queries at a time (the scores
of a whole long prompt do not fit) — against every key in a full layer,
against the keys of the block's band in a sliding one (the others are
masked anyway); an expert at a time; nothing else regrouped.  It imports
nothing of ``mxnet_tpu`` but the spec class (:func:`spec`).  Weights are
HELD as drawn and cast to float32 where they are multiplied.

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (experts and head
included; the router stays float32, as in the program) in e4m3 with one
scale per tensor; ``bfloat16`` multiplies in bfloat16.  And it names ONE
mechanism left out or misplaced, each in float32 (:data:`MECHANISMS`) —
what a program without it would serve.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.solar_open2 import (  # noqa: F401
    HI, gated_ffn, lin, mm, program_names, rms, seed_key, to_float32)

# mechanisms a control leaves out or misplaces
MECHANISMS = (
    "no_qk_norm",         # q and k as their linears give them
    "q_norm_only",        # k is not normalised
    "no_post_norm",       # a branch's output is added as it is
    "post_norm_on_sum",   # RMSNorm(x + o), not x + RMSNorm(o)
    "no_gate",            # the output gate left out
    "gate_on_x",          # the gate reads the block's un-normalised input
    "no_rotation",        # no layer rotates
    "rotate_all",         # the full layers rotate too
    "no_window",          # the sliding layers see every key
    "no_embed_scale",     # the token rows without sqrt(d)
    "no_select_bias",     # the choice made by s, not s + b
    "bias_in_weights",    # the weights from s + b too
    "no_renorm",          # weights s_e, not s_e / sum
    "no_route_scale",     # the factor on the weights left out
    "no_shared",          # the shared expert left out
)
QUERY_BLOCK = 512
# rows of a padded request the forward is fed: the smallest of these
# that holds its last served position, else all that came.  Attention is
# causal, so the padding past a request's end moves none of its logits;
# the sample's lengths are a few of the traffic's, not one a request
CROP_ROWS = (9216, 17408)


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def sizes(cfg):
    L = int(cfg["num_hidden_layers"])
    # the published layers this cut holds (all of the first L where the
    # file does not say), and from the published list their kinds
    held_layers = tuple(int(i) for i in cfg.get("layers_held", range(L)))
    if len(held_layers) != L:
        raise ValueError(f"layers_held {held_layers} are not the "
                         f"{L} layers of num_hidden_layers")
    return dict(
        L=L, d=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]),
        dense=min(int(cfg["num_dense_layers"]), L),
        Hq=int(cfg["num_attention_heads"]),
        Hkv=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
        sliding=tuple(int(cfg["layer_types"][i] == "sliding_attention")
                      for i in held_layers),
        W=int(cfg["sliding_window"]), theta=float(cfg["rope_theta"]),
        wd=int(cfg["intermediate_size"]),
        E=int(cfg.get("num_experts_published", cfg["num_experts"])),
        held=int(cfg["num_experts"]),
        first=int(cfg.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        w=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["num_shared_experts"]),
        route_scale=float(cfg["route_scale"]),
        route_norm=bool(cfg.get("route_norm", True)),
        embed_scale=math.sqrt(float(cfg["hidden_size"]))
        if cfg.get("mup_enabled") else 1.0,
        std=float(cfg.get("initializer_range", 0.02)),
        bias_std=float(cfg.get("selection_bias_std", 0.05)))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it.
    Raises at once on a program whose layer list knows no q/k norm and
    no post-norms: it would refuse the keys by name further on, or
    serve a model without them."""
    import inspect

    from mxnet_tpu.models import hybrid_lm

    mixers = getattr(hybrid_lm, "MIXERS", None)
    if not isinstance(mixers, dict) \
            or "qk_norm" not in mixers.get("attention", ()) \
            or "post_norm" not in inspect.signature(
                hybrid_lm.HybridSpec.__init__).parameters:
        raise NotImplementedError(
            "this program's HybridSpec has no attention key 'qk_norm' and "
            "no 'post_norm': the afmoe family (per-head q/k norms, "
            "sandwich norms) cannot be served by it")
    z = sizes(cfg)
    moe = {"kind": "moe", "experts": z["E"], "top_k": z["top_k"],
           "width": z["w"], "shared": z["shared"],
           "experts_held": z["held"], "first_expert": z["first"],
           "routed_scale": z["route_scale"], "select_bias": True}
    layers = []
    for i, sliding in enumerate(z["sliding"]):
        mixer = {"kind": "attention", "heads": z["Hq"],
                 "kv_heads": z["Hkv"], "head_dim": z["D"], "gate": True,
                 "qk_norm": True}
        if sliding:
            mixer.update(rope_theta=z["theta"], window=z["W"])
        layers.append({"mixer": mixer,
                       "ffn": {"kind": "dense", "width": z["wd"]}
                       if i < z["dense"] else dict(moe)})
    return hybrid_lm.HybridSpec(z["V"], z["d"], layers, norm_eps=z["eps"],
                                embed_scale=z["embed_scale"],
                                post_norm=True)


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

FLOAT32_LEAVES = ("router_weight", "router_bias")


def _layer_shapes(z, dense):
    d = z["d"]
    hd, kd = z["Hq"] * z["D"], z["Hkv"] * z["D"]
    out = dict(
        norm1_gamma=(d,), norm2_gamma=(d,), post_norm1_gamma=(d,),
        post_norm2_gamma=(d,), q_norm_gamma=(z["D"],),
        k_norm_gamma=(z["D"],), q_weight=(hd, d), k_weight=(kd, d),
        v_weight=(kd, d), gate_weight=(hd, d), o_weight=(d, hd))
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
    side by side: N(0, std) matrices (no depth scaling of the
    projections back into the residual stream: a post-norm takes a
    branch's scale out, and a small one would only bring its eps into
    play), unit gains, the selection bias N(0, bias_std); each rounded to
    the type it is held in (the router and its bias float32)."""
    z = dict(static)

    def make(name, shape, k):
        if name.endswith("_gamma"):
            x = jnp.ones(shape, jnp.float32)
        else:
            x = (z["bias_std"] if name == "router_bias" else z["std"]) \
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

def rotate(x, theta):
    """x (T, heads, D), row t at position t: pairs (i, i + D/2) of every
    head turned by ``t * theta^(-2i/D)``."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attend(q, k, v, window):
    """q (T, Hq, D), k and v (T, Hkv, D) -> (T, Hq·D): causal softmax
    attention, query head i on KV head ``i // (Hq / Hkv)``; ``window``:
    the keys a query sees, its own among them (0: every key up to it)."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = math.gcd(T, QUERY_BLOCK)   # queries a block: its scores fit
    # a sliding layer's block sees the keys of its band alone: the
    # `back` before its first query (whole blocks) and its own
    back = -(-(window - 1) // bq) * bq if window else 0
    band = bool(window) and back + bq < T
    span = back + bq if band else T

    def group(xs):                  # one KV head and its G query heads
        qg, kg, vg = xs             # (G, T, D), (T, D), (T, D)
        if band:                    # rows before position 0: masked
            kg = jnp.pad(kg, ((back, 0), (0, 0)))
            vg = jnp.pad(vg, ((back, 0), (0, 0)))

        def block(ys):
            qb, i = ys              # (G, bq, D), the block's positions
            if band:                # keys i[0] - back .. i[0] + bq - 1
                kb = lax.dynamic_slice_in_dim(kg, i[0], span, axis=0)
                vb = lax.dynamic_slice_in_dim(vg, i[0], span, axis=0)
                j = (i[0] - back + jnp.arange(span))[None, :]
            else:
                kb, vb, j = kg, vg, jnp.arange(T)[None, :]
            s = jnp.einsum("gtd,sd->gts", qb, kb, precision=HI) * D ** -0.5
            see = (j <= i[:, None]) & (j >= 0)
            if window:
                see &= i[:, None] - j < window
            return jnp.einsum("gts,sd->gtd", jax.nn.softmax(
                jnp.where(see, s, -jnp.inf), axis=-1), vb, precision=HI)

        out = lax.map(block, (
            qg.reshape(G, T // bq, bq, D).transpose(1, 0, 2, 3),
            jnp.arange(T).reshape(T // bq, bq)))
        return out.transpose(1, 0, 2, 3).reshape(G, T, D)

    a = lax.map(group, (q.reshape(T, Hkv, G, D).transpose(1, 2, 0, 3),
                        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return a.transpose(2, 0, 1, 3).reshape(T, Hq * D)   # (Hkv, G, T, D) ->


def attention(p, h, x, z, precision, sliding, wrong=None):
    """The branch's output ``o`` (before its post-norm).  ``h``: the
    normalised input; ``x``: the block's input (a misplaced gate reads
    it)."""
    T = h.shape[0]
    Hq, Hkv, D = z["Hq"], z["Hkv"], z["D"]
    q = lin(h, p["q_weight"], precision).reshape(T, Hq, D)
    k = lin(h, p["k_weight"], precision).reshape(T, Hkv, D)
    v = lin(h, p["v_weight"], precision).reshape(T, Hkv, D)
    if wrong != "no_qk_norm":   # over each head's D lanes, one gain
        q = rms(q, p["q_norm_gamma"], z["eps"])
        if wrong != "q_norm_only":
            k = rms(k, p["k_norm_gamma"], z["eps"])
    rope = {"no_rotation": 0, "rotate_all": 1}.get(wrong, sliding)
    if rope:
        q, k = rotate(q, z["theta"]), rotate(k, z["theta"])
    window = 0 if wrong == "no_window" else z["W"] * sliding
    a = attend(q, k, v, window)
    if wrong != "no_gate":
        a = a * jax.nn.sigmoid(lin(x if wrong == "gate_on_x" else h,
                                   p["gate_weight"], precision))
    return lin(a, p["o_weight"], precision)


def route(p, h2, z, wrong=None):
    """(chosen experts (T, k), their weights (T, k)), float32."""
    s = jax.nn.sigmoid(jnp.dot(h2, p["router_weight"].T, precision=HI))
    choice = s if wrong == "no_select_bias" else s + p["router_bias"]
    topi = lax.top_k(choice, z["top_k"])[1]
    topv = jnp.take_along_axis(
        choice if wrong == "bias_in_weights" else s, topi, axis=-1)
    if z["route_norm"] and wrong != "no_renorm":
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if wrong != "no_route_scale":
        topv = topv * z["route_scale"]
    return topi, topv


def routed(p, h2, z, precision, wrong=None, first=None, held=None):
    """(the part of the routed sum that experts ``first .. first + held
    - 1`` give — the configuration's own share where not said — and the
    chosen experts), an expert at a time.  ``p`` holds the weights of
    exactly those experts."""
    first = z["first"] if first is None else first
    held = z["held"] if held is None else held
    topi, wts = route(p, h2, z, wrong)
    # coef[t, j]: token t's weight for held expert j (0 if not chosen)
    here = first + jnp.arange(held)
    coef = jnp.sum(jnp.where(topi[:, :, None] == here[None, None, :],
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


def shared(p, h2, precision):
    return gated_ffn(h2, p["shared_gate_weight"], p["shared_up_weight"],
                     p["shared_down_weight"], precision)


def ffn(p, h2, z, precision, dense, wrong=None):
    """(the FFN branch's output ``y`` before its post-norm, the chosen
    experts or None)."""
    if dense:
        return gated_ffn(h2, p["ffn_gate_weight"], p["ffn_up_weight"],
                         p["ffn_down_weight"], precision), None
    y, topi = routed(p, h2, z, precision, wrong)
    if z["shared"] and wrong != "no_shared":
        y = y + shared(p, h2, precision)
    return y, topi


def _arithmetic(precision):
    """A mechanism left out is computed in float32."""
    return "float32" if precision in MECHANISMS else precision


def hidden(w, tokens, z, precision="float32"):
    """tokens (T,) -> (the last block's output (T, d), the chosen
    experts of every expert layer (L - dense, T, k), sorted)."""
    wrong = precision if precision in MECHANISMS else None
    precision = _arithmetic(precision)
    x = w["tok_embed_weight"].astype(jnp.float32)[tokens]
    if wrong != "no_embed_scale":
        x = x * z["embed_scale"]

    def add(x, out, gamma):         # what a branch leaves in the stream
        if wrong == "no_post_norm":
            return x + out
        if wrong == "post_norm_on_sum":
            return rms(x + out, gamma, z["eps"])
        return x + rms(out, gamma, z["eps"])

    chosen = []
    for i, (sliding, p) in enumerate(zip(z["sliding"], w["layers"])):
        h = rms(x, p["norm1_gamma"], z["eps"])
        x = add(x, attention(p, h, x, z, precision, sliding, wrong),
                p["post_norm1_gamma"])
        h2 = rms(x, p["norm2_gamma"], z["eps"])
        y, topi = ffn(p, h2, z, precision, i < z["dense"], wrong)
        if topi is not None:
            chosen.append(topi)
        x = add(x, y, p["post_norm2_gamma"])
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
    Logits are computed at the ``n_out`` served positions only, over
    the first :data:`CROP_ROWS` rows that hold them.

    Returns, per served position: the float32 reference's best logit
    minus its logit of the served token; minus its logit of the token
    that ``precision`` puts first there (zeros for float32); and whether
    any layer's top-k expert SET differs between float32 and
    ``precision`` at that position."""
    rows = min([r for r in CROP_ROWS if int(start) + n_out <= r]
               + [tokens.shape[0]])
    return _served_gaps(w, tokens[:rows], start, served, _static(cfg),
                        precision, n_out)
