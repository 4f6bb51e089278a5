"""The plain reference for the ``zaya`` family, and its seeded weights.

ZAYA1-8B as its public ``config.json``, the paper of its attention
("Compressed Convolutional Attention: Efficient Attention in a
Compressed Latent Space", arXiv:2510.04476) and the ZAYA1 report
("Training Foundation Models on a Full-Stack AMD Platform",
arXiv:2511.17127: the router, the residual scaling) give it, cut to one
stage of a stated pipeline (the configuration file says which, and lists
what the config does not fix under ``assumed``): pre-norm residual
blocks, RMSNorm (``rms_norm_eps``), no bias on a linear map, the head
tied to the token table.  On the residual stream ``x`` (T, d), d =
``hidden_size``; H / J = ``num_attention_heads`` /
``num_key_value_heads`` heads of D = ``head_dim``, G = H / J; every one
of the layers alike:

* ``h = RMSNorm(x; g1)``; the LATENT ``q~ = h W_q`` (H x D), ``k~ = h
  W_k`` (J x D); ``u = [q~ ; k~]``, H + J heads of D;
* two causal convolutions along the sequence of 2 taps each
  (``cca_time0``, ``cca_time1``), zeros before the first token:
  depthwise ``c_t = a0 . u_t + a1 . u_(t-1)``, then grouped by head
  ``e_t[j] = B0_j c_t[j] + B1_j c_(t-1)[j]`` (B: D x D a head and tap);
* the q-k mean of the rows BEFORE the convolutions: ``m^q[i] = (q~[i] +
  k~[i // G]) / 2``, ``m^k[g] = (mean_{i in g} q~[i] + k~[g]) / 2``;
  ``q = e[q part] + m^q``, ``k = e[k part] + m^k``;
* a head's norm and temperature: ``q <- sqrt(D) q / |q|_2``, ``k <-
  tau_g sqrt(D) k / |k|_2`` (tau: one learned positive number a KV head);
* rotation: the first ``partial_rotary_factor x D`` lanes of each head of
  q and k, pairs ``(i, i + R/2)`` turned by ``t * theta^(-2i/R)``
  (rotate-half), the other lanes carry no position;
* the value shift: ``v_t = [h_t W_v1 ; h_(t-1) W_v2]`` (J D / 2 lanes
  each, ``h_(-1) = 0``), read as J heads of D;
* ``y = softmax(q k^T / sqrt(D)) v``, causal, query head i on KV head ``i
  // G``, all inside the latent; ``o = y W_o`` (H D -> d);
* the residual path, either half: ``x <- (a_r . x + b_r) + (a_o . f(h) +
  b_o)``, four learned vectors of d;
* ``h2 = RMSNorm(x'; g2)``; the router, all float32: ``r_l = h2 W_r``
  (d -> ``router_hidden_size``), ``r_l <- r_l + gamma_l . r_(l-1)`` (the
  hidden row of the layer before; the first layer carries nothing), ``s =
  W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l)))`` over ALL ``num_experts``, ``p =
  softmax(s)``, ``e* = argmax(p + b)`` (b: the balancing bias, the
  choice only), ``y = p_(e*) W_down,e*(silu(W_gate,e* h2) * W_up,e* h2)``
  — ONE expert a token, weighed by its probability un-normalised; no
  shared expert;
* ``logits = RMSNorm(x_L; g_f) E^T`` (``tie_word_embeddings``).

Departures from the two papers that the builder knows of (each also
under ``assumed`` in the configuration file, with its reason): the
latent's widths are the config's ``heads x head_dim`` and ``kv_heads x
head_dim``; the second convolution is grouped by HEAD (the paper groups
channels; the group's size is not in the config) and both run over q and
k alike, with no non-linearity between; the q-k mean under grouped heads
is the form above (the paper writes it for equal head counts); the
norm's sqrt(D) and the temperature on the keys alone; the value shift as
two projections of half the value lanes each; the router's depth of
three with exact GELU, its RMSNorm, the carry's form; the chosen
expert's weight un-normalised; the bias moving the choice alone; the
residual scales' form; and the initialisation (:func:`_draw`), which a
speed and agreement benchmark needs only to be seeded — and to let each
mechanism carry a share a control can see.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
cache, no tail (the whole sequence is there: ``u_(t-1)`` is a shift), no
batching; attention a block of queries at a time (``afmoe.attend``), an
expert at a time over every token; nothing else regrouped.  It imports
nothing of ``mxnet_tpu`` but the spec class (:func:`spec`).  Weights are
HELD as drawn and cast to float32 where they are multiplied.

``precision`` selects the arithmetic, for the controls: ``float32`` is
the reference; ``fp8`` computes every linear layer (the grouped
convolution, the experts and the head included; the router stays
float32, as in the program) in e4m3 with one scale per tensor;
``bfloat16`` multiplies in bfloat16; ``bfloat16_held`` also HOLDS in
bfloat16 what a program of that compute type holds there — every
activation a layer hands on: the normed rows, a linear map's output, q, k
and v as attention reads them, the residual stream after each add (the
router stays float32 on those rows) — the seed's own yardstick of what
the configuration's precision costs (``runners/serve_pages_relative``).
And it names ONE mechanism left out or misplaced, each in float32
(:data:`MECHANISMS`) — what a program without it would serve.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.afmoe import attend
from benchmark.reference.solar_open2 import (  # noqa: F401
    HI, lin, mm, program_names, rms, seed_key, to_float32)

# mechanisms a control leaves out or misplaces
MECHANISMS = (
    "no_conv",            # e = u: neither convolution
    "no_qk_mean",         # q, k = the convolutions' output alone
    "value_current",      # the value's second half from the CURRENT token
    "no_rotation",        # no lane rotates
    "rotate_all",         # all D lanes of a head rotate
    "no_temperature",     # tau = 1
    "no_carry",           # the router's hidden row is not carried
    "weight_one",         # the chosen expert's weight 1.0, not p
    "no_select_bias",     # the choice made by p, not p + b
    "no_residual_scale",  # x + f(h): the four learned vectors left out
)
NORM_EPS = 1e-6     # under the root of a head's summed squares (assumed)
# the router's last map is drawn this many times its fan-in scale
# (``assumed.initialisation``): logits of order one
ROUTER_LOGIT_GAIN = 2.0


# ---------------------------------------------------------------------
# sizes, spec
# ---------------------------------------------------------------------

def sizes(cfg):
    L = int(cfg["num_hidden_layers"])
    D = int(cfg["head_dim"])
    rope = cfg["rope_parameters"]["hybrid"]
    return dict(
        L=L, d=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        eps=float(cfg["rms_norm_eps"]),
        Hq=int(cfg["num_attention_heads"]),
        Hkv=int(cfg["num_key_value_heads"]), D=D,
        taps=(int(cfg["cca_time0"]), int(cfg["cca_time1"])),
        theta=float(rope["rope_theta"]),
        R=int(round(D * float(rope["partial_rotary_factor"]))),
        E=int(cfg["num_experts"]), top_k=int(cfg["num_experts_per_tok"]),
        w=int(cfg["moe_intermediate_size"]),
        hidden=int(cfg["router_hidden_size"]),
        tied=bool(cfg["tie_word_embeddings"]),
        std=float(cfg.get("initializer_range", 0.02)),
        bias_std=float(cfg["selection_bias_std"]))


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def spec(cfg):
    """The model as ``mx.DecodeEngine(params, model=...)`` takes it.
    Raises at once on a program whose layer list knows no ``cca`` mixer:
    it would refuse the kind by name further on."""
    from mxnet_tpu.models import hybrid_lm

    if "cca" not in getattr(hybrid_lm, "MIXERS", {}):
        raise NotImplementedError(
            "this program's HybridSpec has no mixer kind 'cca' (and no "
            "'mlp' router): the zaya family cannot be served by it")
    z = sizes(cfg)
    if not z["tied"] or z["top_k"] != 1 or z["taps"] != (2, 2):
        raise ValueError(
            f"the zaya reference is written for a tied head, one expert a "
            f"token and two convolutions of two taps; got {z}")
    layer = {
        "mixer": {"kind": "cca", "heads": z["Hq"], "kv_heads": z["Hkv"],
                  "head_dim": z["D"], "conv": list(z["taps"]),
                  "rope_theta": z["theta"], "rotary_dim": z["R"]},
        "ffn": {"kind": "moe", "experts": z["E"], "top_k": z["top_k"],
                "width": z["w"], "score": "softmax", "select_bias": True,
                "router": {"kind": "mlp", "hidden": z["hidden"],
                           "carry": True}}}
    return hybrid_lm.HybridSpec(z["V"], z["d"], [layer] * z["L"],
                                norm_eps=z["eps"], tied_head=True,
                                learned_residual=True)


# ---------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------

FLOAT32_LEAVES = (
    "router_down_weight", "router_carry_gamma", "router_norm_gamma",
    "router_1_weight", "router_2_weight", "router_3_weight", "router_bias",
    "qk_norm_temperature")


def _layer_shapes(z, first):
    d, D, w, n = z["d"], z["D"], z["w"], z["hidden"]
    hd, kd = z["Hq"] * D, z["Hkv"] * D
    heads = z["Hq"] + z["Hkv"]
    out = dict(
        norm1_gamma=(d,), norm2_gamma=(d,), q_weight=(hd, d),
        k_weight=(kd, d), v1_weight=(kd - kd // 2, d),
        v2_weight=(kd // 2, d), o_weight=(d, hd),
        mix_conv0_weight=(hd + kd, 2), mix_conv1_weight=(heads, 2, D, D),
        qk_norm_temperature=(z["Hkv"],), res1_scales=(4, d),
        res2_scales=(4, d), router_down_weight=(n, d),
        router_carry_gamma=(n,), router_norm_gamma=(n,),
        router_1_weight=(n, n), router_2_weight=(n, n),
        router_3_weight=(z["E"], n), router_bias=(z["E"],),
        experts_gate_weight=(z["E"], d, w),
        experts_up_weight=(z["E"], d, w),
        experts_down_weight=(z["E"], w, d))
    if first:       # r_(-1) = 0: the first layer carries nothing
        del out["router_carry_gamma"]
    return out


@functools.partial(jax.jit, static_argnames=("static", "kind",
                                             "embed_dtype", "dtype"))
def _draw(key, static, kind, embed_dtype, dtype):
    """One program makes the tensors of one layer (``kind``: ``first``
    or ``layer``) or of the top (``kind`` None: the table, the last norm)
    on the device, a layer at a time.  N(0, std) matrices and unit gains,
    but where that draw would hide a mechanism (the configuration's
    ``assumed.initialisation`` gives each reason): the convolutions'
    taps uniform in +-2^-1/2 (depthwise) and N(0, (2 D)^-1/2) (grouped:
    the mix keeps a row's scale, and the previous token is half of it —
    N(0, 0.02) would leave it 2% of a row); the temperature log-uniform
    in 0.5..2; the residual scales a N(1, 0.1), b N(0, std); the
    router's matrices at fan-in scale (N(0, d^-1/2) down, N(0, (2 /
    hidden)^1/2) inside, ``ROUTER_LOGIT_GAIN`` x hidden^-1/2 out: logits
    of order one, so that the sixteen probabilities differ by token —
    N(0, 0.02) three times over leaves every probability 1/16 and the
    bias alone choosing; the rows of the two maps that read a GELU's
    output centred to zero sum, so that its positive mean adds no
    offset every token shares and the load spreads as a trained
    router's does under its balancing bias: uncentred, 128 rows hit 8 to
    15 of 16 experts with 4 x the mean on one), the carry's gain
    uniform in 0.3..0.7, the balancing bias N(0, bias_std); each rounded
    to the type it is held in (the router and the temperature
    float32)."""
    z = dict(static)
    d, n = z["d"], z["hidden"]

    def normal(k, shape, std, mean=0.0):
        return mean + std * jax.random.normal(k, shape, jnp.float32)

    def make(name, shape, k):
        if name == "router_carry_gamma":
            x = jax.random.uniform(k, shape, jnp.float32, 0.3, 0.7)
        elif name.endswith("_gamma"):
            x = jnp.ones(shape, jnp.float32)
        elif name == "mix_conv0_weight":
            x = jax.random.uniform(k, shape, jnp.float32, -2 ** -0.5,
                                   2 ** -0.5)
        elif name == "mix_conv1_weight":
            x = normal(k, shape, (2.0 * z["D"]) ** -0.5)
        elif name == "qk_norm_temperature":
            x = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                           math.log(0.5), math.log(2.0)))
        elif name in ("res1_scales", "res2_scales"):
            ka, kb = jax.random.split(k)
            a = normal(ka, (2, shape[1]), 0.1, 1.0)
            b = normal(kb, (2, shape[1]), z["std"])
            x = jnp.stack([a[0], b[0], a[1], b[1]])
        elif name == "router_down_weight":
            x = normal(k, shape, d ** -0.5)
        elif name in ("router_1_weight", "router_2_weight"):
            x = normal(k, shape, (2.0 / n) ** 0.5)
        elif name == "router_3_weight":
            x = normal(k, shape, ROUTER_LOGIT_GAIN * n ** -0.5)
        elif name == "router_bias":
            x = normal(k, shape, z["bias_std"])
        else:
            x = normal(k, shape, z["std"])
        if name in ("router_2_weight", "router_3_weight"):
            # a map that reads a GELU's output: rows of zero sum
            x = x - jnp.mean(x, axis=1, keepdims=True)
        if name in FLOAT32_LEAVES:
            return x
        return x.astype(embed_dtype if name == "tok_embed_weight"
                        else dtype)

    shapes = _layer_shapes(z, kind == "first") if kind else {
        "tok_embed_weight": (z["V"], z["d"]), "final_norm_gamma": (z["d"],)}
    return {nm: make(nm, s, k) for (nm, s), k in
            zip(shapes.items(), jax.random.split(key, len(shapes)))}


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights, ``{"layers": [{leaf: array}, ...], top
    leaves}``, in the types the program serves them in."""
    static = _static(cfg)
    L = sizes(cfg)["L"]
    keys = jax.random.split(seed_key(seed), L + 1)
    out = _draw(keys[-1], static, None, embed_dtype, dtype)
    out["layers"] = [_draw(k, static, "layer" if i else "first",
                           embed_dtype, dtype)
                     for i, k in enumerate(keys[:L])]
    return out


# ---------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------

def shift(x):
    """Row t - 1 at row t, zeros at row 0."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def rotate_span(x, theta, R):
    """x (T, heads, D), row t at position t: the first R lanes of every
    head in pairs (i, i + R/2) turned by ``t * theta^(-2i/R)``; the
    other D - R lanes as they are."""
    T = x.shape[0]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., R:]], axis=-1)


def mix(p, qt, kt, precision, wrong=None):
    """The latent mixed along the sequence: q~ (T, H, D), k~ (T, J, D)
    -> (q (T, H, D), k (T, J, D)) before the norm."""
    T, H, D = qt.shape
    J = kt.shape[1]
    u = jnp.concatenate([qt, kt], axis=1)                 # (T, H + J, D)
    if wrong == "no_conv":
        e = u
    else:
        a = p["mix_conv0_weight"].astype(jnp.float32).reshape(H + J, D, 2)
        c = a[..., 1] * u + a[..., 0] * shift(u)
        b = p["mix_conv1_weight"]                  # [head, tap, out, in]

        def grouped(rows, tap):     # a head at a time: rows[:, j] B_j^T
            return jax.vmap(lambda r, w: lin(r, w, precision),
                            in_axes=(1, 0), out_axes=1)(rows, b[:, tap])

        e = grouped(c, 1) + grouped(shift(c), 0)
    if wrong == "no_qk_mean":
        return e[:, :H], e[:, H:]
    qg = qt.reshape(T, J, H // J, D)
    mq = 0.5 * (qg + kt[:, :, None])
    mk = 0.5 * (jnp.mean(qg, axis=2) + kt)
    return e[:, :H] + mq.reshape(T, H, D), e[:, H:] + mk


def unit(x, D):
    """Each head to length sqrt(D)."""
    return x * (lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                          + NORM_EPS) * D ** 0.5)


def as_held(precision):
    """What a tensor is after the compute type has held it: rounded to
    bfloat16 under ``bfloat16_held``, itself otherwise."""
    if precision == "bfloat16_held":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return lambda x: x


def attention(p, h, z, precision, wrong=None, held=lambda x: x):
    """The branch's output ``o`` (T, d)."""
    T = h.shape[0]
    H, J, D = z["Hq"], z["Hkv"], z["D"]
    qt = held(lin(h, p["q_weight"], precision)).reshape(T, H, D)
    kt = held(lin(h, p["k_weight"], precision)).reshape(T, J, D)
    q, k = mix(p, qt, kt, precision, wrong)
    q, k = unit(q, D), unit(k, D)
    if wrong != "no_temperature":
        k = k * p["qk_norm_temperature"][:, None]
    R = {"no_rotation": 0, "rotate_all": D}.get(wrong, z["R"])
    if R:
        q, k = rotate_span(q, z["theta"], R), rotate_span(k, z["theta"], R)
    v2 = lin(h, p["v2_weight"], precision)
    v = held(jnp.concatenate(
        [lin(h, p["v1_weight"], precision),
         v2 if wrong == "value_current" else shift(v2)], axis=-1))
    y = held(attend(held(q), held(k), v.reshape(T, J, D), 0))
    return held(lin(y, p["o_weight"], precision))


def router(p, h2, prev, z, wrong=None):
    """(the experts' logits (T, E), the hidden row (T, hidden) the next
    layer is handed), float32.  ``prev``: the row the layer before left
    (None: the first layer)."""
    def f32(x, w):
        return jnp.dot(x, w.astype(jnp.float32).T, precision=HI)

    r = f32(h2, p["router_down_weight"])
    if prev is not None and wrong != "no_carry":
        r = r + p["router_carry_gamma"] * prev
    x = rms(r, p["router_norm_gamma"], z["eps"])
    for name in ("router_1_weight", "router_2_weight"):
        x = jax.nn.gelu(f32(x, p[name]), approximate=False)
    return f32(x, p["router_3_weight"]), r


def route(p, s, wrong=None):
    """(the chosen expert (T,), its weight (T,)) from the logits."""
    prob = jax.nn.softmax(s, axis=-1)
    choice = prob if wrong == "no_select_bias" else prob + p["router_bias"]
    e = jnp.argmax(choice, axis=-1)
    wt = jnp.take_along_axis(prob, e[:, None], axis=-1)[:, 0]
    return e, (jnp.ones_like(wt) if wrong == "weight_one" else wt)


def routed(p, h2, e, wt, precision, held=lambda x: x):
    """``wt_t`` x the chosen expert of each token, an expert at a time
    over every token (the unchosen rows are multiplied by zero)."""
    E = p["experts_gate_weight"].shape[0]
    coef = jnp.where(e[None, :] == jnp.arange(E)[:, None], wt[None, :], 0.0)

    def one(acc, xs):
        wg, wu, wd, c = xs
        y = mm(held(jax.nn.silu(mm(h2, wg, precision))
                    * mm(h2, wu, precision)), wd, precision)
        return acc + c[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h2),
                      (p["experts_gate_weight"], p["experts_up_weight"],
                       p["experts_down_weight"], coef))
    return out


def _arithmetic(precision):
    """A mechanism left out is computed in float32; what is held in
    bfloat16 is multiplied in it."""
    if precision == "bfloat16_held":
        return "bfloat16"
    return "float32" if precision in MECHANISMS else precision


def hidden(w, tokens, z, precision="float32"):
    """tokens (T,) -> (the last block's output (T, d), the chosen expert
    of every layer (L, T, 1))."""
    wrong = precision if precision in MECHANISMS else None
    held = as_held(precision)
    precision = _arithmetic(precision)
    x = w["tok_embed_weight"][tokens].astype(jnp.float32)

    def add(x, out, scales):
        if wrong == "no_residual_scale":
            return x + out
        a_r, b_r, a_o, b_o = scales.astype(jnp.float32)
        return (a_r * x + b_r) + (a_o * out + b_o)

    chosen, prev = [], None
    for p in w["layers"]:
        h = held(rms(x, p["norm1_gamma"], z["eps"]))
        x = held(add(x, attention(p, h, z, precision, wrong, held),
                     p["res1_scales"]))
        h2 = held(rms(x, p["norm2_gamma"], z["eps"]))
        s, prev = router(p, h2, prev, z, wrong)
        e, wt = route(p, s, wrong)
        chosen.append(e[:, None])
        x = held(add(x, held(routed(p, h2, e, wt, precision, held)),
                     p["res2_scales"]))
    return x, jnp.stack(chosen)


def logits(w, rows, z, precision="float32"):
    rows = as_held(precision)(rms(rows, w["final_norm_gamma"], z["eps"]))
    return lin(rows, w["tok_embed_weight"], _arithmetic(precision))


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
    any layer's chosen expert differs between float32 and ``precision``
    at that position."""
    return _served_gaps(w, tokens, start, served, _static(cfg), precision,
                        n_out)
