"""The plain reference for the ``gpt2`` family, and its seeded weights.

GPT-2 as this repo's block computes it (``models/transformer.py``):
learned token and position tables, pre-LN residual blocks (LayerNorm
eps 1e-5, fused QKV projection packed ``[q | k | v]`` with the heads
contiguous inside each third, full causal multi-head attention scaled by
1/sqrt(head size), exact erf GELU, biases everywhere), a final LayerNorm
and an output head that is NOT tied to the token table.  Departures
from the published model are listed in the configuration files.

Plain float32 ``jax.numpy`` under ``precision=HIGHEST``: no kernels, no
cache, no batching tricks; layers are stacked and scanned so that it
compiles in seconds.  It imports nothing of ``mxnet_tpu`` and takes
nothing the program has made: the weights come from ``--seed`` through
:func:`draw`, which hands the SAME values to the program (in the types
it serves or trains them in, by the program's parameter names) and to
the reference (stacked, float32).

``precision`` selects the arithmetic of the linear layers, for the
controls of "How ``correct`` is decided": ``float32`` is the reference;
``fp8`` (e4m3, one scale per tensor, forward and backward operands) is
what a lower-precision path would compute.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

LAYER_KEYS = ("ln1_gamma", "ln1_beta", "qkv_weight", "qkv_bias",
              "proj_weight", "proj_bias", "ln2_gamma", "ln2_beta",
              "ff1_weight", "ff1_bias", "ff2_weight", "ff2_bias")
TOP_KEYS = ("tok_embed_weight", "pos_embed_weight", "ln_f_gamma",
            "ln_f_beta", "head_weight", "head_bias")
HI = lax.Precision.HIGHEST
LN_EPS = 1e-5
ADAM = dict(beta1=0.9, beta2=0.999, epsilon=1e-8)


def sizes(cfg):
    """(layers, d_model, heads, d_ff, vocab, positions) of a config."""
    d = int(cfg["n_embd"])
    return (int(cfg["n_layer"]), d, int(cfg["n_head"]),
            int(cfg.get("n_inner") or 4 * d), int(cfg["vocab_size"]),
            int(cfg["n_positions"]))


def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def _shapes(cfg):
    L, d, _, dff, V, P = sizes(cfg)
    layer = {"ln1_gamma": (d,), "ln1_beta": (d,),
             "qkv_weight": (3 * d, d), "qkv_bias": (3 * d,),
             "proj_weight": (d, d), "proj_bias": (d,),
             "ln2_gamma": (d,), "ln2_beta": (d,),
             "ff1_weight": (dff, d), "ff1_bias": (dff,),
             "ff2_weight": (d, dff), "ff2_bias": (d,)}
    top = {"tok_embed_weight": (V, d), "pos_embed_weight": (P, d),
           "ln_f_gamma": (d,), "ln_f_beta": (d,),
           "head_weight": (V, d), "head_bias": (V,)}
    return L, layer, top


@functools.partial(jax.jit, static_argnames=("cfg_key", "embed_dtype",
                                             "dtype"))
def _draw(key, cfg_key, embed_dtype, dtype):
    """One program makes every tensor on the device: N(0, std) weight
    matrices (residual projections scaled by 1/sqrt(2 L)), unit gains,
    zero biases; each rounded to the type it is held in."""
    cfg = dict(cfg_key)
    L, layer, top = _shapes(cfg)
    std = float(cfg["initializer_range"])
    resid = 1.0 / math.sqrt(2.0 * L)

    def make(name, shape, k, stacked):
        full = (L,) + shape if stacked else shape
        if name.endswith("_gamma"):
            x = jnp.ones(full, jnp.float32)
        elif name.endswith(("_beta", "_bias")):
            x = jnp.zeros(full, jnp.float32)
        else:
            scale = std * (resid if name in ("proj_weight", "ff2_weight")
                           else 1.0)
            x = scale * jax.random.normal(k, full, jnp.float32)
        held = embed_dtype if name == "tok_embed_weight" else dtype
        return x.astype(held)

    names = list(layer) + list(top)
    keys = jax.random.split(key, len(names))
    out = {}
    for name, k in zip(names, keys):
        shape = layer.get(name) or top[name]
        out[name] = make(name, shape, k, name in layer)
    return out


def draw(cfg, seed, embed_dtype="bfloat16", dtype="bfloat16"):
    """The seeded weights: ``{"layers": {key: (L, ...)}, top keys}`` in
    the types the program holds them in."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    flat = _draw(seed_key(seed), cfg_key, embed_dtype, dtype)
    return {"layers": {k: flat[k] for k in LAYER_KEYS},
            **{k: flat[k] for k in TOP_KEYS}}


def program_names(drawn):
    """The drawn weights by the program's parameter names
    (``layer3_qkv_weight`` ...), one array per layer."""
    L = drawn["layers"]["qkv_weight"].shape[0]
    out = {k: drawn[k] for k in TOP_KEYS}
    for i in range(L):
        for k in LAYER_KEYS:
            out[f"layer{i}_{k}"] = drawn["layers"][k][i]
    return out


@jax.jit
def to_float32(drawn):
    """What the reference computes on: the same values, float32."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), drawn)


# ---------------------------------------------------------------------
# arithmetic of the linear layers, by precision
# ---------------------------------------------------------------------

def _fq(x):
    """Round to fp8 e4m3 with one scale per tensor (max |x| -> 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _dot_nt(x, w):
    """x (..., k) @ w (n, k)^T in float32, HIGHEST."""
    return jnp.einsum("...k,nk->...n", x, w, precision=HI)


def _quantized_linear(q):
    """A linear layer whose three products (forward, and both of the
    backward) take operands rounded by ``q``."""

    @jax.custom_vjp
    def linear(x, w):
        return _dot_nt(q(x), q(w))

    def fwd(x, w):
        xq, wq = q(x), q(w)
        return _dot_nt(xq, wq), (xq, wq)

    def bwd(res, g):
        xq, wq = res
        gq = q(g)
        dx = jnp.einsum("...n,nk->...k", gq, wq, precision=HI)
        dw = jnp.einsum("...n,...k->nk", gq, xq, precision=HI)
        return dx, dw

    linear.defvjp(fwd, bwd)
    return linear


LINEAR = {"float32": _dot_nt, "fp8": _quantized_linear(_fq)}


# ---------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------

def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * g + b


def _block(x, p, heads, lin):
    B, T, d = x.shape
    D = d // heads
    h = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = lin(h, p["qkv_weight"]) + p["qkv_bias"]
    q, k, v = (t.reshape(B, T, heads, D) for t in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HI).reshape(B, T, d)
    x = x + lin(o, p["proj_weight"]) + p["proj_bias"]
    h = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    h = jax.nn.gelu(lin(h, p["ff1_weight"]) + p["ff1_bias"],
                    approximate=False)
    return x + lin(h, p["ff2_weight"]) + p["ff2_bias"]


def hidden(w, tokens, heads, precision="float32"):
    """tokens (B, T) int -> the final LayerNorm's output (B, T, d)."""
    lin = LINEAR[precision]
    T = tokens.shape[1]
    x = w["tok_embed_weight"][tokens] + w["pos_embed_weight"][:T]

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, heads, lin), None

    x, _ = lax.scan(body, x, w["layers"])
    return _ln(x, w["ln_f_gamma"], w["ln_f_beta"])


def logits(w, h, precision="float32"):
    return LINEAR[precision](h, w["head_weight"]) + w["head_bias"]


def token_losses(w, tokens, labels, heads, precision="float32"):
    """Next-token cross-entropy per position, (B, T)."""
    z = logits(w, hidden(w, tokens, heads, precision), precision)
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return lse - picked


# ---------------------------------------------------------------------
# serving: teacher-forced logit gaps
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("heads", "precision", "n_out"))
def served_gaps(w, tokens, start, served, heads, precision, n_out):
    """One request, teacher-forced.  ``tokens`` (1, T): prompt + served
    tokens, padded; ``start``: index of the position that predicts the
    first served token; ``served`` (n_out,): the served tokens, padded.

    Returns, per served position: the float32 reference's best logit
    minus its logit of the served token, and minus its logit of the
    token that ``precision`` puts first there (the control's reading;
    all zeros when ``precision`` is float32)."""
    h = hidden(w, tokens, heads, "float32")
    rows = lax.dynamic_slice_in_dim(h[0], start, n_out, axis=0)
    z = logits(w, rows, "float32")
    best = jnp.max(z, axis=-1)
    gap_served = best - jnp.take_along_axis(z, served[:, None], -1)[:, 0]
    if precision == "float32":
        first = jnp.argmax(z, axis=-1)
    else:
        hl = hidden(w, tokens, heads, precision)
        rl = lax.dynamic_slice_in_dim(hl[0], start, n_out, axis=0)
        first = jnp.argmax(logits(w, rl, precision), axis=-1)
    gap_low = best - jnp.take_along_axis(z, first[:, None], -1)[:, 0]
    return gap_served, gap_low


# ---------------------------------------------------------------------
# training: loss, gradient, Adam, one program per step
# ---------------------------------------------------------------------

def _leaf_sq(tree):
    """Sum of squares per leaf; per layer for the stacked leaves."""
    out = {k: jnp.sum(jnp.square(tree[k])) for k in TOP_KEYS}
    out["layers"] = {
        k: jnp.sum(jnp.square(v).reshape(v.shape[0], -1), axis=1)
        for k, v in tree["layers"].items()}
    return out


@functools.partial(jax.jit, static_argnames=("heads", "precision", "micro"),
                   donate_argnums=(0, 1, 2))
def train_step(w, m, v, t, tokens, lr, heads, precision, micro):
    """One Adam step on ``tokens`` (B, T+1): the gradient of the summed
    cross-entropy over B rows divided by B (MXNet's default
    ``rescale_grad`` = 1 / batch), accumulated over blocks of ``micro``
    rows so that it fits beside nothing else.

    Returns (w, m, v, mean loss, squared norm of the gradient per leaf,
    the loss per token (B, T)).
    """
    B, T1 = tokens.shape
    blocks = tokens.reshape(B // micro, micro, T1)

    def loss_fn(w, blk):
        ls = token_losses(w, blk[:, :-1], blk[:, 1:], heads, precision)
        return jnp.sum(ls), ls

    def one(carry, blk):
        g_acc, l_acc = carry
        (l, ls), g = jax.value_and_grad(loss_fn, has_aux=True)(w, blk)
        return (jax.tree_util.tree_map(jnp.add, g_acc, g), l_acc + l), ls

    zero = jax.tree_util.tree_map(jnp.zeros_like, w)
    (g, loss), per_token = lax.scan(one, (zero, jnp.float32(0)), blocks)
    g = jax.tree_util.tree_map(lambda a: a / B, g)
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["epsilon"]
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    w = jax.tree_util.tree_map(
        lambda p, a, b: p - lr_t * a / (jnp.sqrt(b) + eps), w, m, v)
    return (w, m, v, loss / (B * (T1 - 1)), _leaf_sq(g),
            per_token.reshape(B, T1 - 1))


@jax.jit
def change_sq(w, w0):
    """Squared norm of w - w0 per leaf."""
    return _leaf_sq(jax.tree_util.tree_map(jnp.subtract, w, w0))


def by_program_names(leaf_tree):
    """A per-leaf tree of scalars (``_leaf_sq``'s shape) as a flat dict
    of Python floats by the program's parameter names."""
    host = jax.device_get(leaf_tree)
    out = {k: float(host[k]) for k in TOP_KEYS}
    for k, vec in host["layers"].items():
        for i, x in enumerate(vec):
            out[f"layer{i}_{k}"] = float(x)
    return out


def train_three(cfg, seed, batches, lr, precision="float32", micro=2,
                steps=3):
    """Follow the first ``steps`` training steps from the seeded
    weights.  ``batches`` (n, B, T+1) int32, rotated.  Returns the
    losses, the first step's loss per token, the first gradient's norm
    per leaf and the norm of the parameters' change per leaf."""
    heads = sizes(cfg)[2]
    w = to_float32(draw(cfg, seed, embed_dtype="float32"))
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, grad_norm, first_token_losses = [], None, None
    for i in range(steps):
        w, m, v, loss, gsq, per_token = train_step(
            w, m, v, jnp.float32(i + 1), batches[i % len(batches)],
            jnp.float32(lr), heads=heads, precision=precision,
            micro=micro)
        losses.append(float(loss))
        if i == 0:
            grad_norm = {k: math.sqrt(x)
                         for k, x in by_program_names(gsq).items()}
            first_token_losses = jax.device_get(per_token)
    del m, v
    w0 = to_float32(draw(cfg, seed, embed_dtype="float32"))  # again: cheap
    change = {k: math.sqrt(x)
              for k, x in by_program_names(change_sq(w, w0)).items()}
    return {"losses": losses, "grad_norm": grad_norm,
            "change_norm": change, "token_losses": first_token_losses}
