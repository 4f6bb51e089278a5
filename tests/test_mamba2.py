"""The ``mamba2`` mixer and what came with it (``ops/hybrid.py``,
``ops/pallas_hybrid.py``, ``models/hybrid_lm.py``) against the plain
reference ``benchmark/reference/granitemoehybrid.py`` at small sizes,
seeded weights, float32: each op against the recurrence as written (both
bodies: the lax fallback and the Pallas kernels interpreted), the
router's second score, the shares of an expert-parallel layer, and the
engine — a prompt then decode through the slot — against the
reference's full forward.  A file of its own, so that an xdist worker
of its own takes the interpreted cases."""

import copy
import hashlib
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.kv_cache import state_pool_shape  # noqa: E402
from mxnet_tpu.models.hybrid_lm import HybridSpec, mixer_state  # noqa: E402
from mxnet_tpu.ops import hybrid  # noqa: E402

from benchmark.reference import granitemoehybrid as ref  # noqa: E402
from _engines import WAIT, Family, run_op  # noqa: E402

# the published shape at a size a test can hold: the first five entries
# of the layer pattern (attention second, so that mamba layers lie on
# both sides of it), 16 query heads over 2 KV heads, a state that is
# NOT square (8 x 16 a head), 4 of 12 experts held, 3 a token, a shared
# expert of a width of its own, the four multipliers, a tied head
CFG = {
    "family": "granitemoehybrid", "hidden_size": 64,
    "num_hidden_layers": 4, "num_hidden_layers_published": 40,
    "layer_types": ["mamba", "attention", "mamba", "mamba", "mamba"],
    "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 8,
    "attention_multiplier": 0.1, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "mamba_n_heads": 16, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "num_local_experts": 4,
    "num_local_experts_published": 12, "first_expert": 0,
    "num_experts_per_tok": 3, "intermediate_size": 32,
    "shared_intermediate_size": 48, "vocab_size": 96,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "initializer_range": 0.02,
}


# -- Mamba2Chunk = Mamba2Step token by token = the recurrence as written --

def mamba2_plain(x, bm, cm, a, dt, d_skip):
    """S_t = a_t S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t, a
    head's state (P, N); float64."""
    T, H, P = x.shape
    S = np.zeros((H, P, bm.shape[-1]))
    out = np.zeros((T, H, P))
    for t in range(T):
        S = a[t][:, None, None] * S \
            + (dt[t][:, None] * x[t])[:, :, None] * bm[t][None, None, :]
        out[t] = S @ cm[t] + d_skip[:, None] * x[t]
    return out, S


# (T, n): padded (n < T) and not; lengths that are and are not whole
# chunks of 128, and one that ends a token into the third chunk
_LENGTHS = [(1, 1), (12, 9), (127, 127), (128, 128), (200, 129),
            (256, 256), (300, 257)]


@pytest.mark.parametrize("T, n", _LENGTHS,
                         ids=[f"T{T}-n{n}" for T, n in _LENGTHS])
def test_mamba2_chunk_is_step_by_step_is_the_recurrence(kernels, T, n):
    rng = np.random.default_rng(0)
    f32 = lambda v: np.asarray(v, np.float32)
    H, P, N = 8, 8, 16
    xbc = f32(rng.standard_normal((1, T, H * P + 2 * N)))
    dt_raw = f32(rng.standard_normal((1, T, H)))
    a_log = f32(np.log(rng.uniform(1, 16, H)))
    dt_bias = f32(rng.uniform(-3, 1, H))
    d_skip = f32(rng.standard_normal(H))
    pool = f32(rng.standard_normal(state_pool_shape(3, (H, P, N))))  # dirty
    attrs = dict(num_heads=H, d_state=N)

    y, pool_c = run_op("Mamba2Chunk", [xbc, dt_raw, a_log, dt_bias, d_skip,
                                       pool, [2], [n]], **attrs)
    x, bm, cm = (np.asarray(t)[0] for t in
                 hybrid.mamba2_split(jnp.asarray(xbc), H, N))
    dt, la = (np.asarray(t)[0].astype(np.float64) for t in
              hybrid.mamba2_gates(jnp.asarray(dt_raw), jnp.asarray(a_log),
                                  jnp.asarray(dt_bias)))
    assert la.max() < 0 and la.min() < -3       # decays weak and strong
    want, S = mamba2_plain(x[:n].astype(np.float64), bm[:n], cm[:n],
                           np.exp(la[:n]), dt[:n], d_skip)
    tol = 2e-5 * max(1.0, np.abs(want).max())
    np.testing.assert_allclose(np.asarray(y)[0, :n], want.reshape(n, -1),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(pool_c)[2], S, atol=tol)
    # slots nobody named are untouched
    np.testing.assert_array_equal(np.asarray(pool_c)[:2], pool[:2])
    if T > 12:
        return
    # the small cases token by token through Mamba2Step as well; the
    # slot was dirty and is overwritten: step by step from zero
    pool_s = jnp.asarray(pool).at[1].set(0.0)
    got = []
    for t in range(n):
        o, pool_s = run_op(
            "Mamba2Step", [xbc[:, t:t + 1], dt_raw[:, t:t + 1], a_log,
                           dt_bias, d_skip, pool_s, [1], [t + 1]], **attrs)
        got.append(np.asarray(o)[0, 0])
    np.testing.assert_allclose(np.stack(got), want.reshape(n, -1), atol=tol)
    np.testing.assert_allclose(np.asarray(pool_s)[1], S, atol=tol)


def test_short_conv_bias_and_gate_before_the_norm():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, 6, 5)).astype(np.float32)
    w = rng.standard_normal((5, 4)).astype(np.float32)
    b = rng.standard_normal(5).astype(np.float32)
    pool = np.zeros((2, 8, 128), np.float32)
    plain, _ = run_op("ShortConv", [x, w, pool, [1], [6]], step=False)
    biased, _ = run_op("ShortConv", [x, w, pool, [1], [6], b], step=False,
                       bias=True)
    xp = np.concatenate([np.zeros((3, 5), np.float32), x[0]])
    lin = sum(xp[j:j + 6] * w[:, j] for j in range(4))
    silu = lambda v: v / (1 + np.exp(-v))
    np.testing.assert_allclose(np.asarray(plain)[0], silu(lin), atol=1e-5)
    np.testing.assert_allclose(np.asarray(biased)[0], silu(lin + b),
                               atol=1e-5)
    # RMSNorm(y * SiLU(z)) over ALL channels, against norm-then-sigmoid
    y = rng.standard_normal((2, 3, 16)).astype(np.float32)
    z = rng.standard_normal((2, 3, 16)).astype(np.float32)
    g = rng.standard_normal(16).astype(np.float32)
    got, = run_op("GatedRMSNorm", [y, z, g], gate="silu_first", eps=1e-5)
    t = y * silu(z)
    want = t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    other, = run_op("GatedRMSNorm", [y, z, g], eps=1e-5)
    assert np.abs(np.asarray(other) - want).max() > 0.1
    with pytest.raises(MXNetError, match="neither"):
        run_op("GatedRMSNorm", [y, z, g], gate="tanh")


# -- MoEFFN: a softmax over the chosen logits; the shares add up ----------

def moe_layer(cfg, seed=3):
    z = ref.sizes(cfg)
    drawn = ref.draw(cfg, seed, embed_dtype="float32", dtype="float32")
    return z, {k: np.array(v) for k, v in drawn["layers"][0].items()}


def routed(p, h, first, held, top_k, n):
    out, counters = run_op(
        "MoEFFN", [h, p["router_weight"], p["experts_gate_weight"][held],
                   p["experts_up_weight"][held],
                   p["experts_down_weight"][held], [n],
                   np.zeros(4, np.int32)],
        top_k=top_k, first_expert=first, step=False, count=True,
        score="softmax_topk")
    return np.asarray(out), np.asarray(counters)


def test_softmax_over_the_top_k_logits_against_the_reference(kernels):
    z, p = moe_layer(CFG)
    rng = np.random.default_rng(4)
    h = rng.standard_normal((1, 24, z["d"])).astype(np.float32)
    pj = {k: jnp.asarray(v) for k, v in p.items()}
    topi, wts = hybrid.moe_route(jnp.asarray(h[0]), pj["router_weight"],
                                 z["top_k"], "softmax_topk")
    ti, tw = ref.route(pj, jnp.asarray(h[0]), z)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(ti))
    np.testing.assert_allclose(np.asarray(wts), np.asarray(tw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 1.0, atol=1e-6)
    # ... and they are NOT the normalised sigmoid scores
    _, sig = hybrid.moe_route(jnp.asarray(h[0]), pj["router_weight"],
                              z["top_k"])
    assert np.abs(np.asarray(sig) - np.asarray(wts)).max() > 1e-3
    with pytest.raises(MXNetError, match="router score"):
        hybrid.moe_route(jnp.asarray(h[0]), pj["router_weight"], 3, "max")
    got, _ = routed(p, h, 0, slice(None), z["top_k"], 20)
    want, _ = ref.moe(pj, jnp.asarray(h[0]), z, "float32")
    shared = ref.gated_ffn(jnp.asarray(h[0]), pj["shared_gate_weight"],
                           pj["shared_up_weight"], pj["shared_down_weight"],
                           "float32")
    np.testing.assert_allclose(got[0, :20], np.asarray(want - shared)[:20],
                               atol=1e-5)


def test_two_shares_add_up_to_the_uncut_layer():
    """Each chip of the 2-way deployment adds its own experts' part;
    with what both compute alike (the shared expert) counted once, the
    parts are the whole layer."""
    whole = dict(CFG, num_local_experts=12)            # nothing cut
    z, p = moe_layer(whole)
    rng = np.random.default_rng(5)
    h = rng.standard_normal((1, 16, z["d"])).astype(np.float32)
    pj = {k: jnp.asarray(v) for k, v in p.items()}
    want, _ = ref.moe(pj, jnp.asarray(h[0]), z, "float32")
    total = np.asarray(ref.gated_ffn(
        jnp.asarray(h[0]), pj["shared_gate_weight"], pj["shared_up_weight"],
        pj["shared_down_weight"], "float32"))
    pairs = 0
    for first in (0, 6):
        part, counters = routed(p, h, first, slice(first, first + 6),
                                z["top_k"], 16)
        total = total + part[0]
        pairs += counters[0]
        assert counters[0] + counters[1] == 16 * z["top_k"]
    assert pairs == 16 * z["top_k"]          # every pair on one chip
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5)


# -- the spec is data; the engine serves it; Solar is as it was -----------

def test_spec_round_trips_and_sizes_its_slots_by_the_mixer():
    spec = ref.spec(CFG)
    again = HybridSpec.from_dict(copy.deepcopy(spec.to_dict()))
    assert again.to_dict() == spec.to_dict()
    assert again.symbol("decode").list_arguments() == \
        spec.symbol("decode").list_arguments()
    assert spec.mixer_kinds() == ("mamba2", "attention", "mamba2", "mamba2")
    assert spec.cache_kinds() == ("slots", "pages", "slots", "slots")
    assert (spec.embed_scale, spec.residual_scale, spec.logits_scale,
            spec.tied_head) == (12.0, 0.22, 1 / 16, True)
    assert mixer_state(spec.layers[0]["mixer"]) == ((16, 8, 16), (8, 128))
    assert mixer_state(spec.layers[1]["mixer"]) is None
    pools = {n: s for n, s, _, _ in spec.pools(9, 4, 4, "float32")}
    assert pools["layer0_state"] == (4, 16, 8, 16)      # not square
    assert pools["layer0_tail"] == (4, 8, 128)
    assert "layer1_kpool" in pools and "layer1_state" not in pools
    args = spec.symbol("prefill").list_arguments()
    assert "head_weight" not in args and "layer0_conv_bias" in args
    # a published config's own mixer sizes: (128, 64, 128) a slot
    full = dict(kind="mamba2", heads=128, head_dim=64, d_state=128, conv=4)
    # (its convolution carries 8,448 channels: 3 rows of them in (8, 3200))
    assert mixer_state(full) == ((128, 64, 128), (8, 3200))
    two = copy.deepcopy(spec.to_dict())
    two["layers"][0]["mixer"]["groups"] = 2
    with pytest.raises(MXNetError, match="one group"):
        HybridSpec.from_dict(two)


def test_solar_symbols_are_what_they_were():
    """The hybrid family grew a mixer, a router score, four multipliers
    and a tied head as DATA: a spec that names none of them builds the
    symbols it built before (their JSON, hashed on the parent).  The
    prefill symbol has since gained the last-row gather before the final
    norm (three nodes, for every spec of the family; node by node against
    the parent's: ``test_smallthinker.py``): its hash is that PR's."""
    from benchmark.reference import solar_open2
    from test_hybrid_lm import CFG as SOLAR

    from mxnet_tpu.name import NameManager

    spec = solar_open2.spec(SOLAR)
    got = {}
    for ph in ("prefill", "decode"):
        with NameManager():     # unnamed nodes count from 0
            got[ph] = hashlib.sha256(spec.symbol(
                ph, kv_block=4).tojson().encode()).hexdigest()[:16]
    assert got == {"prefill": "909416b8ce622db8",
                   "decode": "e2ddb52657a71e28"}


FAMILY = Family(ref, CFG, pad=96, max_len=96, kv_block=4, max_streams=3,
                decode_buckets=(1, 2, 4), cache_buckets=(8, 24),
                prefill_buckets=(16, 32, 96))


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


def test_engine_prompt_then_decode_is_the_references_full_forward(kernels):
    """Five streams through three slots, two of them asked for their
    state: served tokens are the reference's best at every position
    (logits, teacher-forced through its full forward), and a slot at
    retirement holds the reference scan's last state."""
    # an engine a body: its programs are the body's
    eng, drawn = FAMILY.engine()
    ps = FAMILY.prompts(np.random.default_rng(8), (9, 20, 13, 27, 6))
    with eng:
        futs = [eng.submit(p, max_new_tokens=24, return_state=(i % 2 == 1))
                for i, p in enumerate(ps)]
        outs = [f.result(timeout=WAIT) for f in futs]
        st = eng.stats()
    assert st["state_slots"] == 3 and st["state_slots_live"] == 0
    assert st["moe_pairs_here"] + st["moe_pairs_elsewhere"] == \
        st["stream_steps"] * CFG["num_experts_per_tok"] * 4
    for i, (p, out) in enumerate(zip(ps, outs)):
        tokens = out["tokens"] if i % 2 else out
        n = len(p) + len(tokens)
        seq = np.zeros(96, np.int32)    # padded: one trace for all five
        seq[:n] = np.concatenate([p, tokens])
        rows = FAMILY.logits(drawn, seq[:n])[len(p) - 1:-1]
        assert rows.max(-1).mean() - rows.mean() > 0.03   # logits spread
        gap = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
        assert gap.max() < 1e-4
        if not i % 2:
            continue
        want = ref.final_states(CFG, drawn, jnp.asarray(seq), n - 1)
        assert sorted(out["state"]) == sorted(want) == [
            "layer0_state", "layer2_state", "layer3_state"]
        for name, got in out["state"].items():
            assert got.shape == (16, 8, 16)
            np.testing.assert_allclose(
                got.transpose(0, 2, 1), np.asarray(want[name]), atol=2e-6)
            assert np.abs(got).max() > 1e-3
