"""The hybrid family's ops (``ops/hybrid.py``) against their plain forms
at small sizes, float32, both bodies of each: the lax fallback and the
Pallas kernel interpreted on the CPU (a file of its own so that a second
xdist worker shares the interpreted cases with ``test_hybrid_lm.py``'s
engine tests; ``KDAChunk``'s are a third file's, ``test_kda_chunk.py``)."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.kv_cache import conv_tail_shape  # noqa: E402
from mxnet_tpu.ops.registry import OpContext, get_op  # noqa: E402

from benchmark.reference import solar_open2 as ref  # noqa: E402
from _engines import run_op  # noqa: E402
from test_hybrid_lm import CFG  # noqa: E402


# -- ShortConv: the tail crosses the prefill / decode boundary -----------

def test_short_conv_tail_carried_across_prefill_decode_boundary(kernels):
    rng = np.random.default_rng(1)
    T, C, K, n = 10, 6, 4, 6
    x = rng.standard_normal((1, T, C)).astype(np.float32)
    w = rng.standard_normal((C, K)).astype(np.float32)
    pool = rng.standard_normal(conv_tail_shape(3, K, C)).astype(np.float32)
    whole, _ = run_op("ShortConv", [x, w, pool, [1], [T]], step=False)
    # prefill the first n (padded to T), then one token at a time
    head, pool2 = run_op("ShortConv", [x, w, pool, [2], [n]], step=False)
    got = [np.asarray(head)[0, :n]]
    for t in range(n, T):
        y, pool2 = run_op("ShortConv", [x[:, t:t + 1], w, pool2, [2],
                                        [t + 1]], step=True)
        got.append(np.asarray(y)[0])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(whole)[0],
                               atol=1e-6)
    # a prompt shorter than the kernel leaves zeros before it
    _, pool3 = run_op("ShortConv", [x, w, pool, [1], [2]], step=False)
    tail = np.asarray(pool3)[1].reshape(-1)[:(K - 1) * C].reshape(K - 1, C)
    np.testing.assert_array_equal(tail[0], 0.0)
    np.testing.assert_allclose(tail[1:], x[0, :2])


# -- MoEFFN ---------------------------------------------------------------

def moe_layer(cfg, seed=3):
    """One expert layer's drawn weights (float32) and its sizes."""
    z = ref.sizes(cfg)
    drawn = ref.draw(cfg, seed, embed_dtype="float32", dtype="float32")
    return z, {k: np.array(v) for k, v in drawn["layers"][1].items()}


def routed(p, h, first, held_slice, top_k, lengths, step=False):
    out, counters = run_op(
        "MoEFFN", [h, p["router_weight"],
                   p["experts_gate_weight"][held_slice],
                   p["experts_up_weight"][held_slice],
                   p["experts_down_weight"][held_slice], lengths,
                   np.zeros(4, np.int32)],
        top_k=top_k, first_expert=first, step=step, count=True)
    return np.asarray(out), np.asarray(counters)


def test_moe_ffn_uneven_routing_and_an_expert_without_tokens(kernels):
    cfg = dict(CFG, n_shared_experts=0)
    z, p = moe_layer(cfg)
    rng = np.random.default_rng(4)
    h = rng.standard_normal((1, 24, z["d"])).astype(np.float32)
    # held expert 1 is never chosen, held expert 2 by every token: a
    # constant feature, weighed against the one and for the other
    h[..., 0] = 3.0
    p["router_weight"][1, 0] = -50.0
    p["router_weight"][2, 0] = 5.0
    got, counters = routed(p, h, 0, slice(None), z["top_k"], [20])
    want, chosen = ref.moe({k: jnp.asarray(v) for k, v in p.items()},
                           jnp.asarray(h[0]), z, "float32")
    chosen = np.asarray(chosen)[:20]
    assert not (chosen == 1).any() and (chosen == 2).sum() > 10
    np.testing.assert_allclose(got[0, :20], np.asarray(want)[:20],
                               atol=1e-5)
    np.testing.assert_array_equal(got[0, 20:], 0.0)   # padding: no pair
    here = int((chosen < z["held"]).sum())
    loads = [(chosen == e).sum() for e in range(z["held"])]
    assert list(counters) == [here, 20 * z["top_k"] - here,
                              sum(1 for n in loads if n), max(loads)]


def _held_part(p, h, z, act):
    """``ref.moe``'s routed sum with the gate's activation ``act``: the
    reference itself for ``silu``, its formula for ``relu``."""
    pj = {k: jnp.asarray(v) for k, v in p.items()}
    want, chosen = ref.moe(pj, jnp.asarray(h), z, "float32")
    if act == "relu":
        topi, wts = (np.asarray(a) for a in ref.route(pj, jnp.asarray(h), z))
        want = np.zeros_like(h)
        for j in range(z["held"]):
            c = np.where(topi == z["first"] + j, wts, 0.0).sum(axis=1)
            y = (np.maximum(h @ p["experts_gate_weight"][j], 0.0)
                 * (h @ p["experts_up_weight"][j])) \
                @ p["experts_down_weight"][j]
            want = want + c[:, None] * y
    return np.asarray(want), np.asarray(chosen)


def _moe_case(T, top_k, experts, held, first=0, act="silu", d=128):
    """A layer of ``experts`` experts of which ``held`` are here, its
    weights scaled so that outputs are of order 0.1, and T token rows."""
    cfg = dict(CFG, n_shared_experts=0, hidden_size=d,
               num_experts_per_tok=top_k, n_routed_experts=held,
               n_routed_experts_published=experts, first_expert=first,
               initializer_range=0.2)
    z = ref.sizes(cfg)
    drawn = ref.draw(cfg, 3, embed_dtype="float32", dtype="float32")
    p = {k: np.array(v) for k, v in drawn["layers"][1].items()}
    h = np.random.default_rng(T + top_k).standard_normal(
        (T, d)).astype(np.float32)
    return z, p, h, act


def _nodes():
    from mxnet_tpu import profiler

    c = profiler.metrics_summary()["counters"]
    return tuple(int(c.get(f"moe.nodes_{body}", 0))
                 for body in ("indexed", "gathered"))


# (the case, whether its node takes its rows BY INDEX where the kernels
# are on — as a prompt, as a step): pairs at or past _tile_rows' line of
# 4,096 make tiles of 128 rows, and with at most half the experts held
# here the kernels fetch those rows themselves; with every expert held
# the rows are gathered and only the way out (slabs, the combine kernel)
# is the prompt's; below the line a decode step's 16-row tiles, gathered.
# Since PR 48 a PROMPT takes the 128-row tiles from 32 pairs a held
# expert on (a tile streams its expert's matrices whatever its rows): the
# two cases just below the line are a step's 16-row tiles and a prompt's
# 128 (the half-held one then by index), the two small ones lie below
# the prompt's line too, and the last case — one expert a token, every
# expert held — between the two lines
MOE_CASES = {
    "all_held_k6": (_moe_case(704, 6, 8, 8), False, False),
    "all_held_k6_below_the_line": (_moe_case(680, 6, 8, 8), False, False),
    "all_held_k6_below_both_lines": (_moe_case(40, 6, 8, 8), False, False),
    "thin_share_k8": (_moe_case(1024, 8, 32, 4, first=8), True, True),
    "half_held_relu_k10": (
        _moe_case(416, 10, 32, 16, act="relu"), True, True),
    "half_held_relu_k10_below_the_line": (
        _moe_case(400, 10, 32, 16, act="relu"), True, False),
    "half_held_relu_k10_below_both_lines": (
        _moe_case(48, 10, 32, 16, act="relu"), False, False),
    "all_held_k1_a_prompts_own_line": (
        _moe_case(160, 1, 4, 4), False, False),
}


@pytest.mark.parametrize("step", [False, True], ids=["prompt", "step"])
@pytest.mark.parametrize("case", list(MOE_CASES))
def test_moe_ffn_rows_by_index_and_gathered(kernels, case, step):
    """``MoEFFN`` against the reference on both sides of the tile line,
    every held share, k = 6, 8, 10 and both gates, with padding in both
    ``step`` forms: a prompt (1, T, d) whose last 9 positions lie past
    ``lengths``, and a step (T, 1, d) with every seventh row empty."""
    (z, p, h, act), *by_index = MOE_CASES[case]
    by_index = by_index[step]
    T, d = h.shape
    live = np.arange(T) % 7 != 3 if step else np.arange(T) < T - 9
    before = _nodes()
    got, counters = run_op(
        "MoEFFN", [h.reshape((T, 1, d) if step else (1, T, d)),
                   p["router_weight"], p["experts_gate_weight"],
                   p["experts_up_weight"], p["experts_down_weight"],
                   live.astype(np.int32) if step else [T - 9],
                   np.zeros(4, np.int32)],
        top_k=z["top_k"], first_expert=z["first"], step=step, count=True,
        act=act)
    got = np.asarray(got).reshape(T, d)
    want, chosen = _held_part(p, h, z, act)
    assert np.abs(want).max() > 0.05                  # something to hold
    np.testing.assert_allclose(got[live], want[live], atol=1e-5)
    np.testing.assert_array_equal(got[~live], 0.0)    # padding: no pair
    here = (chosen >= z["first"]) & (chosen < z["first"] + z["held"])
    nowhere = live & ~here.any(axis=1)
    if "thin" in case:
        assert nowhere.sum() > T // 8       # a quarter of the tokens
    np.testing.assert_array_equal(got[nowhere], 0.0)  # exactly
    assert counters[0] == here[live].sum()
    assert counters[0] + counters[1] == live.sum() * z["top_k"]
    took = tuple(a - b for a, b in zip(_nodes(), before))
    assert took == ((1, 0) if kernels and by_index else (0, 1))


def test_moe_ffn_an_expert_without_tokens_beside_one_with_all_by_index(
        kernels):
    """``test_moe_ffn_uneven_routing…``'s case at a prompt's size: a held
    expert no token chooses (no tile) beside one every token chooses."""
    z, p, h, _ = _moe_case(608, 8, 32, 8)
    h[:, 0] = 3.0
    p["router_weight"][1, 0] = -50.0
    p["router_weight"][2, 0] = 50.0
    got, counters = routed(p, h[None], 0, slice(None), z["top_k"], [600])
    want, chosen = _held_part(p, h, z, "silu")
    assert not (chosen[:600] == 1).any() and (chosen[:600] == 2).sum() == 600
    np.testing.assert_allclose(got[0, :600], want[:600], atol=1e-5)
    np.testing.assert_array_equal(got[0, 600:], 0.0)
    loads = [(chosen[:600] == e).sum() for e in range(z["held"])]
    assert list(counters)[2:] == [sum(1 for n in loads if n), 600]


def test_moe_ffn_by_index_makes_no_dispatched_copy(monkeypatch):
    """With the kernels on, a prompt's ``MoEFFN`` lowers to a program
    that holds no (M, d) array of dispatched rows and no (N, k, d) array
    of gathered outputs, in any type: the rows go in by index and come
    out as slabs."""
    import jax

    monkeypatch.setenv("MXNET_PALLAS", "1")
    z, p, h, _ = _moe_case(1024, 8, 32, 4, first=8, d=256)
    T, d = h.shape
    k, held = z["top_k"], z["held"]
    M = T * min(k, held) + held * 128

    def ffn(*inputs):
        return get_op("MoEFFN").compute(
            OpContext(is_train=False, rng=None),
            {"top_k": str(k), "first_expert": "8"}, list(inputs), [])[0]

    bf = jnp.bfloat16
    text = jax.jit(ffn).lower(
        jnp.asarray(h[None], bf), jnp.asarray(p["router_weight"]),
        *(jnp.asarray(p[f"experts_{w}_weight"], bf)
          for w in ("gate", "up", "down")),
        jnp.asarray([T], jnp.int32), jnp.zeros(4, jnp.int32)).as_text()
    assert f"tensor<{T}x{d}x" in text                 # the token rows
    assert f"tensor<{M}x{d}x" not in text
    assert f"tensor<{T}x{k}x{d}x" not in text
    assert f"tensor<{T * k}x{d}x" not in text


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each chip of the deployment adds its own experts' part; with what
    every chip computes alike (the shared expert) counted once, the
    parts are the whole layer."""
    whole = dict(CFG, n_routed_experts=32)            # nothing cut
    z, p = moe_layer(whole)
    rng = np.random.default_rng(5)
    h = rng.standard_normal((1, 16, z["d"])).astype(np.float32)
    pj = {k: jnp.asarray(v) for k, v in p.items()}
    want, _ = ref.moe(pj, jnp.asarray(h[0]), z, "float32")
    total = np.asarray(ref.gated_ffn(
        jnp.asarray(h[0]), pj["shared_gate_weight"],
        pj["shared_up_weight"], pj["shared_down_weight"], "float32"))
    pairs = 0
    for share in range(8):
        part, counters = routed(p, h, 4 * share,
                                slice(4 * share, 4 * share + 4),
                                z["top_k"], [16])
        total = total + part[0]
        pairs += counters[0]
        assert counters[0] + counters[1] == 16 * z["top_k"]
    assert pairs == 16 * z["top_k"]          # every pair on one chip
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5)


# -- grouped queries over the paged cache ---------------------------------

def test_gqa_paged_decode_kernel_matches_the_gather(monkeypatch):
    rng = np.random.default_rng(6)
    B, H, Hkv, D, KVB, P, MB = 3, 16, 2, 8, 4, 12, 3
    q = rng.standard_normal((B, 1, H * D)).astype(np.float32)
    kv = rng.standard_normal((2, B, 1, Hkv * D)).astype(np.float32)
    pools = rng.standard_normal((2, P, KVB, Hkv * D)).astype(np.float32)
    table = np.array([[3, 5, 0], [7, 1, 2], [0, 0, 0]], np.int32)
    lengths = np.array([6, 11, 0], np.int32)
    outs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("MXNET_PALLAS", flag)
        outs[flag] = run_op("GQAPagedDecode",
                            [q, kv[0], kv[1], pools[0], pools[1], table,
                             lengths], num_heads=H, kv_heads=Hkv)
    for a, b in zip(outs["0"], outs["1"]):
        np.testing.assert_allclose(np.asarray(a)[:2], np.asarray(b)[:2],
                                   atol=1e-5)
