"""The fourth spec of the layer-list family (``models/hybrid_lm.py``):
multi-head latent attention over ONE cached row a token (a prefill that
up-projects it, a decode step that absorbs the up-projection), rotary
positions on a span of the head with rescaled (YaRN) frequencies, a
dense layer before the expert layers, a router whose choice is moved by
a bias and limited to groups — against its plain reference
(``benchmark/reference/deepseek_v3.py``) at a small size, seeded
weights: prefill + decode through the latent pages on logits, each
mechanism left out failing that comparison, the kernels interpreted
against the lax bodies, the shares of the experts adding up, and the
engine's pages."""

import copy
import importlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu import kv_cache, profiler  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.executor import build_graph_fn  # noqa: E402
from mxnet_tpu.models.hybrid_lm import HybridSpec  # noqa: E402
from mxnet_tpu.ops import hybrid as hy  # noqa: E402

from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from _engines import WAIT, Family  # noqa: E402

# the published shape at a size a test can hold: one dense layer and
# three expert layers, 4 heads of (16 + 8 | 16), ranks 24 / 16, 16
# experts in 4 groups (2 groups, 4 experts a token) of which this share
# holds 8, positions rescaled past 16
KVB = 16
CFG = {
    "family": "deepseek_v3", "hidden_size": 64, "num_hidden_layers": 4,
    "num_hidden_layers_published": 61, "first_k_dense_replace": 3,
    "dense_layers_held": 1, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "n_routed_experts_published": 16,
    "first_expert": 0, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 96,
    # a wider draw than the published 0.02: at d 64 the blocks must move
    # the residual stream enough for a missing mechanism to show
    "initializer_range": 0.1, "selection_bias_std": 0.1,
}


def draw(seed=7, dtype="float32", cfg=CFG):
    return ref.draw(cfg, seed, embed_dtype=dtype, dtype=dtype)


# -- the two symbols, driven by hand: logits through the latent pages ----

class Programs:
    """The spec's prefill and decode symbols over hand-kept pools and a
    table (one stream): what the engine's programs compute, with the
    logits kept."""

    def __init__(self, drawn, max_len=128, dtype=np.float32):
        self.spec = ref.spec(CFG)
        self.params = {k: jnp.asarray(v)
                       for k, v in ref.program_names(drawn).items()}
        self.mb = max_len // KVB
        layout = self.spec.pools(1 + self.mb, KVB, 2, dtype)
        self.names = [n for n, _, _, _ in layout]
        self.pools = [jnp.zeros(shape, dt) for _, shape, dt, _ in layout]
        self.graph = {ph: build_graph_fn(self.spec.symbol(ph))
                      for ph in ("prefill", "decode")}
        self.fn = {ph: jax.jit(g, static_argnums=(3,))
                   for ph, g in self.graph.items()}
        self.key = jax.random.PRNGKey(0)

    def fresh(self):
        """The same programs over pools nobody has written."""
        self.pools = [jnp.zeros_like(p) for p in self.pools]
        return self

    def args(self, tokens, positions, lengths):
        table = np.zeros((1, self.mb), np.int32)
        n = -(-int(lengths[0]) // KVB)
        table[0, :n] = 1 + np.arange(n)
        out = dict(self.params, data=jnp.asarray(tokens),
                   positions=jnp.asarray(positions),
                   lengths=jnp.asarray(lengths),
                   block_table=jnp.asarray(table),
                   slots=jnp.zeros((1,), jnp.int32))
        out.update(zip(self.names, self.pools))
        return out

    def run(self, phase, tokens, positions, lengths):
        outs, _ = self.fn[phase](self.args(tokens, positions, lengths), {},
                                 self.key, False)
        self.pools = list(outs[1:])
        return np.asarray(outs[0])[0, 0]

    def serve(self, seq, n_prompt, bucket):
        """Logits at positions n_prompt - 1 .. len(seq) - 1: a prefill of
        ``seq[:n_prompt]`` padded to ``bucket``, then a decode step a
        token."""
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n_prompt] = seq[:n_prompt]
        rows = [self.run("prefill", toks, np.arange(bucket)[None],
                         np.asarray([n_prompt], np.int32))]
        for t in range(n_prompt, len(seq)):
            rows.append(self.run(
                "decode", np.asarray([[seq[t]]], np.int32),
                np.asarray([[t]], np.int32), np.asarray([t + 1], np.int32)))
        return np.stack(rows)

    def kernels_in(self, phase, bucket=96):
        """The Pallas kernels a phase's program calls, by name."""
        shape = (1, bucket) if phase == "prefill" else (1, 1)
        text = str(jax.make_jaxpr(
            lambda a: self.graph[phase](a, {}, self.key, False))(
            self.args(np.zeros(shape, np.int32), np.zeros(shape, np.int32),
                      np.asarray([5], np.int32))))
        return {k for k in ("mla_flash_fwd", "mla_paged_decode",
                            "mla_latent_write") if k in text}


def sequence(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG["vocab_size"], n).astype(np.int32)


# (prompt, total): a prompt that ends inside a page and one that ends on
# a page's edge; both decode far past the 16 original positions
CASES = [(20, 90), (32, 70)]


@pytest.fixture(scope="module")
def served():
    """The program's logits (lax bodies) for each case."""
    drawn = draw()
    progs = Programs(drawn)      # one build for the cases
    out = []
    for i, (n_prompt, total) in enumerate(CASES):
        seq = sequence(20 + i, total)
        out.append((seq, n_prompt,
                    progs.fresh().serve(seq, n_prompt, bucket=96)))
    return drawn, out


def reference_rows(drawn, seq, n_prompt, precision="float32"):
    return FAMILY.logits(drawn, seq, precision)[n_prompt - 1:]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_prefill_and_decode_through_the_latent_pages_match_the_reference(
        served, case):
    drawn, runs = served
    seq, n_prompt, got = runs[case]
    want = reference_rows(drawn, seq, n_prompt)
    assert got.shape == want.shape
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("mechanism", ref.MECHANISMS)
def test_a_mechanism_left_out_fails_the_comparison(served, mechanism):
    drawn, runs = served
    worst = 0.0
    for seq, n_prompt, got in runs:
        wrong = reference_rows(drawn, seq, n_prompt, mechanism)
        worst = max(worst, float(np.abs(got - wrong).max()))
    assert worst > 1e-2, (mechanism, worst)


def test_bfloat16_program_is_close_to_the_float32_reference():
    drawn = draw(dtype="bfloat16")
    seq, n_prompt = sequence(31, 80), 40
    got = Programs(drawn, dtype=jnp.bfloat16).serve(
        seq, n_prompt, bucket=96).astype(np.float32)
    want = reference_rows(drawn, seq, n_prompt)
    # bfloat16 products against float32 ones, through an absorbed query
    # that is rounded once more than the prefill's: the stated tolerance
    # is on the mean, a quarter of the logits' RMS (a top-4 set of 16
    # experts flips on the eighth bit)
    assert np.abs(got - want).mean() < 0.25 * np.sqrt((want ** 2).mean())


def test_kernels_interpreted_match_the_lax_bodies(served, monkeypatch):
    drawn, runs = served
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk.enabled()
    progs = Programs(drawn)
    assert progs.kernels_in("prefill") == {"mla_flash_fwd",
                                           "mla_latent_write"}
    assert progs.kernels_in("decode") == {"mla_paged_decode"}
    for seq, n_prompt, lax_rows in runs:
        got = Programs(drawn).serve(seq[:n_prompt + 6], n_prompt, bucket=96)
        np.testing.assert_allclose(got, lax_rows[:7], atol=2e-4)


def test_the_lax_bodies_call_no_kernel(served):
    progs = Programs(served[0])
    assert not progs.kernels_in("prefill") and not progs.kernels_in("decode")


def test_paged_kernel_walks_rows_of_unequal_length_and_an_empty_row(
        monkeypatch):
    # 5 rows over 4 chunks of 32 keys: an empty row between live ones, a
    # row that ends inside a chunk, one on its edge, one page alone
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_MLA_PAGED_CHUNK_KEYS", 32)
    rng = np.random.default_rng(0)
    B, H, lanes, R, MB = 5, 4, 128, 16, 8
    pool = jnp.asarray(rng.normal(size=(1 + B * MB, KVB, lanes)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, lanes)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(B * MB).reshape(B, MB),
                        jnp.int32)
    lengths = np.asarray([37, 0, 64, 5, 128], np.int32)
    got = np.asarray(pk.mla_paged_decode(
        q, pool, table, jnp.asarray(lengths - 1), R, 0.3))
    rows = np.asarray(pool)[np.asarray(table)].reshape(B, MB * KVB, lanes)
    for b, n in enumerate(lengths):
        if not n:
            assert np.all(got[b] == 0)
            continue
        s = np.asarray(q)[b] @ rows[b, :n].T * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[b, :n, :R]
        np.testing.assert_allclose(got[b], want, atol=1e-5)


# a prompt of two query tiles, the last ragged; B = 2 of unequal lengths,
# one ending inside an edge sub-block, one a single row; a prompt that
# fills its bucket
@pytest.mark.parametrize("T, lengths", [
    (200, None), (200, (150, 1)), (256, (256, 97))],
    ids=["fills_no_tile", "unequal_lengths", "fills_the_bucket"])
@pytest.mark.parametrize("tiles", [
    (128, 128, 32, 64, 4), (64, 128, 32, 32, 2), None], ids=str)
def test_prefill_kernel_under_its_walk_is_the_lax_body(monkeypatch, tiles,
                                                       T, lengths):
    # `mla_flash` under the band schedule — a bare body below the
    # diagonal, the diagonal's tile in sub-blocks, key tiles wider than
    # query tiles — at small tiles and at the ones `_mla_tiles` picks
    # (None: one query tile of 256 rows here)
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    if tiles:
        monkeypatch.setattr(pk, "_mla_tiles", lambda t, *widths: tiles)
    rng = np.random.default_rng(1)
    B, H, n, r, dv = 2, 4, 16, 8, 16

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q_n, q_r, k_n, k_r, v = (arr(B, T, H * n), arr(B, T, H * r),
                             arr(B, T, H * n), arr(B, T, r),
                             arr(B, T, H * dv))
    got = np.asarray(pk.mla_flash(
        jnp.concatenate([q_n, q_r], -1), q_r, jnp.concatenate([k_n, v], -1),
        k_r, H, n, dv, 0.2,
        lengths=None if lengths is None else jnp.asarray(lengths)))
    want = np.asarray(hy.mla_causal(q_n, q_r, k_n, k_r, v, H, 0.2))
    for b, rows in enumerate(lengths or (T, T)):
        np.testing.assert_allclose(got[b, :rows], want[b, :rows], atol=2e-5)
        assert np.all(got[b, rows:] == 0)


# -- the router -----------------------------------------------------------

def parent_moe_route(x2, router_w, top_k):
    """``moe_route`` (score sigmoid) as the parent commit had it."""
    logits = jnp.dot(x2.astype(jnp.float32),
                     router_w.astype(jnp.float32).T,
                     precision=lax.Precision.HIGHEST)
    topv, topi = lax.top_k(jax.nn.sigmoid(logits), top_k)
    return topi, topv / jnp.sum(topv, axis=-1, keepdims=True)


def test_moe_route_without_the_new_keys_is_the_parents_bit_for_bit():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 64)) * 0.3, jnp.float32)
    for got, want in zip(hy.moe_route(x, w, 4), parent_moe_route(x, w, 4)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_moe_route_bias_moves_the_choice_and_groups_limit_it():
    rng = np.random.default_rng(3)
    N, E, G, k = 200, 16, 4, 4
    x = jnp.asarray(rng.normal(size=(N, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, 32)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(E,)) * 0.2, jnp.float32)
    topi, wts = (np.asarray(t) for t in hy.moe_route(
        x, w, k, select_bias=b, groups=G, top_groups=2, routed_scale=2.5))
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x) @ np.asarray(w).T)))
    sb = s + np.asarray(b)
    moved = 0
    for t in range(N):
        by_group = sb[t].reshape(G, E // G)
        best = np.sort(by_group, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-best)[:2]
        allowed = np.concatenate([np.arange(E // G) + g * (E // G)
                                  for g in kept])
        want = allowed[np.argsort(-sb[t, allowed])[:k]]
        assert set(topi[t]) == set(want)
        assert len({e // (E // G) for e in topi[t]}) <= 2
        np.testing.assert_allclose(
            np.sort(wts[t]), np.sort(2.5 * s[t, want] / s[t, want].sum()),
            rtol=1e-5)
        moved += set(want) != set(np.argsort(-s[t])[:k])
    np.testing.assert_allclose(wts.sum(1), 2.5, rtol=1e-5)
    assert moved > N // 4      # the bias and the groups matter at this draw
    with pytest.raises(MXNetError, match="softmax_topk.*takes no"):
        hy.moe_route(x, w, k, score="softmax_topk", select_bias=b)
    with pytest.raises(MXNetError, match="groups"):
        hy.moe_route(x, w, k, groups=3, top_groups=2)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    # every chip's share of one expert layer (its routed part, under the
    # group-limited choice over ALL experts) + the shared expert once =
    # the layer with every expert held
    whole = dict(CFG, n_routed_experts=16, num_hidden_layers=2,
                 dense_layers_held=1)
    z = ref.sizes(whole)
    p = ref.draw(whole, 11, "float32", "float32")["layers"][1]
    h2 = jnp.asarray(np.random.default_rng(4).normal(size=(40, 64)),
                     jnp.float32)
    uncut, chosen = ref.routed(p, h2, z, "float32")
    assert np.abs(np.asarray(uncut)).max() > 1e-2
    parts, held = [], 4
    for first in range(0, 16, held):
        share = dict(p, **{k: p[k][first:first + held] for k in (
            "experts_gate_weight", "experts_up_weight",
            "experts_down_weight")})
        zs = dict(z, held=held, first=first)
        y, again = ref.routed(share, h2, zs, "float32")
        assert np.array_equal(np.asarray(again), np.asarray(chosen))
        parts.append(np.asarray(y))
    np.testing.assert_allclose(sum(parts), np.asarray(uncut), atol=1e-5)
    # the program's op on one share: the same routed part
    spec = HybridSpec(96, 64, [{
        "mixer": {"kind": "mla", "heads": 4, "q_rank": 24, "kv_rank": 16,
                  "nope_dim": 16, "rope_dim": 8, "v_dim": 16},
        "ffn": {"kind": "moe", "experts": 16, "top_k": 4, "width": 32,
                "experts_held": held, "first_expert": 8, "groups": 4,
                "top_groups": 2, "routed_scale": 2.5,
                "select_bias": True}}])
    assert spec.layers[0]["ffn"]["first_expert"] == 8
    from mxnet_tpu.ops.registry import get_op
    out = get_op("MoEFFN").compute(
        None, dict(top_k=4, first_expert=8, step=True, select_bias=True,
                   groups=4, top_groups=2, routed_scale=2.5),
        [h2[:, None], p["router_weight"],
         p["experts_gate_weight"][8:12], p["experts_up_weight"][8:12],
         p["experts_down_weight"][8:12], jnp.ones((40,), jnp.int32),
         jnp.zeros((4,), jnp.int32), p["router_bias"]], [])
    np.testing.assert_allclose(np.asarray(out[0])[:, 0], parts[2],
                               atol=1e-5)


# -- the engine: latent pages ---------------------------------------------

FAMILY = Family(ref, CFG, pad=128, max_len=128, kv_block=KVB,
                max_streams=3, decode_buckets=(1, 2, 4),
                cache_buckets=(4, 8), prefill_buckets=(32, 96))
# the tests that name no argument share one engine (``engines``) and read
# its counters from ``reset_stats()`` on
make_engine, served_gap = FAMILY.engine, FAMILY.served_gap


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


def test_a_batch_of_unequal_lengths_is_served_and_every_page_comes_back(
        engines):
    eng, drawn = engines(make_engine)
    rng = np.random.default_rng(3)
    # one prompt ends on a page's edge, one inside a page, one is short
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in (32, 45, 7, 80)]
    outs = [f.result(timeout=WAIT) for f in
            [eng.submit(p, max_new_tokens=m)
             for p, m in zip(ps, (40, 30, 50, 20))]]
    st = eng.stats()
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    assert eng._alloc.used_blocks == 0 and st["preempted"] == 0
    assert st["prefill_pairs"] == sum(len(p) * (len(p) + 1) // 2
                                      for p in ps)
    assert st["moe_pairs_here"] > 0 and st["moe_pairs_elsewhere"] > 0
    # the pool spends what the gauge says a token and layer
    item = 4
    assert st["mla_cache_bytes_per_token"] == 128 * item
    assert st["mla_cache_bytes_needed_per_token"] == (16 + 8) * item
    g = profiler.metrics_summary()["gauges"]
    assert g["mla.cache_bytes_per_token"] == 128 * item
    tokens = eng._alloc.num_blocks * KVB
    assert g["serving.kv_pool_bytes"] - 16 == \
        st["mla_cache_bytes_per_token"] * tokens * CFG["num_hidden_layers"]


def small_tiles(monkeypatch):
    """From here on the kernels interpreted, the prompt kernels in tiles
    of 32 rows: a bucket of 96 is three query tiles."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    heads = pk._mla_tiles
    monkeypatch.setattr(pk, "_mla_tiles", lambda t, *widths: (
        32, 32, 32, 32, heads(t, *widths)[4]))


@pytest.mark.parametrize("n_prompt", [20, 96], ids=["lower_half", "fills"])
def test_prompt_kernels_given_the_length_leave_the_logits(monkeypatch,
                                                          n_prompt):
    # the prompt's rows are the lax body's whether the bucket's other
    # tiles are walked or not: prefill + six decode steps
    drawn, seq = draw(), sequence(31, n_prompt + 6)
    lax_rows = Programs(drawn).serve(seq, n_prompt, bucket=96)
    small_tiles(monkeypatch)
    got = Programs(drawn).serve(seq, n_prompt, bucket=96)
    np.testing.assert_allclose(got, lax_rows, atol=2e-4)


@pytest.mark.parametrize("lengths", [(20,), (96,), (20, 96)],
                         ids=["lower_half", "fills", "both"])
def test_the_engine_counts_the_tiles_its_prompt_kernels_walk_and_skip(
        engines, monkeypatch, lengths):
    # one engine for the three cases: its programs are traced under
    # ``small_tiles``, which each of them sets
    small_tiles(monkeypatch)
    eng, drawn = engines(make_engine, prefill_buckets=(96,))
    rng = np.random.default_rng(6)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in lengths]
    outs = [f.result(timeout=WAIT) for f in
            [eng.submit(p, max_new_tokens=6) for p in ps]]
    st = eng.stats()
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    # four latent layers, every key up to the query (1 + 2 + 3), three
    # query tiles; a prompt of 20 rows has one live tile a layer
    bucket, live = 4 * (1 + 2 + 3), 4
    assert ref.spec(CFG).prompt_attention() == ((0, True),) * 4
    skipped = sum(bucket - live for n in lengths if n == 20)
    assert (st["prefill_tiles_walked"], st["prefill_tiles_skipped"]) == (
        len(lengths) * bucket - skipped, skipped)
    assert st["prefill_tiles_skipped_share"] == round(
        skipped / (len(lengths) * bucket), 4)
    # the latent kernel masks the diagonal's tile of each live query tile
    assert st["prefill_tiles_masked"] == sum(
        4 * -(-n // 32) for n in lengths)
    assert st["prefill_scores_computed_over_needed"] == round(
        st["prefill_tiles_walked"] * 32 * 32
        / sum(4 * (n * (n + 1) // 2) for n in lengths), 4)


def test_recompute_preemption_under_a_tight_pool_leaves_the_logits():
    # 9 pages for three streams that grow to 5 each: someone is thrown
    # out, gives its pages back, and is prefilled again (an engine of its
    # own: the pool is sized for it)
    eng, drawn = make_engine(cache_blocks=10, max_len=80,
                             cache_buckets=(5,))
    rng = np.random.default_rng(5)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in (30, 41, 36)]
    with eng:
        outs = [f.result(timeout=WAIT) for f in
                [eng.submit(p, max_new_tokens=38) for p in ps]]
        st = eng.stats()
    assert st["preempted"] >= 1
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    assert eng._alloc.used_blocks == 0


@pytest.mark.parametrize("kw, feature", [
    (dict(prefix_cache=1), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(spec_tokens=2), "spec_tokens"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_dtype="fp8"), "kv_dtype='fp8'"),
    (dict(tp=2), "tp=2"),
    (dict(pp=2), "pp=2"),
])
def test_features_over_latent_pages_are_refused_by_name(kw, feature):
    with pytest.raises(MXNetError) as err:
        make_engine(**kw)
    assert feature in str(err.value) and "mla" in str(err.value)


def test_page_export_and_import_are_refused_by_name(engines):
    eng, _ = engines(make_engine)
    with pytest.raises(MXNetError, match="page export.*latent row"):
        eng.submit(np.arange(1, 6, dtype=np.int32), prefill_only=True)
    with pytest.raises(MXNetError, match="page import.*latent row"):
        eng.import_stream({}, [])


# -- the spec -------------------------------------------------------------

def structure(sym):
    import test_smallthinker
    return test_smallthinker.structure(sym)


@pytest.mark.parametrize("family", ["solar_open2", "granitemoehybrid",
                                    "smallthinker"])
def test_the_three_specs_there_were_build_the_parents_symbols(family):
    # tests/data/hybrid_symbols_pr37.json: ``structure`` of the parent
    # commit's symbols for the three tiny configurations
    import test_hybrid_lm
    import test_mamba2
    import test_smallthinker

    cfg = {"solar_open2": test_hybrid_lm.CFG,
           "granitemoehybrid": test_mamba2.CFG,
           "smallthinker": test_smallthinker.CFG}[family]
    spec = importlib.import_module(
        f"benchmark.reference.{family}").spec(cfg)
    with open(os.path.join(ROOT, "tests", "data",
                           "hybrid_symbols_pr37.json")) as f:
        parent = json.load(f)[family]
    for ph in ("prefill", "decode"):
        assert structure(spec.symbol(ph)) == parent[ph]
    assert spec.latent_row is None


def test_spec_is_data_and_reports_the_row_it_caches():
    spec = ref.spec(CFG)
    again = HybridSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert again.to_dict() == spec.to_dict()
    for ph in ("prefill", "decode"):
        assert structure(again.symbol(ph)) == structure(spec.symbol(ph))
    assert spec.feeds == ("data", "lengths", "block_table", "slots",
                          "positions")
    assert spec.cache_kinds() == ("pages",) * 4
    assert spec.pool_kinds() == ("pages",) * 4 + ("counters",)
    assert spec.latent_row == (24, 128)
    assert (spec.kv_heads, spec.head_dim) == (1, 128) and not spec.window
    shapes = {n: s for n, s, _, _ in spec.pools(50, KVB, 2, np.float32)}
    assert shapes["layer0_latent_pool"] == (50, KVB, 128) \
        == kv_cache.latent_pool_shape(50, KVB, 16, 8)
    assert "mla" in spec.name
    m = spec.layers[0]["mixer"]
    assert m["scale"] == pytest.approx(
        24 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    # every projection is a node of its own name
    for ph, names in (("prefill", ("kv_up",)),
                      ("decode", ("absorb_k", "absorb_v"))):
        nodes = {n[1] for n in structure(spec.symbol(ph))}
        for n in ("q_down", "q_up", "kv_down", "attn", "o") + names:
            assert f"layer3_{n}" in nodes, (ph, n)
    assert "layer3_kv_up" not in {
        n[1] for n in structure(spec.symbol("decode"))}


def test_the_published_rescaling_gives_the_issues_numbers():
    inv = hy.yarn_inv_freq(64, 10000.0, 40.0, 4096.0, 32.0, 1.0)
    plain = hy.yarn_inv_freq(64, 10000.0)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(32) / 32),
                               rtol=1e-6)
    # pairs 0..10 keep their frequency, 23.. have it divided by 40
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert plain[15] / 40 < inv[15] < plain[15]
    scaling = {"factor": 40, "mscale_all_dim": 1}
    assert hy.mla_scale(128, 64, scaling) == pytest.approx(0.13523,
                                                           abs=1e-5)
    assert hy.mla_scale(128, 64) == pytest.approx(192 ** -0.5)
    # the reference computes its own, the same
    z = ref.sizes(dict(CFG, qk_rope_head_dim=64, rope_scaling=dict(
        CFG["rope_scaling"], factor=40,
        original_max_position_embeddings=4096)))
    np.testing.assert_allclose(ref.inv_freq(z), inv, rtol=1e-6)


def test_rotate_half_with_frequencies_as_data_is_the_plain_one():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, 5, 3 * 8)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 50, (2, 5)), jnp.int32)
    a = hy.rotate_half(x, pos, 10000.0, 3)
    b = hy.rotate_half(x, pos, 0.0, 3,
                       inv_freq=hy.yarn_inv_freq(8, 10000.0))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("part, key", [("mixer", "kv_rnk"),
                                       ("ffn", "group")])
def test_an_unknown_key_of_a_layer_is_refused_by_name(part, key):
    d = ref.spec(CFG).to_dict()
    d["layers"][1][part][key] = 4
    with pytest.raises(MXNetError, match=f"layer 1.*{key}"):
        HybridSpec.from_dict(d)


def test_latent_layers_beside_attention_layers_are_refused():
    d = copy.deepcopy(ref.spec(CFG).to_dict())
    d["layers"][2]["mixer"] = {"kind": "attention", "heads": 4,
                               "head_dim": 16}
    with pytest.raises(MXNetError, match="one page geometry"):
        HybridSpec.from_dict(d)
    d = copy.deepcopy(ref.spec(CFG).to_dict())
    d["layers"][2]["mixer"]["rope_scaling"] = {"type": "linear",
                                               "factor": 2}
    with pytest.raises(MXNetError, match="yarn"):
        HybridSpec.from_dict(d)


def test_latent_pool_bytes():
    # 576 values are held as 640 lanes: one pool a layer
    assert kv_cache.latent_pool_shape(7, 16, 512, 64) == (7, 16, 640)
    assert kv_cache.pool_device_bytes(
        100, 16, 5, 128, 7168, "bf16", latent_row=(512, 64)) \
        == 100 * 16 * 640 * 2 * 5
    # beside K and V rows of every head
    assert kv_cache.pool_device_bytes(100, 16, 5, 128, 128 * 128, "bf16") \
        == 2 * 5 * 100 * 16 * 128 * 128 * 2
