"""The fifth spec of the layer-list family (``models/hybrid_lm.py``):
gated grouped-query attention with an RMSNorm over each head's q and k
before the rotation, sliding-window rotary layers beside global layers
without positions, SANDWICH norms (a branch's output normalised before
it is added), a leading dense layer, sigmoid-scored experts chosen under
a selection bias in one group, one shared expert, token rows x sqrt(d) —
against its plain reference (``benchmark/reference/afmoe.py``) at a
small size, seeded weights: prefill + decode through the pages of BOTH
pools on logits, each mechanism left out or misplaced failing that
comparison, the kernels interpreted against the lax bodies, the shares
of the experts adding up, the engine's counters and pages, and the four
specs there were building the symbols they built."""

import copy
import importlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.executor import build_graph_fn  # noqa: E402
from mxnet_tpu.models.hybrid_lm import HybridSpec  # noqa: E402

from benchmark.reference import afmoe as ref  # noqa: E402
from _engines import WAIT, Family  # noqa: E402

# the published shape at a size a test can hold: the dense layer 0
# (sliding) and expert layers 6-9 (sliding, full, sliding, sliding) of a
# [sliding x 3, full] x 15 stack, 6 query heads over 2 KV heads of 16, a
# window of two pages, 16 experts (2 a token) of which this share holds
# 8, one shared
W, KVB = 32, 16
CFG = {
    "family": "afmoe", "hidden_size": 64, "num_hidden_layers": 5,
    "layers_held": [0, 6, 7, 8, 9], "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + (["sliding_attention"] * 3 + ["full_attention"]) * 14,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": W, "rope_theta": 10000, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_published": 16, "first_expert": 0,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_scale": 2.448,
    "route_norm": True, "mup_enabled": True, "rms_norm_eps": 1e-5,
    "vocab_size": 96,
    # a wider draw than the published 0.02: at d 64 the token rows must
    # weigh against five blocks' unit-norm outputs
    "initializer_range": 0.1, "selection_bias_std": 0.1,
}
ATTENTION_KERNELS = ("flash_fwd_window", "flash_fwd_mha", "kv_pages_write",
                     "paged_window", "paged_attention", "moe_gmm")


def draw(seed=7, dtype="float32", cfg=CFG):
    return ref.draw(cfg, seed, embed_dtype=dtype, dtype=dtype)


# -- the two symbols, driven by hand: logits through both pools ----------

class Programs:
    """The spec's prefill and decode symbols over hand-kept pools and
    tables: what the engine's programs compute, with the logits kept.
    The windowed pools' table holds the scratch page for every block the
    window no longer reaches, as the engine's does."""

    def __init__(self, drawn, max_len=160, dtype=np.float32, rows=1):
        self.spec = ref.spec(CFG)
        self.params = {k: jnp.asarray(v)
                       for k, v in ref.program_names(drawn).items()}
        self.mb, self.rows = max_len // KVB, rows
        self.per_row = W // KVB + 2          # windowed pages a stream
        layout = self.spec.pools(1 + rows * self.mb, KVB, 2, dtype,
                                 window_blocks=1 + rows * self.per_row)
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

    def tables(self, row, length):
        """(block table, window table) rows of stream ``row`` about to
        be fed the token at position ``length - 1``: windowed page ids
        cycle through the stream's few pages, as a reused page would."""
        full = np.zeros(self.mb, np.int32)
        win = np.zeros(self.mb, np.int32)
        n = -(-length // KVB)
        full[:n] = 1 + row * self.mb + np.arange(n)
        first = max(length - W, 0) // KVB    # the oldest block still seen
        for b in range(first, n):
            win[b] = 1 + row * self.per_row + b % self.per_row
        return full, win

    def args(self, tokens, positions, lengths, rows, keep_from=0):
        tabs = [self.tables(r, int(n)) for r, n in zip(rows, lengths)]
        full = np.stack([t[0] for t in tabs])
        win = np.stack([t[1] for t in tabs])
        win[:, :keep_from] = 0
        out = dict(self.params, data=jnp.asarray(tokens),
                   positions=jnp.asarray(positions),
                   lengths=jnp.asarray(lengths, jnp.int32),
                   block_table=jnp.asarray(full),
                   window_table=jnp.asarray(win),
                   slots=jnp.zeros((len(rows),), jnp.int32))
        out.update(zip(self.names, self.pools))
        return out

    def run(self, phase, tokens, positions, lengths, rows=(0,),
            keep_from=0):
        outs, _ = self.fn[phase](
            self.args(tokens, positions, lengths, rows, keep_from), {},
            self.key, False)
        self.pools = list(outs[1:])
        return np.asarray(outs[0])[:, 0]

    def prefill(self, seq, n_prompt, bucket, row=0):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n_prompt] = seq[:n_prompt]
        return self.run("prefill", toks, np.arange(bucket)[None],
                        [n_prompt], rows=(row,),
                        keep_from=max(n_prompt - W + 1, 0) // KVB)[0]

    def serve(self, seq, n_prompt, bucket):
        """Logits at positions n_prompt - 1 .. len(seq) - 1: a prefill of
        ``seq[:n_prompt]`` padded to ``bucket``, then a decode step a
        token."""
        rows = [self.prefill(seq, n_prompt, bucket)]
        for t in range(n_prompt, len(seq)):
            rows.append(self.run(
                "decode", np.asarray([[seq[t]]], np.int32),
                np.asarray([[t]], np.int32), [t + 1])[0])
        return np.stack(rows)

    def kernels_in(self, phase, bucket=96):
        """The Pallas kernels a phase's program calls, by name."""
        shape = (1, bucket) if phase == "prefill" else (1, 1)
        text = str(jax.make_jaxpr(
            lambda a: self.graph[phase](a, {}, self.key, False))(
            self.args(np.zeros(shape, np.int32), np.zeros(shape, np.int32),
                      [bucket if phase == "prefill" else 5], (0,))))
        return {k for k in ATTENTION_KERNELS if k in text}


def sequence(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG["vocab_size"], n).astype(np.int32)


# (prompt, total, bucket): a prompt shorter than the window whose decode
# crosses it and gives windowed pages back; one several windows long
# that ends inside a page (its early windowed pages are never written);
# one that ends on a page's edge and fills its bucket
CASES = [(20, 90, 96), (107, 150, 128), (96, 120, 96)]


@pytest.fixture(scope="module")
def served():
    """The program's logits (lax bodies) for each case."""
    drawn = draw()
    progs = Programs(drawn)      # one build for the cases
    out = []
    for i, (n_prompt, total, bucket) in enumerate(CASES):
        seq = sequence(20 + i, total)
        out.append((seq, n_prompt,
                    progs.fresh().serve(seq, n_prompt, bucket)))
    return drawn, out


def reference_rows(drawn, seq, n_prompt, precision="float32"):
    return FAMILY.logits(drawn, seq, precision)[n_prompt - 1:]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_prefill_and_decode_through_both_pools_match_the_reference(
        served, case):
    drawn, runs = served
    seq, n_prompt, got = runs[case]
    want = reference_rows(drawn, seq, n_prompt)
    assert got.shape == want.shape
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("mechanism", ref.MECHANISMS)
def test_a_mechanism_left_out_or_misplaced_fails_the_comparison(
        served, mechanism):
    # on the case several windows long (each form is another eager pass
    # of the reference)
    drawn, runs = served
    seq, n_prompt, got = runs[1]
    wrong = reference_rows(drawn, seq, n_prompt, mechanism)
    worst = float(np.abs(got - wrong).max())
    assert worst > 1e-2, (mechanism, worst)


def test_a_batch_of_unequal_lengths_decodes_as_its_rows_do_alone(served):
    # three streams of the cases' prompts in ONE decode program: rows of
    # unequal length, each against its own pages of both pools
    drawn, runs = served
    progs = Programs(drawn, rows=3)
    for r, (seq, n_prompt, _) in enumerate(runs):
        progs.prefill(seq, n_prompt, CASES[r][2], row=r)
    for step in range(3):
        at = [n + step for _, n, _ in runs]
        got = progs.run(
            "decode", np.asarray([[seq[t]] for (seq, _, _), t in
                                  zip(runs, at)], np.int32),
            np.asarray([[t] for t in at], np.int32),
            [t + 1 for t in at], rows=(0, 1, 2))
        for r, (_, _, alone) in enumerate(runs):
            np.testing.assert_allclose(got[r], alone[1 + step], atol=1e-5)


def test_bfloat16_program_is_close_to_the_float32_reference():
    drawn = draw(dtype="bfloat16")
    seq, n_prompt = sequence(31, 100), 70
    got = Programs(drawn, dtype=jnp.bfloat16).serve(
        seq, n_prompt, bucket=96).astype(np.float32)
    want = reference_rows(drawn, seq, n_prompt)
    # bfloat16 products against float32 ones; five post-norms bring
    # every branch's rounding back to unit scale and a top-2 set of 16
    # experts flips on the eighth bit, so the stated tolerance is on the
    # mean: a quarter of the logits' RMS
    assert np.abs(got - want).mean() < 0.25 * np.sqrt((want ** 2).mean())


def test_kernels_interpreted_match_the_lax_bodies(served, monkeypatch):
    drawn, runs = served
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk.enabled()
    progs = Programs(drawn)
    assert progs.kernels_in("prefill") == {
        "flash_fwd_window", "flash_fwd_mha", "kv_pages_write", "moe_gmm"}
    assert progs.kernels_in("decode") == {
        "paged_window", "paged_attention", "moe_gmm"}
    seq, n_prompt, lax_rows = runs[1]       # several windows long
    got = Programs(drawn).serve(seq[:n_prompt + 6], n_prompt,
                                bucket=CASES[1][2])
    np.testing.assert_allclose(got, lax_rows[:7], atol=2e-4)


def test_the_lax_bodies_call_no_kernel(served):
    progs = Programs(served[0])
    assert not progs.kernels_in("prefill") and not progs.kernels_in("decode")


def test_the_global_flash_kernel_groups_queries_through_its_index_map(
        monkeypatch):
    # flash_mha_window(window=0): every key up to the query, K and V at
    # their own head count — against the lax body over repeated K / V
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import attention, pallas_kernels as pk
    rng = np.random.default_rng(1)
    B, T, H, Hkv, D = 2, 200, 6, 2, 16      # two tiles, the last ragged
    monkeypatch.setattr(pk, "_mha_window_tiles",
                        lambda t, window: (128, 128, 64, 128))
    q, k, v = (jnp.asarray(rng.normal(size=(B * n, T, D)), jnp.float32)
               for n in (H, Hkv, Hkv))
    got = pk.flash_mha_window(q, k, v, 0, H, Hkv)

    def bthd(x, n):
        return x.reshape(B, n, T, D).transpose(0, 2, 1, 3)

    monkeypatch.setenv("MXNET_PALLAS", "0")
    want = attention.blockwise_attention(
        bthd(q, H), jnp.repeat(bthd(k, Hkv), H // Hkv, axis=2),
        jnp.repeat(bthd(v, Hkv), H // Hkv, axis=2), causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want.transpose(0, 2, 1, 3)
                                    .reshape(B * H, T, D)), atol=2e-5)
    with pytest.raises(MXNetError, match="window -1"):
        pk.flash_mha_window(q, k, v, -1, H, Hkv)


# -- the share ------------------------------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    # every chip's share of one expert layer (its routed part, under the
    # biased choice over ALL experts) + the shared expert once = the
    # layer with every expert held — on ``y``, BEFORE its post-norm: a
    # norm of a partial sum is not a partial sum of norms
    whole = dict(CFG, num_experts=16)
    z = ref.sizes(whole)
    p = ref.draw(whole, 11, "float32", "float32")["layers"][2]
    h2 = jnp.asarray(np.random.default_rng(4).normal(size=(40, 64)),
                     jnp.float32)
    uncut, chosen = ref.ffn(p, h2, z, "float32", dense=False)
    shared = np.asarray(ref.shared(p, h2, "float32"))
    assert np.abs(np.asarray(uncut) - shared).max() > 1e-2
    parts, held = [], 4
    for first in range(0, 16, held):
        share = dict(p, **{k: p[k][first:first + held] for k in (
            "experts_gate_weight", "experts_up_weight",
            "experts_down_weight")})
        y, again = ref.routed(share, h2, z, "float32", first=first,
                              held=held)
        assert np.array_equal(np.asarray(again), np.asarray(chosen))
        parts.append(np.asarray(y))
    np.testing.assert_allclose(sum(parts) + shared, np.asarray(uncut),
                               atol=1e-5)
    # the program's op on one share: the same routed part
    from mxnet_tpu.ops.registry import get_op
    out = get_op("MoEFFN").compute(
        None, dict(top_k=2, first_expert=8, step=True, select_bias=True,
                   routed_scale=2.448),
        [h2[:, None], p["router_weight"],
         p["experts_gate_weight"][8:12], p["experts_up_weight"][8:12],
         p["experts_down_weight"][8:12], jnp.ones((40,), jnp.int32),
         jnp.zeros((4,), jnp.int32), p["router_bias"]], [])
    np.testing.assert_allclose(np.asarray(out[0])[:, 0], parts[2],
                               atol=1e-5)


def test_the_selection_bias_moves_the_choice_not_the_weights():
    z = ref.sizes(CFG)
    p = draw()["layers"][1]
    h2 = jnp.asarray(np.random.default_rng(5).normal(size=(200, 64)),
                     jnp.float32)
    topi, wts = (np.asarray(t) for t in ref.route(p, h2, z))
    plain, _ = ref.route(p, h2, z, "no_select_bias")
    moved = np.mean(np.sort(topi, -1) != np.sort(np.asarray(plain), -1))
    assert moved > 0.1          # the bias matters at this draw
    s = 1 / (1 + np.exp(-np.asarray(h2) @ np.asarray(p["router_weight"]).T))
    chosen = np.take_along_axis(s, topi, -1)
    np.testing.assert_allclose(
        wts, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_served_gaps_over_cropped_rows_are_those_of_all_rows(monkeypatch):
    drawn = draw()
    seq = np.zeros(160, np.int32)
    seq[:90] = sequence(9, 90)
    served = jnp.asarray(seq[60:68])
    whole = ref.served_gaps(CFG, drawn, jnp.asarray(seq), 59, served,
                            "no_window", 8)
    monkeypatch.setattr(ref, "CROP_ROWS", (96,))
    cropped = ref.served_gaps(CFG, drawn, jnp.asarray(seq), 59, served,
                              "no_window", 8)
    for a, b in zip(whole, cropped):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert np.asarray(whole[1]).max() > 1e-3


# -- the engine -------------------------------------------------------------

FAMILY = Family(ref, CFG, pad=352, max_len=352, kv_block=KVB,
                max_streams=3, decode_buckets=(1, 2, 4),
                cache_buckets=(8, 22), prefill_buckets=(32, 96, 128))
# the tests that name no argument share one engine (``engines``) and read
# its counters from ``reset_stats()`` on
make_engine, served_gap = FAMILY.engine, FAMILY.served_gap


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


def test_the_engine_serves_unequal_prompts_counts_its_buckets_and_gets_every_page_back(engines):  # noqa: E501
    eng, drawn = engines(make_engine)
    rng = np.random.default_rng(3)
    # shorter than the window, several windows long, on a page's edge,
    # inside a page; the first decodes six windows: its windowed pages
    # are given back and taken again
    ps = [rng.integers(1, 96, n).astype(np.int32)
          for n in (12, 107, 96, 45)]
    new = (6 * W - 12, 40, 30, 50)
    outs = [f.result(timeout=WAIT) for f in
            [eng.submit(p, max_new_tokens=m) for p, m in zip(ps, new)]]
    st = eng.stats()
    for p, o, m in zip(ps, outs, new):
        assert len(o) == m and served_gap(drawn, p, o) < 1e-4
    # the rows of the programs that ran: 32 + 128 + 96 + 96
    assert st["prefills"] == 4 and st["preempted"] == 0
    assert st["prefill_tokens"] == sum(len(p) for p in ps) == 260
    assert st["prefill_bucket_tokens"] == 32 + 128 + 96 + 96
    assert st["prefill_bucket_fill"] == round(260 / 352, 4)
    c = profiler.metrics_summary()
    assert c["counters"]["serving.prefill_bucket_tokens"] >= 352
    assert c["gauges"]["serving.prefill_bucket_fill"] == \
        st["prefill_bucket_fill"]
    assert st["window_pages"] == 3 * (W // KVB + 2)
    assert st["window_pages_released"] >= 6 * W // KVB - 3
    assert st["window_pages_live"] == 0 and eng._walloc.used_blocks == 0
    assert eng._alloc.used_blocks == 0
    assert 0 < st["window_pages_held_share"] < 0.7
    assert st["window_context_tokens"] < st["context_tokens"]
    assert st["window_prefill_pairs"] > 0 and st["prefill_pairs"] > 0
    assert st["moe_pairs_here"] > 0 and st["moe_pairs_elsewhere"] > 0


@pytest.fixture
def small_tiles(monkeypatch):
    """The kernels interpreted, the prompt kernels in tiles of 32 rows:
    a bucket of 96 is three query tiles."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_mha_block", lambda block_size, t: 32)
    monkeypatch.setattr(pk, "_mha_window_tiles",
                        lambda t, window: (32, 32, 32, 32))


@pytest.mark.parametrize("case", [0, 2], ids=["lower_half", "fills"])
def test_prompt_kernels_given_the_length_leave_the_logits(served,
                                                          small_tiles, case):
    # the prompt's rows are the lax body's whether the bucket's other
    # tiles are walked or not: prefill + six decode steps
    drawn, runs = served
    seq, n_prompt, lax_rows = runs[case]
    got = Programs(drawn).serve(seq[:n_prompt + 6], n_prompt,
                                bucket=CASES[case][2])
    np.testing.assert_allclose(got, lax_rows[:7], atol=2e-4)


@pytest.mark.parametrize("lengths", [(20,), (96,), (20, 96)],
                         ids=["lower_half", "fills", "both"])
def test_the_engine_counts_the_tiles_its_prompt_kernels_walk_and_skip(
        engines, small_tiles, lengths):
    # one engine for the three cases: its programs are traced under
    # ``small_tiles``, which each of them sets
    eng, drawn = engines(make_engine, prefill_buckets=(96,))
    rng = np.random.default_rng(6)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in lengths]
    outs = [f.result(timeout=WAIT) for f in
            [eng.submit(p, max_new_tokens=6) for p in ps]]
    st = eng.stats()
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    # four windowed layers (a band of 32 keys: two tiles a query tile
    # past the first) and a global one (1 + 2 + 3), three query tiles
    bucket = 4 * (1 + 2 + 2) + (1 + 2 + 3)
    skipped = sum(bucket - 5 for n in lengths if n == 20)
    assert (st["prefill_tiles_walked"], st["prefill_tiles_skipped"]) == (
        len(lengths) * bucket - skipped, skipped)
    assert st["prefill_tiles_skipped_share"] == round(
        skipped / (len(lengths) * bucket), 4)
    # an edge of its band crosses every tile a windowed layer walks
    # here; the global layer masks its diagonal's tiles alone.  A tile
    # of 32 is its own sub-block: every walked tile is computed whole
    masked = {20: 4 * 1 + 1, 96: 4 * 5 + 3}
    needed = {20: 5 * (20 * 21 // 2),
              96: 4 * (32 * 33 // 2 + 64 * 32) + 96 * 97 // 2}
    assert st["prefill_tiles_masked"] == sum(masked[n] for n in lengths)
    assert st["prefill_scores_computed_over_needed"] == round(
        st["prefill_tiles_walked"] * 32 * 32
        / sum(needed[n] for n in lengths), 4)
    c = profiler.metrics_summary()
    assert c["counters"]["serving.prefill_tiles_walked"] >= 5
    assert c["gauges"]["serving.prefill_tiles_skipped_share"] == \
        st["prefill_tiles_skipped_share"]
    assert c["gauges"]["serving.prefill_scores_computed_over_needed"] == \
        st["prefill_scores_computed_over_needed"]


def test_the_lax_bodies_count_no_prompt_tile(engines):
    eng, _ = engines(make_engine)
    eng.submit(sequence(1, 20), max_new_tokens=2).result(timeout=WAIT)
    st = eng.stats()
    assert st["prefill_tiles_walked"] == st["prefill_tiles_skipped"] == 0
    assert st["prefill_tiles_skipped_share"] == 0.0
    assert st["prefill_tiles_masked"] == 0
    assert st["prefill_scores_computed_over_needed"] == 0.0
    assert ref.spec(CFG).prompt_attention() == (
        (W, False), (W, False), (0, False), (W, False), (W, False))


def test_recompute_preemption_under_a_tight_pool_leaves_the_logits():
    # 13 ordinary pages for three streams that grow to 6 each: someone
    # is thrown out, gives back its pages of both pools, and comes back
    # (an engine of its own: the pool is sized for it)
    eng, drawn = make_engine(cache_blocks=14, max_len=96,
                             cache_buckets=(6,), prefill_buckets=(32, 96))
    rng = np.random.default_rng(5)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in (30, 41, 36)]
    with eng:
        outs = [f.result(timeout=WAIT) for f in
                [eng.submit(p, max_new_tokens=50) for p in ps]]
        st = eng.stats()
    assert st["preempted"] >= 1
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    assert st["window_pages_live"] == 0
    assert eng._alloc.used_blocks == 0 and eng._walloc.used_blocks == 0
    # a re-prefill runs its bucket's rows again
    assert st["prefill_bucket_tokens"] >= 32 + 96 + 96 + 32


@pytest.mark.parametrize("kw, feature", [
    (dict(prefix_cache=1), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(spec_tokens=2), "spec_tokens"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_dtype="fp8"), "kv_dtype='fp8'"),
    (dict(tp=2), "tp=2"),
    (dict(pp=2), "pp=2"),
])
def test_features_over_windowed_pools_are_refused_by_name(kw, feature):
    with pytest.raises(MXNetError) as err:
        make_engine(**kw)
    assert feature in str(err.value) and "window" in str(err.value)


def test_page_export_and_import_are_refused_by_name(engines):
    eng, _ = engines(make_engine)
    with pytest.raises(MXNetError, match="page export.*windowed"):
        eng.submit(np.arange(1, 6, dtype=np.int32), prefill_only=True)
    with pytest.raises(MXNetError, match="page import.*windowed"):
        eng.import_stream({}, [])


# -- the spec -------------------------------------------------------------

def structure(sym):
    import test_smallthinker
    return test_smallthinker.structure(sym)


def test_spec_is_data_and_names_its_new_nodes():
    spec = ref.spec(CFG)
    assert spec.post_norm and spec.embed_scale == 8.0
    d = json.loads(json.dumps(spec.to_dict()))
    assert d["post_norm"] is True
    assert all(ly["mixer"]["qk_norm"] for ly in d["layers"])
    again = HybridSpec.from_dict(d)
    assert again.to_dict() == spec.to_dict()
    for ph in ("prefill", "decode"):
        assert structure(again.symbol(ph)) == structure(spec.symbol(ph))
    assert spec.window == W and spec.phases == ("prefill", "decode")
    assert spec.feeds == ("data", "lengths", "block_table", "slots",
                          "positions", "window_table")
    assert spec.cache_kinds() == ("window_pages",) * 2 + ("pages",) \
        + ("window_pages",) * 2
    assert [ly["ffn"]["kind"] for ly in spec.layers] == \
        ["dense"] + ["moe"] * 4
    assert "rope_theta" not in spec.layers[2]["mixer"] \
        and "window" not in spec.layers[2]["mixer"]
    for ph in ("prefill", "decode"):
        nodes = {n[1]: n for n in structure(spec.symbol(ph))}
        for i in range(5):
            for n in ("q_norm", "k_norm", "post_norm1", "post_norm2",
                      "gate", "attn"):
                assert f"layer{i}_{n}" in nodes, (ph, i, n)
        # one gain of a head's width for all heads
        assert nodes["layer3_q_norm"][2]["num_groups"] == "6"
        assert nodes["layer3_k_norm"][2]["num_groups"] == "2"
    args, _, _ = spec.symbol("decode").infer_shape_partial(
        data=(2, 1), lengths=(2,), positions=(2, 1), block_table=(2, 4),
        window_table=(2, 4), slots=(2,))
    shapes = dict(zip(spec.symbol("decode").list_arguments(), args))
    assert shapes["layer1_q_norm_gamma"] == (16,)
    assert shapes["layer1_k_norm_gamma"] == (16,)
    assert shapes["layer1_post_norm1_gamma"] == (64,)


def test_without_the_new_keys_the_nodes_are_not_there():
    d = ref.spec(CFG).to_dict()
    d["post_norm"] = False
    for ly in d["layers"]:
        del ly["mixer"]["qk_norm"]
    plain = HybridSpec.from_dict(d)
    for ph in ("prefill", "decode"):
        names = {n[1] for n in structure(plain.symbol(ph))}
        assert not [n for n in names if "post_norm" in n
                    or n.endswith(("_q_norm", "_k_norm"))]
    assert "post_norm" in plain.to_dict()


@pytest.mark.parametrize("part, key", [("mixer", "qk_norms"),
                                       ("ffn", "post_norm")])
def test_an_unknown_key_of_a_layer_is_refused_by_name(part, key):
    d = ref.spec(CFG).to_dict()
    d["layers"][1][part][key] = True
    with pytest.raises(MXNetError, match=f"layer 1.*{key}"):
        HybridSpec.from_dict(d)


def test_the_reference_refuses_a_program_without_the_keys(monkeypatch):
    from mxnet_tpu.models import hybrid_lm
    monkeypatch.setitem(hybrid_lm.MIXERS, "attention", tuple(
        k for k in hybrid_lm.MIXERS["attention"] if k != "qk_norm"))
    with pytest.raises(NotImplementedError, match="qk_norm.*post_norm"):
        ref.spec(CFG)


@pytest.mark.parametrize("family", ["solar_open2", "granitemoehybrid",
                                    "smallthinker", "deepseek_v3"])
def test_the_four_specs_there_were_build_the_parents_symbols(family):
    # tests/data/hybrid_symbols_pr39.json: ``structure`` of the parent
    # commit's symbols for the four tiny configurations
    import test_deepseek_v3
    import test_hybrid_lm
    import test_mamba2
    import test_smallthinker

    cfg = {"solar_open2": test_hybrid_lm.CFG,
           "granitemoehybrid": test_mamba2.CFG,
           "smallthinker": test_smallthinker.CFG,
           "deepseek_v3": test_deepseek_v3.CFG}[family]
    spec = importlib.import_module(
        f"benchmark.reference.{family}").spec(cfg)
    with open(os.path.join(ROOT, "tests", "data",
                           "hybrid_symbols_pr39.json")) as f:
        parent = json.load(f)[family]
    for ph in ("prefill", "decode"):
        assert structure(spec.symbol(ph)) == parent[ph]
    assert not spec.post_norm
    assert copy.deepcopy(spec.to_dict())["post_norm"] is False
