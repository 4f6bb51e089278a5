"""The third spec of the layer-list family (``models/hybrid_lm.py``):
sliding-window layers with rotary positions beside global layers without
positions, ReLU-gated experts whose router reads the block's input —
against its plain reference (``benchmark/reference/smallthinker.py``)
at a small size, seeded weights: prefill + decode through the pages of
BOTH pools on logits, each mechanism left out failing that comparison,
and the engine's second allocator (pages behind the window given back
and reused, both pools empty after retirement and preemption, admission
held by either pool)."""

import copy
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.executor import build_graph_fn  # noqa: E402
from mxnet_tpu.models.hybrid_lm import HybridSpec  # noqa: E402

from benchmark.reference import smallthinker as ref  # noqa: E402
from _engines import WAIT, Family  # noqa: E402

# the published shape at a size a test can hold: two periods of the
# [global, windowed x 3] pattern, 4 query heads over 2 KV heads, 8
# experts, 2 a token, a window of two pages
W, KVB = 32, 16
CFG = {
    "family": "smallthinker", "hidden_size": 64, "num_hidden_layers": 8,
    "num_hidden_layers_published": 52, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "rms_norm_eps": 1e-6, "rope_layout": [0, 1, 1, 1] * 13,
    "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": W,
    "rope_theta": 1.5e6, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 32,
    # a wider draw than the published 0.02: at d 64 the blocks must
    # move the residual stream enough for a missing mechanism to show
    "initializer_range": 0.1, "attention_initializer_range": 0.3,
}


def draw(seed=7, dtype="float32"):
    return ref.draw(CFG, seed, embed_dtype=dtype, dtype=dtype)


# -- the two symbols, driven by hand: logits through both pools ----------

class Programs:
    """The spec's prefill and decode symbols over hand-kept pools and
    tables (one stream): what the engine's programs compute, with the
    logits kept.  The windowed pools' table holds the scratch page for
    every block the window no longer reaches, as the engine's does."""

    def __init__(self, drawn, max_len=128):
        self.spec = ref.spec(CFG)
        self.params = {k: jnp.asarray(v)
                       for k, v in ref.program_names(drawn).items()}
        self.mb = max_len // KVB
        layout = self.spec.pools(1 + self.mb, KVB, 2, np.float32,
                                 window_blocks=1 + W // KVB + 2)
        self.names = [n for n, _, _, _ in layout]
        self.pools = [jnp.zeros(shape, dt) for _, shape, dt, _ in layout]
        self.fn = {ph: jax.jit(build_graph_fn(self.spec.symbol(ph)),
                               static_argnums=(3,))
                   for ph in ("prefill", "decode")}
        self.key = jax.random.PRNGKey(0)

    def fresh(self):
        """The same programs over pools nobody has written."""
        self.pools = [jnp.zeros_like(p) for p in self.pools]
        return self

    def tables(self, length):
        """(block table, window table) for a stream about to be fed the
        token at position ``length - 1``: windowed page ids cycle
        through the pool's few pages, as a reused page would."""
        full = np.zeros((1, self.mb), np.int32)
        win = np.zeros((1, self.mb), np.int32)
        n = -(-length // KVB)
        full[0, :n] = 1 + np.arange(n)
        first = max(length - W, 0) // KVB    # the oldest block still seen
        for b in range(first, n):
            win[0, b] = 1 + b % (W // KVB + 2)
        return full, win

    def run(self, phase, tokens, positions, lengths, keep_from=0):
        full, win = self.tables(int(lengths[0]))
        if phase == "prefill":
            win[0, :keep_from] = 0
        args = dict(self.params, data=jnp.asarray(tokens),
                    positions=jnp.asarray(positions),
                    lengths=jnp.asarray(lengths),
                    block_table=jnp.asarray(full),
                    window_table=jnp.asarray(win),
                    slots=jnp.zeros((1,), jnp.int32))
        args.update(zip(self.names, self.pools))
        outs, _ = self.fn[phase](args, {}, self.key, False)
        self.pools = list(outs[1:])
        return np.asarray(outs[0])[0, 0]

    def serve(self, seq, n_prompt, bucket):
        """Logits at positions n_prompt - 1 .. len(seq) - 1: a prefill
        of ``seq[:n_prompt]`` padded to ``bucket``, then a decode step
        a token."""
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n_prompt] = seq[:n_prompt]
        rows = [self.run("prefill", toks, np.arange(bucket)[None],
                         np.asarray([n_prompt], np.int32),
                         keep_from=max(n_prompt - W + 1, 0) // KVB)]
        for t in range(n_prompt, len(seq)):
            rows.append(self.run(
                "decode", np.asarray([[seq[t]]], np.int32),
                np.asarray([[t]], np.int32), np.asarray([t + 1], np.int32)))
        return np.stack(rows)


def sequence(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG["vocab_size"], n).astype(np.int32)


# (prompt, total): a short prompt whose decode crosses the window, and a
# prompt longer than the window (its early pages are never written)
CASES = [(20, 90), (75, 110)]


@pytest.fixture(scope="module")
def served():
    """The program's logits and the reference's, for each case."""
    drawn = draw()
    progs = Programs(drawn)      # one build for the cases
    out = []
    for i, (n_prompt, total) in enumerate(CASES):
        seq = sequence(20 + i, total)
        got = progs.fresh().serve(seq, n_prompt, bucket=96)
        out.append((seq, n_prompt, got))
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
def test_a_mechanism_left_out_fails_the_comparison(served, mechanism):
    drawn, runs = served
    worst = 0.0
    for seq, n_prompt, got in runs:
        wrong = reference_rows(drawn, seq, n_prompt, mechanism)
        worst = max(worst, float(np.abs(got - wrong).max()))
    assert worst > 1e-2, (mechanism, worst)


def test_bfloat16_program_is_close_to_the_float32_reference():
    drawn = draw(dtype="bfloat16")
    seq, n_prompt = sequence(31, 100), 70
    progs = Programs(drawn)
    progs.pools = [p.astype(jnp.bfloat16) if "pool" in n else p
                   for n, p in zip(progs.names, progs.pools)]
    got = progs.serve(seq, n_prompt, bucket=96).astype(np.float32)
    want = reference_rows(drawn, seq, n_prompt)
    # bfloat16 products against float32 ones, at this draw's width: a
    # top-2 set of 8 experts flips on the eighth bit (the reference's
    # own bfloat16 form reads 0.09 here, the program 0.15), so the
    # stated tolerance is on the mean: a quarter of the logits' RMS
    assert np.abs(got - want).mean() < 0.25 * np.sqrt((want ** 2).mean())


def test_kernels_interpreted_match_the_lax_bodies(served, monkeypatch):
    drawn, runs = served
    seq, n_prompt, lax_rows = runs[1]
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk.enabled()
    got = Programs(drawn).serve(seq[:n_prompt + 6], n_prompt, bucket=96)
    np.testing.assert_allclose(got, lax_rows[:7], atol=2e-4)


# -- the engine: two allocators -----------------------------------------

FAMILY = Family(ref, CFG, pad=352, max_len=352, kv_block=KVB,
                max_streams=3, decode_buckets=(1, 2, 4),
                cache_buckets=(8, 22), prefill_buckets=(32, 96))
# the tests that name no argument share one engine (``engines``) and read
# its counters from ``reset_stats()`` on
make_engine, served_gap = FAMILY.engine, FAMILY.served_gap


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


def small_tiles(monkeypatch):
    """From here on the kernels interpreted, the prompt kernels in tiles
    of 32 rows: a bucket of 96 is three query tiles."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_mha_block", lambda block_size, t: 32)
    monkeypatch.setattr(pk, "_mha_window_tiles",
                        lambda t, window: (32, 32, 32, 32))


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


# (the prompts alone: tests/test_afmoe.py, tests/test_deepseek_v3.py)
@pytest.mark.parametrize("lengths", [(20, 96)], ids=["both"])
def test_the_engine_counts_the_tiles_its_prompt_kernels_walk_and_skip(
        monkeypatch, lengths):
    # (an engine of its own: its programs are the interpreted kernels')
    small_tiles(monkeypatch)
    eng, drawn = make_engine(prefill_buckets=(96,))
    rng = np.random.default_rng(6)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in lengths]
    with eng:
        outs = [f.result(timeout=WAIT) for f in
                [eng.submit(p, max_new_tokens=6) for p in ps]]
        st = eng.stats()
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    # six windowed layers (a band of 32 keys: two tiles a query tile
    # past the first) and two global ones (1 + 2 + 3), three query
    # tiles; a prompt of 20 rows has one live tile a layer
    bucket, live = 6 * (1 + 2 + 2) + 2 * (1 + 2 + 3), 8
    assert ref.spec(CFG).prompt_attention() == tuple(
        (0 if i % 4 == 0 else W, False) for i in range(8))
    skipped = sum(bucket - live for n in lengths if n == 20)
    assert (st["prefill_tiles_walked"], st["prefill_tiles_skipped"]) == (
        len(lengths) * bucket - skipped, skipped)
    assert st["prefill_tiles_skipped_share"] == round(
        skipped / (len(lengths) * bucket), 4)
    # every tile a windowed layer walks here is crossed by an edge of
    # its band, a global layer's diagonal tiles alone
    assert st["prefill_tiles_masked"] == sum(
        {20: 6 * 1 + 2 * 1, 96: 6 * 5 + 2 * 3}[n] for n in lengths)
    assert st["prefill_scores_computed_over_needed"] > 1.0


def watch_window_pages(eng, monkeypatch):
    """Record the most windowed pages any one owner held, and fail the
    moment a page is handed out while held."""
    alloc = eng._walloc
    held, most = {}, {}
    real_alloc, real_free = alloc.alloc, alloc.free

    def a(n, owner=None):
        pages = real_alloc(n, owner=owner)
        for p in pages or ():
            assert p not in held, f"page {p} given to two streams"
            held[p] = owner
        mine = sum(1 for o in held.values() if o == owner)
        most[owner] = max(most.get(owner, 0), mine)
        return pages

    def f(pages):
        for p in pages:
            del held[p]
        real_free(pages)

    monkeypatch.setattr(alloc, "alloc", a)
    monkeypatch.setattr(alloc, "free", f)
    return held, most


def test_a_long_stream_holds_a_windows_pages_and_gives_the_rest_back(
        engines, monkeypatch):
    eng, drawn = engines(make_engine)
    held, most = watch_window_pages(eng, monkeypatch)
    rng = np.random.default_rng(3)
    long_prompt = rng.integers(1, 96, 12).astype(np.int32)
    # 10 x W tokens: 20 pages of context through W / KVB + 2 = 4
    first = eng.submit(long_prompt, max_new_tokens=10 * W - 12)
    others = [eng.submit(rng.integers(1, 96, n).astype(np.int32),
                         max_new_tokens=m)
              for n, m in ((40, 70), (9, 60), (80, 50), (25, 90))]
    out = first.result(timeout=WAIT)
    outs = [f.result(timeout=WAIT) for f in others]
    st = eng.stats()
    assert max(most.values()) <= W // KVB + 2
    assert st["window_pages"] == 3 * (W // KVB + 2)
    # pages given back were taken again: five streams, 12 pages
    assert st["window_pages_released"] >= 10 * W // KVB - 3
    assert st["window_pages_live"] == 0 and not held
    assert eng._alloc.used_blocks == 0 and st["preempted"] == 0
    assert 0 < st["window_pages_held_share"] < 0.6
    assert st["window_context_tokens"] < st["context_tokens"]
    assert st["window_prefill_pairs"] > 0
    # a stale or shared page would show in the logits
    assert served_gap(drawn, long_prompt, out) < 1e-4
    assert len(out) == 10 * W - 12 and all(len(o) for o in outs)


def test_preemption_and_retirement_leave_both_pools_empty(monkeypatch):
    # 13 ordinary pages for three streams that grow to 6 each: someone
    # is thrown out, gives back its pages of both pools, and comes back
    # (an engine of its own: the pool is sized for it)
    eng, drawn = make_engine(cache_blocks=14, max_len=96,
                             cache_buckets=(6,))
    held, _ = watch_window_pages(eng, monkeypatch)
    rng = np.random.default_rng(5)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in (30, 41, 36)]
    with eng:
        outs = [f.result(timeout=WAIT) for f in
                [eng.submit(p, max_new_tokens=50) for p in ps]]
        st = eng.stats()
    assert st["preempted"] >= 1
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    assert not held and st["window_pages_live"] == 0
    assert eng._alloc.used_blocks == 0 and eng._walloc.used_blocks == 0


@pytest.mark.parametrize("short", ["pages", "window_pages"])
def test_admission_waits_when_either_pool_is_short(engines, short):
    eng, _ = engines(make_engine)
    alloc = eng._alloc if short == "pages" else eng._walloc
    taken = alloc.alloc(alloc.free_blocks - 1, owner="test")
    fut = eng.submit(np.arange(1, 41, dtype=np.int32), 4)
    with pytest.raises(Exception):
        fut.result(timeout=1.0)          # held in the queue
    assert eng.stats()["pending"] == 1
    alloc.free(taken)
    with eng._cond:
        eng._cond.notify_all()
    assert len(fut.result(timeout=WAIT)) == 4


@pytest.mark.parametrize("kw, feature", [
    (dict(prefix_cache=1), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(spec_tokens=2), "spec_tokens"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(tp=2), "tp=2"),
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


# -- the spec ------------------------------------------------------------

def structure(sym):
    """A symbol's nodes in order, (op, name, attrs) each; a name the
    symbol library numbered loses its number (a process-wide count)."""
    out = []
    for n in json.loads(sym.tojson())["nodes"]:
        given = n["name"].startswith(("layer", "tok_", "final_", "head",
                                      "moe_", "last_row"))
        out.append([n["op"],
                    n["name"] if given else re.sub(r"\d+$", "", n["name"]),
                    n.get("attrs", n.get("attr", {}))])
    return out


LAST_ROW = ("SwapAxis", "SequenceLast", "expand_dims")


@pytest.mark.parametrize("family", ["solar_open2", "granitemoehybrid"])
def test_the_specs_there_were_build_the_symbols_they_built(family):
    # tests/data/hybrid_symbols_pr32.json: ``structure`` of the parent
    # commit's symbols for the two tiny configurations; this PR's differ
    # by the prefill's last-row gather before the final norm, alone
    import importlib

    import test_hybrid_lm
    import test_mamba2

    cfg = test_hybrid_lm.CFG if family == "solar_open2" else test_mamba2.CFG
    spec = importlib.import_module(
        f"benchmark.reference.{family}").spec(cfg)
    with open(os.path.join(ROOT, "tests", "data",
                           "hybrid_symbols_pr32.json")) as f:
        parent = json.load(f)[family]
    assert structure(spec.symbol("decode")) == parent["decode"]
    now = structure(spec.symbol("prefill"))
    added = [n for n in now if n[0] in LAST_ROW]
    assert [n[0] for n in added] == list(LAST_ROW)
    assert [n for n in now if n[0] not in LAST_ROW] == parent["prefill"]
    assert "positions" not in spec.feeds and not spec.window


def test_spec_is_data_and_names_its_second_pool():
    spec = ref.spec(CFG)
    again = HybridSpec.from_dict(copy.deepcopy(spec.to_dict()))
    assert again.to_dict() == spec.to_dict()
    for ph in ("prefill", "decode"):
        assert structure(again.symbol(ph)) == structure(spec.symbol(ph))
    assert spec.window == W
    assert spec.feeds == ("data", "lengths", "block_table", "slots",
                          "positions", "window_table")
    assert spec.cache_kinds() == ("pages",) + ("window_pages",) * 3 \
        + ("pages",) + ("window_pages",) * 3
    kinds = spec.pool_kinds()
    assert kinds.count("window_pages") == 12 and kinds[-1] == "counters"
    shapes = {n: s for n, s, _, _ in spec.pools(50, KVB, 2, np.float32,
                                                window_blocks=9)}
    assert shapes["layer0_kpool"][0] == 50
    assert shapes["layer1_kpool"][0] == shapes["layer7_vpool"][0] == 9
    assert "window" in spec.name


@pytest.mark.parametrize("part, key", [("mixer", "windw"),
                                       ("ffn", "activation")])
def test_an_unknown_key_of_a_layer_is_refused_by_name(part, key):
    d = ref.spec(CFG).to_dict()
    d["layers"][1][part][key] = 4
    with pytest.raises(MXNetError, match=f"layer 1.*{key}"):
        HybridSpec.from_dict(d)


def test_two_windows_are_refused():
    d = ref.spec(CFG).to_dict()
    d["layers"][2]["mixer"]["window"] = 2 * W
    with pytest.raises(MXNetError, match="one window is built"):
        HybridSpec.from_dict(d)
