"""A prefill's K/V reaches the pools a page a copy
(``pallas_kernels.kv_pages_write``, chosen by
``ops.attention.paged_prefill_write`` from what it can see) — held
against the row-wise scatter it takes the place of: bit for bit on
every slot a reader may read, the path chosen from shapes alone and
counted on ``/metrics``, and the same tokens served end to end by both
families' prefill ops.  The kernel runs interpreted here
(``MXNET_PALLAS=1``); ``tests/test_tpu_compile.py`` compiles it for the
chip at the cells' shapes."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402
from mxnet_tpu.ops import attention as att, pallas_kernels as pk  # noqa: E402

from _engines import WAIT, build  # noqa: E402

KVB, P = 16, 24
T = 4 * KVB


def _calls():
    """(page kernel calls, row scatter calls) so far."""
    c = profiler.metrics_summary()["counters"]
    return (c.get("kv_write.page_kernel_calls", 0.0),
            c.get("kv_write.row_scatter_calls", 0.0))


# (block tables, lengths) of a (B, T) prefill over pools of P pages:
# the rows' pages are distinct and out of order, 0 pads the table
CASES = {
    "whole_pages": ([[5, 2, 9, 7]], [T]),
    "partial_last_page": ([[5, 2, 9, 0]], [2 * KVB + 5]),
    "nothing_live": ([[0, 0, 0, 0]], [0]),
    "two_rows": ([[5, 2, 9, 7], [11, 3, 0, 0]], [T - 1, KVB + 1]),
    # a windowed pool's table: the blocks behind the window hold 0
    "behind_the_window": ([[0, 0, 9, 7]], [3 * KVB + 2]),
}


def _drawn(case, dtype, W, seed=0):
    table, lengths = (np.asarray(x, np.int32) for x in CASES[case])
    rng = np.random.default_rng(seed)
    B = len(lengths)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) \
            .astype(dtype)

    return (draw(B, T, W), draw(B, T, W), draw(P, KVB, W), draw(P, KVB, W),
            jnp.asarray(table), jnp.asarray(lengths))


def _both_paths(monkeypatch, args):
    """(pools by the page kernel, pools by the row scatter)."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    pages0, rows0 = _calls()
    by_page = att.paged_prefill_write(*args)
    assert _calls() == (pages0 + 1, rows0), "the page kernel was not taken"
    monkeypatch.setenv("MXNET_PALLAS", "0")
    by_row = att.paged_prefill_write(*args)
    assert _calls() == (pages0 + 1, rows0 + 1)
    return by_page, by_row


@pytest.mark.parametrize("W", [512, 1280])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_page_path_equals_row_path(monkeypatch, case, dtype, W):
    """Every slot below the length of every live page, and every page no
    table names, bit for bit; the last live page's slots at and past the
    length hold the prompt's padding rows (the page path's contract)
    and the scratch page is not touched."""
    args = _drawn(case, dtype, W)
    by_page, by_row = _both_paths(monkeypatch, args)
    table, lengths = (np.asarray(a) for a in args[4:])
    for rows, pool, got, want in zip(args[:2], args[2:4], by_page, by_row):
        rows, pool, got, want = (
            np.asarray(a.astype(jnp.float32)) for a in (rows, pool, got,
                                                        want))
        named = set()
        for b, n in enumerate(lengths):
            for j in range(-(-int(n) // KVB)):
                page = int(table[b, j])
                if not page:        # behind the window: nowhere
                    continue
                named.add(page)
                live = min(int(n) - j * KVB, KVB)
                np.testing.assert_array_equal(got[page, :live],
                                              want[page, :live])
                np.testing.assert_array_equal(
                    got[page], rows[b, j * KVB:(j + 1) * KVB])
        for page in sorted(set(range(1, P)) - named):
            np.testing.assert_array_equal(got[page], pool[page])
            np.testing.assert_array_equal(want[page], pool[page])
        np.testing.assert_array_equal(got[0], pool[0])


def _start_given(args):
    return args, {"start": jnp.zeros_like(args[5])}


def _ragged_rows(args):
    k, v = (a[:, :T - 3] for a in args[:2])
    return (k, v) + args[2:], {}


def _half_tile_pages(args):
    # 8-row pages of bfloat16: half a sublane tile a page
    kp, vp = (a.reshape(2 * P, KVB // 2, -1) for a in args[2:4])
    return args[:2] + (kp, vp) + args[4:], {}


@pytest.mark.parametrize("why,W,reshape", [
    ("start_given", 512, _start_given),
    ("T_not_whole_pages", 512, _ragged_rows),
    ("page_not_whole_tiles", 512, _half_tile_pages),
    ("lanes_not_whole_tiles_compiled", 320, lambda args: (args, {})),
])
def test_what_the_shapes_refuse_takes_the_row_path(monkeypatch, why, W,
                                                   reshape):
    """The suffix / chunk / verify callers (``start``), a T or a page
    off the tiles and, compiled, a page row of 5 heads x 64 (a tp
    shard): the row scatter, counted as such, and no kernel."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    if why.endswith("compiled"):
        monkeypatch.setattr(pk, "_interpret", lambda: False)
        assert pk.paged_enabled(640) and not pk.paged_enabled(W)
    monkeypatch.setattr(pk, "kv_pages_write", None)     # must not be called
    args, kw = reshape(_drawn("partial_last_page", "bfloat16", W))
    pages0, rows0 = _calls()
    got = att.paged_prefill_write(*args, **kw)
    assert _calls() == (pages0, rows0 + 1)
    assert got[0].shape == args[2].shape


@pytest.mark.parametrize("kernel", [False, True])
def test_decode_attention_reads_the_same_after_either_path(monkeypatch,
                                                           kernel):
    """The readers mask by length: the decode step's attention over the
    pools is bit-equal whichever path wrote them, through the paged
    kernel and through the lax body."""
    H = 4
    args = _drawn("two_rows", "float32", 512, seed=3)
    by_page, by_row = _both_paths(monkeypatch, args)
    monkeypatch.setenv("MXNET_PALLAS", "1" if kernel else "0")
    q = jnp.asarray(np.random.default_rng(5).standard_normal((2, 1, 512)),
                    jnp.float32)
    outs = [np.asarray(att.paged_decode_attention(
        q, kp, vp, args[4], args[5], H)) for kp, vp in (by_page, by_row)]
    assert np.isfinite(outs[0]).all()
    np.testing.assert_array_equal(outs[0], outs[1])


# -- end to end: both families' prefill ops through DecodeEngine ---------

def _gpt2_engine():
    """The GPT-2 block (``PagedCacheWrite``): pages of 8 float32 rows."""
    from mxnet_tpu import models

    V, L, H, DM, MAXLEN, page = 61, 2, 2, 32, 96, 8
    sym = models.transformer_lm(V, MAXLEN, num_layers=L, num_heads=H,
                                d_model=DM, block_size=page)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, MAXLEN))],
             label_shapes=[("softmax_label", (2, MAXLEN))],
             for_training=False)
    mx.random.seed(11)
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0))
    arg, aux = mod.get_params()
    return build(
        {**arg, **aux}, vocab_size=V, num_layers=L, num_heads=H,
        d_model=DM, max_len=MAXLEN, kv_block=page, max_streams=2,
        decode_buckets=[1, 2], prefill_buckets=(32, 64), temperature=0.0,
        ctx=mx.cpu())


def _hybrid_engine():
    """The layer-list family (``GQAPrefillAttention``): one global and
    one windowed layer, so an ordinary and a windowed pool."""
    from benchmark.reference import smallthinker as ref

    cfg = {
        "family": "smallthinker", "hidden_size": 64, "num_hidden_layers": 2,
        "num_hidden_layers_published": 52, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
        "rms_norm_eps": 1e-6, "rope_layout": [0, 1, 1, 1] * 13,
        "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 32, "rope_theta": 1.5e6,
        "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
        "moe_ffn_hidden_size": 32, "initializer_range": 0.1,
        "attention_initializer_range": 0.3,
    }
    drawn = ref.draw(cfg, 7, embed_dtype="float32", dtype="float32")
    return build(
        ref.program_names(drawn), model=ref.spec(cfg), max_len=96,
        kv_block=16, max_streams=2, decode_buckets=(1, 2),
        cache_buckets=(6,), prefill_buckets=(32, 64), ctx=mx.cpu(),
        dtype="float32")


def _serve(make, prompts):
    eng = make()
    try:
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        return [np.asarray(f.result(timeout=WAIT)) for f in futs]
    finally:
        eng.close()


@pytest.mark.parametrize("make", [_gpt2_engine, _hybrid_engine])
def test_engines_serve_the_same_tokens_as_the_row_path(monkeypatch, make):
    """A prompt that ends inside a page and one longer than the hybrid's
    window (its first blocks are behind it), each through the page
    kernel and through the parent's row scatter under the same kernels
    otherwise: the same tokens."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 60, n).astype(np.int32) for n in (21, 60)]
    pages0, rows0 = _calls()
    by_page = _serve(make, prompts)
    pages1, rows1 = _calls()
    assert pages1 > pages0 and rows1 == rows0, \
        "the prefill programs did not take the page kernel"
    monkeypatch.setattr(att, "_writes_whole_pages", lambda *a: False)
    by_row = _serve(make, prompts)
    assert _calls() == (pages1, rows1), "the patched path counted itself"
    for got, want in zip(by_page, by_row):
        np.testing.assert_array_equal(got, want)
