"""The tile schedule of the prompt kernels — the grouped-query one
(``flash_mha_window``: ``flash_fwd_window`` / ``flash_fwd_mha``) and,
without a window, the latent one (``mla_flash``: ``mla_flash_fwd``): a
tile wholly inside the band runs without a mask, a tile an edge of the
band crosses is walked in sub-blocks of which only those a row can see
are computed and only those an edge cuts are masked.  Interpreted on the
CPU: the schedule against the lax body for windows that end inside a
tile and inside a sub-block, square and non-square tiles, groups of 1, 6
and 7 query heads (of 1, 2 and 4 latent heads a step); the prompt's
length as tests/test_prompt_lengths.py holds it, at sub-block edges too;
the host's count of the masked tiles and the computed scores
(``prompt_tile_work``) against the kernels' own blocks; and the
grouped-query kernels' program held to what it was before the latent
kernel shared its walk.  (The latent kernel's cases: a file of their own,
``test_latent_schedule.py``, so that two workers take the interpreted
sweeps.)"""

import hashlib

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ops import attention as att  # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402

# (block_q, block_k, sub, inner): a square tile of 4 x 4 sub-blocks whose
# interior tiles are updated in halves; a key tile twice the query tile,
# as the cells' (1024, 2048, 256, 512), and one half of it; a tile that
# is its own sub-block (no walk)
TILES = [(128, 128, 32, 64), (64, 128, 32, 32), (128, 64, 32, 128),
         (128, 128, 128, 128)]


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "1")


def use_tiles(monkeypatch, tiles):
    monkeypatch.setattr(pk, "_mha_window_tiles", lambda t, window: tiles)


def inputs(T, H, Hkv, B=1, D=16, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(B * n, T, D)).astype(np.float32)
            for n in (H, Hkv, Hkv)]


def lax_body(q, k, v, window, H, Hkv):
    """``_blockwise_attention_partial_lax`` under the same band, K and V
    repeated to the query heads."""
    BH, T, D = q.shape
    B, G = BH // H, H // Hkv

    def bthd(x, n):
        return jnp.asarray(x).reshape(B, n, T, D).transpose(0, 2, 1, 3)

    o, m, l = att._blockwise_attention_partial_lax(
        bthd(q, H), jnp.repeat(bthd(k, Hkv), G, axis=2),
        jnp.repeat(bthd(v, Hkv), G, axis=2), True, 64, 0, window=window)
    out = att.normalize_attention_state(o, m, l, jnp.float32)
    return np.asarray(out.transpose(0, 2, 1, 3).reshape(BH, T, D))


# -- the schedule against the lax body ---------------------------------------

@pytest.mark.parametrize("T, window, H, Hkv", [
    (3000, 1000, 7, 1),     # the window ends inside a tile and a sub-block
    (1024, 100, 6, 1),      # both edges cross one tile
    (1024, 4096, 2, 2),     # a window wider than the prompt: every key
    (2100, 0, 6, 1),        # global, the last tile ragged
])
def test_the_chosen_tiles_against_the_lax_body(interpreted, T, window, H,
                                               Hkv):
    # the tiles the kernel picks for itself at this T (1,024 rows over
    # key tiles of 1,024 or 2,048, sub-blocks of 256)
    q, k, v = inputs(T, H, Hkv, seed=T)
    got = np.asarray(pk.flash_mha_window(q, k, v, window, H, Hkv))
    np.testing.assert_allclose(got, lax_body(q, k, v, window, H, Hkv),
                               atol=2e-5)


@pytest.mark.parametrize("tiles", TILES, ids=str)
@pytest.mark.parametrize("T, window", [
    (512, 0), (512, 200), (500, 100), (512, 129), (300, 33)])
def test_every_tile_shape_against_the_lax_body(interpreted, monkeypatch,
                                               tiles, T, window):
    use_tiles(monkeypatch, tiles)
    q, k, v = inputs(T, 6, 2, seed=T + window)
    got = np.asarray(pk.flash_mha_window(q, k, v, window, 6, 2))
    np.testing.assert_allclose(got, lax_body(q, k, v, window, 6, 2),
                               atol=2e-5)


# -- the prompt's length ------------------------------------------------------

T = 512
# a row of the batch each: one under, on and one over a sub-block's edge
# and a tile's; one row; the whole bucket
LENGTHS = [(31, 32), (33, 127), (128, 129), (1, 512)]


def padded(x, lengths, block):
    """``x`` with what a bucket's padding may hold: large finite values
    in the rows past each prompt inside its last live query tile, NaN
    in the tiles past it (what is never computed may hold anything)."""
    x = np.array(x)
    per = x.shape[0] // len(lengths)
    for b, n in enumerate(lengths):
        rows = x[b * per:(b + 1) * per]
        dead = -(-n // block) * block
        rows[:, n:dead] = 1e4 * np.sign(rows[:, n:dead])
        rows[:, dead:] = np.nan
    return x


@pytest.mark.parametrize("tiles", TILES[:3], ids=str)
@pytest.mark.parametrize("lengths", LENGTHS)
@pytest.mark.parametrize("window", [0, 200])
def test_rows_below_a_length_do_not_depend_on_it(interpreted, monkeypatch,
                                                 tiles, window, lengths):
    use_tiles(monkeypatch, tiles)
    H, Hkv = 3, 1
    q, k, v = inputs(T, H, Hkv, B=2, seed=2)
    whole = np.asarray(pk.flash_mha_window(q, k, v, window, H, Hkv))
    got = np.asarray(pk.flash_mha_window(
        *(padded(x, lengths, tiles[0]) for x in (q, k, v)), window, H, Hkv,
        lengths=jnp.asarray(lengths, jnp.int32)))
    for b, n in enumerate(lengths):
        rows = slice(b * H, (b + 1) * H)
        assert np.array_equal(got[rows, :n], whole[rows, :n])
        assert np.all(got[rows, n:] == 0)


@pytest.mark.parametrize("tiles", TILES[:3], ids=str)
def test_no_lengths_is_the_buckets_rows(interpreted, monkeypatch, tiles):
    use_tiles(monkeypatch, tiles)
    q, k, v = inputs(T, 2, 1, B=2, seed=3)
    for window in (0, 200):
        whole = np.asarray(pk.flash_mha_window(q, k, v, window, 2, 1))
        assert np.array_equal(whole, np.asarray(pk.flash_mha_window(
            q, k, v, window, 2, 1, lengths=jnp.asarray((T, T)))))


# -- the host's counts against the kernel's own blocks ------------------------

def kernel_counts(monkeypatch, call, width=16):
    """(walked, masked, computed) of the interpreted kernel ``call``
    runs, counted as it runs: the grid steps that took a computing
    body, those of them whose body is the edge tiles', and the elements
    of every q . k product over ``width`` lanes (a head's; the latent
    kernel's rotary product beside it is over other lanes)."""
    hits = {"walked": 0, "masked": 0, "computed": 0}
    real_when, real_dot = pk.pl.when, pk._dot

    def bump(key, n):
        hits[key] += n

    def when(cond):
        def bind(body):
            if body.__name__ not in ("_interior", "_edge"):
                return real_when(cond)(body)

            def counted():
                jax.debug.callback(lambda: bump("walked", 1))
                if body.__name__ == "_edge":
                    jax.debug.callback(lambda: bump("masked", 1))
                body()
            return real_when(cond)(counted)
        return bind

    def dot(a, b, ca, cb):
        if (ca, cb) == (1, 1) and a.shape[1] == width:  # the scores
            n = a.shape[0] * b.shape[0]
            jax.debug.callback(lambda: bump("computed", n))
        return real_dot(a, b, ca, cb)

    monkeypatch.setattr(pk.pl, "when", when)
    monkeypatch.setattr(pk, "_dot", dot)
    for jitted in (pk._flash_mha_window, pk._mla_flash):
        jitted.clear_cache()                # a trace of its own, with
    jax.block_until_ready(call())           # these two, dropped after
    jax.effects_barrier()
    for jitted in (pk._flash_mha_window, pk._mla_flash):
        jitted.clear_cache()
    monkeypatch.setattr(pk.pl, "when", real_when)
    monkeypatch.setattr(pk, "_dot", real_dot)
    return hits["walked"], hits["masked"], hits["computed"]


@pytest.mark.parametrize("tiles", TILES, ids=str)
@pytest.mark.parametrize("window", [0, 40, 200])
@pytest.mark.parametrize("length", [129, 300, 512])
def test_prompt_tile_work_is_the_kernels_own_blocks(
        interpreted, monkeypatch, tiles, window, length):
    use_tiles(monkeypatch, tiles)
    q, k, v = inputs(T, 2, 1)
    walked, _ = pk.prompt_tile_visits(length, T, window)
    masked, computed, needed = pk.prompt_tile_work(length, T, window)
    got = kernel_counts(monkeypatch, lambda: pk.flash_mha_window(
        q, k, v, window, 2, 1, lengths=jnp.asarray([length])))
    assert got == (2 * walked, 2 * masked, 2 * computed)    # two heads
    w = min(length, window or length)
    assert needed == w * (w + 1) // 2 + (length - w) * w <= computed
    assert masked <= walked


def seen_blocks(length, rows, window, block_q, block_k, sub, guard=None):
    """(walked, masked, computed) from the pairs themselves: a (sub x
    sub) block of the score matrix is computed where a row of a live
    query tile sees one of its columns (``guard``: of a block of
    ``guard`` rows that holds a row of the prompt — the latent kernel
    leaves the others of its last live tile out), a tile is walked where
    a live query tile's row sees a column of it and masked where an
    edge cuts one of its blocks."""
    n = rows // sub
    i = np.arange(n)[:, None] * sub
    j = np.arange(n)[None, :] * sub
    seen = j <= i + sub - 1
    whole = j + sub - 1 <= i
    if window:
        seen &= j + sub - 1 > i - window
        whole &= j > i + sub - 1 - window
    seen[-(-length // block_q) * block_q // sub:] = False
    computed = seen.copy()
    if guard:
        computed[-(-length // guard) * guard // sub:] = False

    def tiles(blocks):
        return int(blocks.reshape(rows // block_q, block_q // sub,
                                  rows // block_k, block_k // sub)
                   .any(axis=(1, 3)).sum())

    return tiles(seen), tiles(seen & ~whole), int(computed.sum()) * sub * sub


@pytest.mark.parametrize("length, rows, window", [
    (17000, 32768, 4096), (17000, 32768, 0), (32768, 32768, 4096),
    (8192, 8192, 4096), (5000, 8192, 0), (4096, 4096, 4096),
    (700, 1024, 4096), (3000, 4096, 1000)])
def test_prompt_tile_work_counts_the_blocks_a_row_can_see(length, rows,
                                                          window):
    bq, bk, sub, _ = pk._mha_window_tiles(rows, window)
    walked, _ = pk.prompt_tile_visits(length, rows, window)
    masked, computed, _ = pk.prompt_tile_work(length, rows, window)
    assert (walked, masked, computed) == seen_blocks(
        length, rows, window, bq, bk, sub)


def test_prompt_tile_work_at_the_cells_shapes():
    # longdoc's t32768: query tiles of 1,024 rows over key tiles of
    # 2,048, edge tiles walked in sub-blocks of 256 — a (1,024 x 1,024)
    # span an edge cuts computes 10 of its 16 sub-blocks
    assert pk._mha_window_tiles(32768, 4096) == (1024, 2048, 256, 512)
    assert pk._mha_window_tiles(1024, 0) == (1024, 1024, 256, 512)
    edge, span = 10 * 256 * 256, 1024 * 1024
    # a windowed layer at a prompt a little over half the bucket: 17
    # live query tiles of three key tiles each but the first four's
    # (1, 1, 2, 2); the diagonal crosses one of a query tile's key
    # tiles, the window's lower edge another from the fifth on, with
    # three whole spans between them
    assert pk.prompt_tile_visits(17000, 32768, 4096) == (45, 45)
    assert pk.prompt_tile_work(17000, 32768, 4096) == (
        4 + 2 * 13, (1 + 2 + 3) * span + 4 * edge
        + 13 * (3 * span + 2 * edge),
        4096 * 4097 // 2 + (17000 - 4096) * 4096)
    # the global layer: only the diagonal's 17 tiles are masked
    assert pk.prompt_tile_visits(17000, 32768) == (81, 191)
    assert pk.prompt_tile_work(17000, 32768) == (
        17, 136 * span + 17 * edge, 17000 * 17001 // 2)
    # a whole bucket: computed over needed, as ISSUE 43 has it.  (Tiles
    # of 1,024 masked whole, as before PR 43: 1.25 windowed, 1.031
    # global.)
    m, c, n = pk.prompt_tile_work(32768, 32768, 4096)
    assert (m, round(c / n, 4)) == (4 + 2 * 28, 1.0625)
    m, c, n = pk.prompt_tile_work(32768, 32768)
    assert (m, round(c / n, 4)) == (32, 1.0078)


@pytest.mark.parametrize("block_q, block_k, sub", [
    (1024, 2048, 128), (1024, 1024, 256), (1024, 512, 128)])
def test_band_walk_without_a_window_is_the_packed_familys_walk(
        block_q, block_k, sub):
    # the diagonal alone: what `_walk` gives the packed kernels, the
    # whole span and the diagonal's block of each row sub-block
    for off in pk._crossing_offsets(block_q, block_k):
        if off % sub:
            continue
        want = [(blk, [p for p in ((whole, None), (diag, (None, 0)))
                       if p[0] is not None])
                for blk, whole, diag in pk._walk(off, block_q, block_k, sub)]
        assert pk._band_walk(off, block_q, block_k, sub) == want


# -- the grouped-query kernels are left alone ---------------------------------

def test_the_grouped_query_schedule_at_the_cells_shapes_is_pinned():
    # longdoc's and mixed's buckets: what `_mha_window_tiles` and
    # `_band_schedule` gave before the latent kernel shared them
    for t, tiles in ((32768, (1024, 2048, 256, 512)),
                     (8192, (1024, 2048, 256, 512)),
                     (4096, (1024, 2048, 256, 512)),
                     (2048, (1024, 2048, 256, 512)),
                     (1024, (1024, 1024, 256, 512)),
                     (3000, (1024, 1024, 256, 512))):
        assert pk._mha_window_tiles(t, 4096) == tiles
        assert pk._mha_window_tiles(t, 0) == tiles
    plan = pk._band_schedule(32768, 1024, 2048, 256, 4096)
    assert (plan.band, plan.edges, plan.interior) == (
        4096, (-5120, -4096, -1024, 0), True)
    assert plan.first.tolist() == [0] * 6 + [i // 2 for i in range(2, 28)]
    assert plan.last.tolist() == [i // 2 for i in range(32)]
    assert int(plan.masked.sum()) == 60
    assert int(plan.scores.sum()) == 133693440
    assert pk.prompt_tile_work(32768, 32768, 4096) == (
        60, 133693440, 125831168)
    plan = pk._band_schedule(8192, 1024, 2048, 256, 0)
    assert (plan.band, plan.edges, plan.interior) == (0, (-1024, 0), True)
    assert plan.first.tolist() == [0] * 8
    assert plan.last.tolist() == [0, 0, 1, 1, 2, 2, 3, 3]
    assert plan.masked.tolist() == [1] * 8
    assert plan.scores.tolist() == [655360 + 1048576 * i for i in range(8)]
    assert pk.prompt_tile_work(5000, 8192) == (5, 13762560, 12502500)
    assert pk.prompt_tile_work(5000, 8192, 4096) == (6, 13369344, 12093440)


@pytest.mark.parametrize("Hq, Hkv, t, window, text", [
    (48, 8, 32768, 4096, ("d9c22be346b75a79792e6a37dafd218f"
                          "18bf7f411f05e3dd45442307c7929ce1", 94256)),
    (48, 8, 32768, 0, ("ac6296fba1cc7ccbe48a815a99bc6175"
                       "d91e80ac39efb57b74111e6a8bb9c550", 54182)),
    (28, 4, 8192, 4096, ("aabfaf10d3faeac23009eb28d1cf46bc"
                         "9a1872310b424d2419515f97898fb596", 94245)),
], ids=["longdoc_window", "longdoc_global", "mixed_window"])
def test_the_grouped_query_kernels_program_is_the_parents(Hq, Hkv, t, window,
                                                          text):
    # `flash_mha_window` traced at a cell's shape (nothing runs): the
    # program's text — the kernel's jaxpr, its grid and blocks —
    # character for character what commit 3c67cef traced (its sha256 and
    # length), from before `_band_update` was shared with `mla_flash`.
    # A change MEANT for this kernel re-pins both; one meant for the
    # latent kernel must not move them
    def sds(heads):
        return jax.ShapeDtypeStruct((heads, t, 128), jnp.bfloat16)

    got = str(jax.make_jaxpr(
        lambda q, k, v, n: pk.flash_mha_window(q, k, v, window, Hq, Hkv,
                                               lengths=n))(
        sds(Hq), sds(Hkv), sds(Hkv), jax.ShapeDtypeStruct((1,), jnp.int32)))
    assert (hashlib.sha256(got.encode()).hexdigest(), len(got)) == text
