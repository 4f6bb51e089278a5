"""The latent prompt kernel (``mla_flash``: ``mla_flash_fwd``) under the
band walk of ``tests/test_window_schedule.py``, whose helpers these cases
use: every tile shape against the lax body, the prompt's length, the
host's count of masked tiles and computed scores against the kernel's own
blocks.  A file of its own so that a second xdist worker takes half of the
interpreted sweeps."""

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import hybrid as hy
from mxnet_tpu.ops import pallas_kernels as pk

from test_window_schedule import (LENGTHS, T, interpreted,  # noqa: F401
                                  kernel_counts, padded, seen_blocks)

# (block_q, block_k, sub, inner, heads a step): TILES with the heads of a
# grid step — all four, a pair twice, and one at a time
MLA_TILES = [(128, 128, 32, 64, 4), (64, 128, 32, 32, 2),
             (128, 64, 32, 128, 4), (128, 128, 128, 128, 1)]
# heads, nope, v, scale (the rotary 8): a nope width with which v's
# lanes start inside a block of four heads' (copied out) and on a block
# of two's (a window on kv)
MLA_DIMS = (4, 24, 16, 0.2)


def use_mla_tiles(monkeypatch, tiles):
    monkeypatch.setattr(pk, "_mla_tiles", lambda t, *widths: tiles)


def mla_inputs(T, B=1, seed=0):
    H, n, dv, _ = MLA_DIMS
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(B, T, lanes)).astype(np.float32)
            for lanes in (H * (n + 8), H * 8, H * (n + dv), 8)]


def mla_lax_body(q, q_r, kv, k_r):
    H, n, _, scale = MLA_DIMS
    return np.asarray(hy.mla_causal(
        *(jnp.asarray(x) for x in (q[..., :H * n], q_r, kv[..., :H * n],
                                   k_r, kv[..., H * n:])), H, scale))


def held_to_the_lax_body(xs, lengths):
    """``mla_flash`` over ``xs``: rows below a length (None: every row)
    the lax body's, rows at and past it zeros."""
    got = np.asarray(pk.mla_flash(
        *xs, *MLA_DIMS,
        lengths=None if lengths is None else jnp.asarray(lengths)))
    want = mla_lax_body(*xs)
    for b, n in enumerate(lengths or [xs[0].shape[1]] * len(want)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-5)
        assert np.all(got[b, n:] == 0)


# every shape `_mla_tiles` can return: one query tile of the bucket's
# rows in 128s (sub-blocks of 128 or 256, its rows whole or in 512s),
# 1,024 rows over key tiles of 1,024, and of 2,048 where the rows
# divide; prompts that end inside an edge sub-block, leave a block of
# 512 rows out, fill no tile and fill the bucket
@pytest.mark.parametrize("T, lengths", [
    (200, None), (200, (77,)), (640, (300, 640)), (1024, (513, 1000)),
    (1500, None), (2048, (1500,)), (2100, (1100,))])
def test_the_latent_kernels_chosen_tiles_against_the_lax_body(
        interpreted, T, lengths):
    held_to_the_lax_body(mla_inputs(T, B=len(lengths or [0]), seed=T),
                         lengths)


def test_the_latent_kernels_tiles_come_from_its_shapes():
    # the walk from the bucket alone (the host's counts ask with no
    # width), the heads a step from their count and rotary width
    for t, want in ((200, (256, 256, 256, 256)), (640, (640, 640, 128, 640)),
                    (1024, (1024, 1024, 256, 512)),
                    (2048, (1024, 2048, 256, 512)),
                    (3000, (1024, 1024, 256, 512)),
                    (8192, (1024, 2048, 256, 512))):
        assert pk._mla_tiles(t, 128, 128, 64, 128) == want + (4,)
        assert pk._mla_tiles(t, 0, 0, 0, 0)[:4] == want
        assert pk._prompt_schedule(t, 0, True)[0] == want[0]
    assert pk._mla_tiles(1024, 6, 128, 64, 128)[4] == 2
    assert pk._mla_tiles(1024, 4, 16, 8, 16)[4] == 4     # interpreted
    assert pk._mla_tiles(1024, 3, 16, 8, 16)[4] == 1


@pytest.mark.parametrize("tiles", MLA_TILES, ids=str)
@pytest.mark.parametrize("T, lengths", [
    (512, None), (500, None), (512, (31, 300)), (300, (129, 33)),
    (512, (128, 512))])
def test_every_latent_tile_shape_against_the_lax_body(
        interpreted, monkeypatch, tiles, T, lengths):
    use_mla_tiles(monkeypatch, tiles)
    held_to_the_lax_body(mla_inputs(T, B=len(lengths or [0]), seed=T),
                         lengths)


@pytest.mark.parametrize("tiles", MLA_TILES[:3], ids=str)
@pytest.mark.parametrize("lengths", LENGTHS)
def test_latent_rows_below_a_length_do_not_depend_on_it(
        interpreted, monkeypatch, tiles, lengths):
    use_mla_tiles(monkeypatch, tiles)
    xs = mla_inputs(T, B=2, seed=2)
    whole = np.asarray(pk.mla_flash(*xs, *MLA_DIMS))
    got = np.asarray(pk.mla_flash(
        *(padded(x, lengths, tiles[0]) for x in xs), *MLA_DIMS,
        lengths=jnp.asarray(lengths, jnp.int32)))
    for b, n in enumerate(lengths):
        assert np.array_equal(got[b, :n], whole[b, :n])
        assert np.all(got[b, n:] == 0)


@pytest.mark.parametrize("tiles", MLA_TILES, ids=str)
@pytest.mark.parametrize("length", [129, 300, 512])
def test_latent_prompt_tile_work_is_the_kernels_own_blocks(
        interpreted, monkeypatch, tiles, length):
    use_mla_tiles(monkeypatch, tiles)
    xs = mla_inputs(T)
    walked, skipped = pk.prompt_tile_visits(length, T, latent=True)
    masked, computed, needed = pk.prompt_tile_work(length, T, latent=True)
    # a head's scores are ONE product over its nope lanes and the lane
    # tile its rotary ones share with the step's other heads
    width = 24 + 8 * tiles[4]
    got = kernel_counts(monkeypatch, lambda: pk.mla_flash(
        *xs, *MLA_DIMS, lengths=jnp.asarray([length])), width=width)
    groups = MLA_DIMS[0] // tiles[4]        # a grid step a group of heads
    assert got == (groups * walked, groups * masked,
                   MLA_DIMS[0] * computed)
    assert needed == length * (length + 1) // 2 <= computed
    # without the length the whole bucket's tiles are walked
    assert kernel_counts(monkeypatch, lambda: pk.mla_flash(
        *xs, *MLA_DIMS), width=width)[0] == groups * (walked + skipped)


@pytest.mark.parametrize("length, rows", [
    (5000, 8192), (8192, 8192), (3072, 4096), (4096, 4096), (1500, 2048),
    (1229, 2048), (700, 1024), (1024, 1024)])
def test_latent_prompt_tile_work_counts_the_blocks_a_row_can_see(length,
                                                                 rows):
    bq, bk, sub, inner = pk._mla_tiles(rows, 128, 128, 64, 128)[:4]
    walked, _ = pk.prompt_tile_visits(length, rows, latent=True)
    masked, computed, _ = pk.prompt_tile_work(length, rows, latent=True)
    assert (walked, masked, computed) == seen_blocks(
        length, rows, 0, bq, bk, sub, guard=inner)


def test_latent_prompt_tile_work_at_the_cells_shapes():
    # longctx's t8192: the grouped-query kernel's tiles.  A prompt of
    # 5,000 rows has five live query tiles of 1, 1, 2, 2, 3 key tiles
    # (before PR 47, square tiles of 512 masked whole: 55 of 136 tiles
    # walked, 10 of them masked, 55 x 512 x 512 scores)
    edge, span = 10 * 256 * 256, 1024 * 1024
    assert pk.prompt_tile_visits(5000, 8192, latent=True) == (9, 11)
    assert pk.prompt_tile_work(5000, 8192, latent=True) == (
        5, (0 + 1 + 2 + 3 + 4) * span + 5 * edge, 5000 * 5001 // 2)
    # ISSUE 47's example, the last live tile's padding rows at 1,500 in
    # 2,048: the tile's second block of 512 rows is left out (2.10
    # computed over needed with it, 1.40 with query tiles of 512)
    m, c, n = pk.prompt_tile_work(1500, 2048, latent=True)
    assert (m, c, round(c / n, 2)) == (2, 1376256, 1.22)
    m, c, n = pk.prompt_tile_work(2458, 4096, latent=True)
    assert (m, c, round(c / n, 2)) == (3, 3604480, 1.19)
    m, c, n = pk.prompt_tile_work(8192, 8192, latent=True)
    assert (m, round(c / n, 4)) == (8, 1.0311)


def test_the_kernel_says_what_it_chose(interpreted, monkeypatch):
    from mxnet_tpu import profiler

    use_mla_tiles(monkeypatch, MLA_TILES[1])
    profiler.reset_metrics()
    pk.mla_flash(*mla_inputs(256), *MLA_DIMS)
    g = profiler.metrics_summary()["gauges"]
    assert (g["mla_flash.tile_q"], g["mla_flash.tile_k"],
            g["mla_flash.subtile"], g["mla_flash.heads_per_step"]) == (
        64, 128, 32, 2)
