"""A prompt's attention kernels stop at the prompt's last row:
``flash_mha_window`` (windowed and global) and ``mla_flash`` take the
prompts' lengths as a scalar operand.  Interpreted on the CPU: rows below
a length are the BITS of the call that knows no length (the same tiles in
the same order), rows at and past it are zeros whatever the padding
holds, a query tile of padding alone fetches nothing, the host's count of
the tiles walked and left out (``prompt_tile_visits``) is the kernels'
own steps.  (The engine's counters: the three families' own test files.)"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402

BLOCK, T = 128, 512
# a row of the batch each: one row; one under, on and one over a tile's
# edge; the middle of a tile; the whole bucket
LENGTHS = [(1, 127), (128, 129), (300, 512)]


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels interpreted, in tiles of 128 rows."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    monkeypatch.setattr(pk, "_mha_window_tiles",
                        lambda t, window: (BLOCK, BLOCK, BLOCK, BLOCK))
    monkeypatch.setattr(pk, "_mla_tiles",
                        lambda t, *widths: (BLOCK, BLOCK, BLOCK, BLOCK, 4))


def padded(x, lengths):
    """``x`` with what a bucket's padding may hold: large finite values
    in the rows past each prompt inside its last live tile, NaN in the
    tiles past it (what is never fetched may hold anything)."""
    x = np.array(x)
    per = x.shape[0] // len(lengths)
    for b, n in enumerate(lengths):
        rows = x[b * per:(b + 1) * per]
        dead = -(-n // BLOCK) * BLOCK
        rows[:, n:dead] = 1e4 * np.sign(rows[:, n:dead])
        rows[:, dead:] = np.nan
    return x


def gqa_inputs(B=2, H=6, Hkv=2, D=16, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(B * n, T, D)).astype(np.float32)
            for n in (H, Hkv, Hkv)], (H, Hkv)


@pytest.mark.parametrize("lengths", LENGTHS)
@pytest.mark.parametrize("window", [0, 40, 200])
def test_flash_mha_window_stops_at_each_prompts_last_row(interpreted,
                                                         window, lengths):
    (q, k, v), (H, Hkv) = gqa_inputs()
    whole = np.asarray(pk.flash_mha_window(q, k, v, window, H, Hkv))
    got = np.asarray(pk.flash_mha_window(
        *(padded(x, lengths) for x in (q, k, v)), window, H, Hkv,
        lengths=jnp.asarray(lengths, jnp.int32)))
    for b, n in enumerate(lengths):
        rows = slice(b * H, (b + 1) * H)
        assert np.array_equal(got[rows, :n], whole[rows, :n])
        assert np.all(got[rows, n:] == 0)


@pytest.mark.parametrize("window", [0, 200])
def test_flash_mha_window_without_lengths_walks_the_whole_bucket(
        interpreted, window):
    (q, k, v), (H, Hkv) = gqa_inputs(seed=3)
    whole = np.asarray(pk.flash_mha_window(q, k, v, window, H, Hkv))
    for full in ((T, T), (T + 9, 10 * T)):      # clipped to the bucket
        assert np.array_equal(whole, np.asarray(pk.flash_mha_window(
            q, k, v, window, H, Hkv, lengths=jnp.asarray(full))))
    # an empty prompt: no tile is live, every row is zero
    none = np.asarray(pk.flash_mha_window(
        q, k, v, window, H, Hkv, lengths=jnp.asarray((0, T))))
    assert np.all(none[:H] == 0) and np.array_equal(none[H:], whole[H:])


def mla_inputs(B=2, H=4, n=16, r=8, dv=16, seed=2):
    rng = np.random.default_rng(seed)

    def arr(lanes):
        return rng.normal(size=(B, T, lanes)).astype(np.float32)

    return [arr(H * (n + r)), arr(H * r), arr(H * (n + dv)), arr(r)], \
        (H, n, dv, 0.2)


@pytest.mark.parametrize("lengths", LENGTHS)
def test_mla_flash_stops_at_each_prompts_last_row(interpreted, lengths):
    xs, dims = mla_inputs()
    whole = np.asarray(pk.mla_flash(*xs, *dims))
    got = np.asarray(pk.mla_flash(
        *(padded(x, lengths) for x in xs), *dims,
        lengths=jnp.asarray(lengths, jnp.int32)))
    for b, n in enumerate(lengths):
        assert np.array_equal(got[b, :n], whole[b, :n])
        assert np.all(got[b, n:] == 0)


def test_mla_flash_without_lengths_walks_the_whole_bucket(interpreted):
    xs, dims = mla_inputs(seed=3)
    whole = np.asarray(pk.mla_flash(*xs, *dims))
    assert np.array_equal(whole, np.asarray(pk.mla_flash(
        *xs, *dims, lengths=jnp.asarray((T, T + 1)))))
    none = np.asarray(pk.mla_flash(*xs, *dims,
                                   lengths=jnp.asarray((T, 0))))
    assert np.all(none[1] == 0) and np.array_equal(none[0], whole[0])


# -- the host's count against the kernels' own steps -------------------------

def computed_steps(monkeypatch, call):
    """How many grid steps of the interpreted kernel ``call`` runs took a
    computing branch: every ``pl.when`` body but the ones that only set
    a tile up, close it or zero it, counted as it runs."""
    hits = []
    real = pk.pl.when

    def when(cond):
        def bind(body):
            if body.__name__ in ("_init", "_dead", "_finalize",
                                 "_finalize_last", "_rows"):    # the
                # others: a tile's unmasked body, an edge tile's walk
                # (`_rows`: a block of rows inside one of them)
                return real(cond)(body)

            def counted():
                jax.debug.callback(lambda: hits.append(1))
                body()
            return real(cond)(counted)
        return bind

    monkeypatch.setattr(pk.pl, "when", when)
    for jitted in (pk._flash_mha_window, pk._mla_flash):
        jitted.clear_cache()                # a trace of its own, with
    jax.block_until_ready(call())           # this ``when``, dropped after
    jax.effects_barrier()
    for jitted in (pk._flash_mha_window, pk._mla_flash):
        jitted.clear_cache()
    return len(hits)


@pytest.mark.parametrize("window", [0, 40, 200, 128])
@pytest.mark.parametrize("length", [0, 1, 128, 129, 300, 512])
def test_prompt_tile_visits_are_the_window_kernels_steps(
        interpreted, monkeypatch, window, length):
    (q, k, v), _ = gqa_inputs(B=1, H=2, Hkv=1)
    walked, skipped = pk.prompt_tile_visits(length, T, window)
    steps = computed_steps(monkeypatch, lambda: pk.flash_mha_window(
        q, k, v, window, 2, 1, lengths=jnp.asarray([length])))
    assert steps == 2 * walked          # two query heads
    whole = computed_steps(monkeypatch, lambda: pk.flash_mha_window(
        q, k, v, window, 2, 1))
    assert whole == 2 * (walked + skipped)
    assert pk.prompt_tile_visits(T, T, window) == (walked + skipped, 0)


@pytest.mark.parametrize("length", [0, 1, 128, 129, 300, 512])
def test_prompt_tile_visits_are_the_latent_kernels_steps(
        interpreted, monkeypatch, length):
    xs, dims = mla_inputs(B=1)          # four heads: one group a step
    walked, skipped = pk.prompt_tile_visits(length, T, latent=True)
    steps = computed_steps(monkeypatch, lambda: pk.mla_flash(
        *xs, *dims, lengths=jnp.asarray([length])))
    assert steps == walked
    assert computed_steps(monkeypatch, lambda: pk.mla_flash(*xs, *dims)) \
        == walked + skipped == 10       # 1 + 2 + 3 + 4 tiles


def test_prompt_tile_visits_at_the_cells_shapes():
    # longdoc's t32768 (query tiles of 1,024 rows over key tiles of
    # 2,048): a global layer and a windowed one at a prompt a little
    # over half the bucket; longctx's t8192 under the same tiles: five
    # live query tiles of 1, 1, 2, 2, 3 key tiles, the three dead ones
    # would have walked 3, 4, 4
    assert pk.prompt_tile_visits(17000, 32768) == (81, 191)
    assert pk.prompt_tile_visits(17000, 32768, 4096) == (45, 45)
    assert pk.prompt_tile_visits(5000, 8192, latent=True) == (9, 11)
    assert pk.prompt_tile_visits(32768, 32768) == (272, 0)
