"""Pallas TPU kernels of the hybrid language-model family
(``models/hybrid_lm.py``): the KDA recurrence, one token against a
stream's state (``kda_step``) and a whole prompt with the state resident
in VMEM (``kda_chunk``), and the grouped matmuls of the routed experts
(``moe_gmm_gate_up``, ``moe_gmm_down``).  Helpers and conventions are
``pallas_kernels``'s: every ``pallas_call`` carries a ``name=``, which is
what a device trace shows.

The KDA state of one head is held TRANSPOSED, ``St = S^T`` (d_v, d_k):
the decay then scales lanes by a row vector, and both contractions with
``k`` and ``q`` are lane reductions that give columns —

    St <- St * alpha                      (alpha, k, q: (1, d_k) rows)
    u   = sum_lanes(St * k)               ((d_v, 1) column = S^T k)
    St <- St + (beta (v - u)) * k         (column x row: the rank-1 term)
    o   = sum_lanes(St * q)               ((d_v, 1) column = S^T q)

which is ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
``o_t = S_t^T q_t`` exactly, in float32 on the VPU; no matmul, no
transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import (_VMEM_LIMIT, _compiler_params, _interpret,
                             _vmem_spec)


def _kda_token(st, q, k, a, v_col, b):
    """One token of one head on the transposed state (module doc)."""
    st = st * a
    u = jnp.sum(st * k, axis=1, keepdims=True)
    st = st + (b * (v_col - u)) * k
    return st, jnp.sum(st * q, axis=1, keepdims=True)


def _eye(n):
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r == c).astype(jnp.float32)


# ---------------------------------------------------------------------------
# kda_step: one token per stream against the slot's state, in place
# ---------------------------------------------------------------------------

def _kda_step_kernel(slots_ref, q_ref, k_ref, a_ref, v_ref, b_ref, s_ref,
                     o_ref, so_ref, *, hb):
    del slots_ref  # used by the index maps
    eye = _eye(s_ref.shape[-1])
    for i in range(hb):
        # v arrives as a row; the update wants it as a column, and
        # gives o as a column where the output wants a row: both
        # through the identity mask and a reduction, on the VPU
        v_col = jnp.sum(eye * v_ref[0, i:i + 1, :], axis=1, keepdims=True)
        st, o_col = _kda_token(
            s_ref[0, i], q_ref[0, i:i + 1, :], k_ref[0, i:i + 1, :],
            a_ref[0, i:i + 1, :], v_col, b_ref[0, i:i + 1, :1])
        so_ref[0, i] = st
        o_ref[0, i:i + 1, :] = jnp.sum(eye * o_col, axis=0, keepdims=True)


def kda_step(q, k, alpha, v, beta, state, slots):
    """q, k, alpha, v, beta (B, H, D) float32 (q, k normalised and
    scaled; beta repeated over D); state (S, H, D, D) float32, head
    states transposed; slots (B,) int32 -> (o (B, H, D) float32, the
    state with the B slots advanced).  The state operand is aliased to
    its output: donated under jit, the update is in place."""
    B, H, D = q.shape
    hb = 8 if H % 8 == 0 else H
    row = _vmem_spec((1, hb, D), lambda b, h, sl: (b, h, 0))
    st = _vmem_spec((1, hb, D, D), lambda b, h, sl: (sl[b], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb),
        in_specs=[row, row, row, row, row, st], out_specs=[row, st])
    o, new_state = pl.pallas_call(
        functools.partial(_kda_step_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="kda_step",
    )(slots.astype(jnp.int32), q, k, alpha, v, beta, state)
    return o, new_state


# ---------------------------------------------------------------------------
# slot_rows_write: a batch's rows into their slots, in place
# ---------------------------------------------------------------------------

def _slot_rows_kernel(slots_ref, rows_ref, pool_ref, out_ref):
    del slots_ref, pool_ref  # the index maps' and the alias's
    out_ref[...] = rows_ref[...]


def slot_rows_write(pool, rows, slots):
    """pool (S, 8, W), rows (B, 8, W), slots (B,) int32 -> the pool with
    row b written to slot ``slots[b]`` (padded rows share the scratch
    slot; the last writer wins).  One whole-tile DMA a row into the
    aliased pool: as an XLA scatter this is a loop of B row updates
    (2.3 ms for 128 rows of 295 KB on the chip, PERF.md section 6)."""
    B = rows.shape[0]
    blk = (1,) + tuple(rows.shape[1:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B,),
        in_specs=[_vmem_spec(blk, lambda b, sl: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],  # aliased, unread
        out_specs=_vmem_spec(blk, lambda b, sl: (sl[b], 0, 0)))
    return pl.pallas_call(
        _slot_rows_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=_compiler_params("arbitrary"),
        interpret=_interpret(),
        name="slot_rows_write",
    )(slots.astype(jnp.int32), rows.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# kda_chunk: a prompt, chunk by chunk, the state resident in VMEM
# ---------------------------------------------------------------------------

def _kda_chunk_kernel(q_ref, k_ref, a_ref, vt_ref, b_ref, o_ref, sT_ref,
                      s_scr, *, tc, nt):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    st = s_scr[...]
    for t in range(tc):
        st, o_col = _kda_token(
            st, q_ref[0, t:t + 1, :], k_ref[0, t:t + 1, :],
            a_ref[0, t:t + 1, :], vt_ref[0, :, t:t + 1],
            b_ref[0, :, t:t + 1])
        o_ref[0, :, t:t + 1] = o_col
    s_scr[...] = st

    @pl.when(j == nt - 1)
    def _last():
        sT_ref[0] = st


def kda_chunk(q, k, alpha, vt, beta):
    """A whole prompt from the zero state.  q, k, alpha (N, T, D)
    float32 with N = batch x heads (q, k normalised and scaled; a
    padded position carries alpha 1 and beta 0 and leaves the state as
    it is); vt (N, D, T): v transposed, so that a token's v is a
    column; beta (N, 1, T) -> (o (N, D, T), the last state (N, D, D),
    transposed as the module doc says).  The recurrence is exact, token
    by token; a chunk of ``tc`` tokens is one grid step."""
    N, T, D = q.shape
    tc = 128 if T % 128 == 0 else T
    nt = T // tc
    row = _vmem_spec((1, tc, D), lambda n, j: (n, j, 0))
    col = _vmem_spec((1, D, tc), lambda n, j: (n, 0, j))
    return pl.pallas_call(
        functools.partial(_kda_chunk_kernel, tc=tc, nt=nt),
        grid=(N, nt),
        in_specs=[row, row, row, col,
                  _vmem_spec((1, 1, tc), lambda n, j: (n, 0, j))],
        out_specs=[col, _vmem_spec((1, D, D), lambda n, j: (n, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, D, T), jnp.float32),
                   jax.ShapeDtypeStruct((N, D, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=_interpret(),
        name="kda_chunk",
    )(q, k, alpha, vt, beta)


# ---------------------------------------------------------------------------
# moe_gmm: grouped matmuls over rows sorted by expert
#
# Rows arrive sorted by expert, each expert's run padded to whole tiles
# of ``tm`` rows (``ops/hybrid.py`` lays them out), so a tile belongs to
# one expert: ``tile_expert[t]``, scalar-prefetched, picks the weight
# block.  The grid covers the worst case (every pair routed here); only
# ``n_used[0]`` tiles hold rows.  A tile past them does nothing, and its
# index maps name the LAST block a used tile touched, so no block is
# fetched or written for it.  K is cut in blocks of whole rows of the
# (E, K, N) weights: each block is one contiguous piece of HBM.
# ---------------------------------------------------------------------------

def _gmm_maps(nk):
    def tile(t, nu):
        return jnp.minimum(t, jnp.maximum(nu[0] - 1, 0))

    def kblock(t, kk, nu):
        return jnp.where(t < nu[0], kk, nk - 1)

    x_map = lambda t, kk, te, nu: (tile(t, nu), kblock(t, kk, nu))
    w_map = lambda t, kk, te, nu: (te[tile(t, nu)], kblock(t, kk, nu), 0)
    o_map = lambda t, kk, te, nu: (tile(t, nu), 0)
    return x_map, w_map, o_map


def _gmm_gate_up_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, o_ref,
                        accg, accu, *, nk):
    del te_ref
    t, kk = pl.program_id(0), pl.program_id(1)
    used = t < nu_ref[0]

    @pl.when(used & (kk == 0))
    def _init():
        accg[...] = jnp.zeros_like(accg)
        accu[...] = jnp.zeros_like(accu)

    @pl.when(used)
    def _mul():
        x = x_ref[...]
        accg[...] += jnp.dot(x, wg_ref[0],
                             preferred_element_type=jnp.float32)
        accu[...] += jnp.dot(x, wu_ref[0],
                             preferred_element_type=jnp.float32)

    @pl.when(used & (kk == nk - 1))
    def _out():
        g = accg[...]
        o_ref[...] = (g * jax.nn.sigmoid(g) * accu[...]).astype(o_ref.dtype)


def _gmm_down_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref, acc, *, nk):
    del te_ref
    t, kk = pl.program_id(0), pl.program_id(1)
    used = t < nu_ref[0]

    @pl.when(used & (kk == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _mul():
        acc[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(used & (kk == nk - 1))
    def _out():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _k_block(K, want):
    return want if K % want == 0 else K


def moe_gmm_gate_up(x, w_gate, w_up, tile_expert, n_used, tm):
    """x (M, K) rows sorted by expert in tiles of ``tm``; w_gate, w_up
    (E, K, N) -> SiLU(x W_gate[e]) * (x W_up[e]), (M, N) in x.dtype,
    for the rows of the first ``n_used[0]`` tiles (the rest is not
    written)."""
    M, K = x.shape
    N = w_gate.shape[2]
    tk = _k_block(K, 512)
    nk = K // tk
    x_map, w_map, o_map = _gmm_maps(nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(M // tm, nk),
        in_specs=[_vmem_spec((tm, tk), x_map),
                  _vmem_spec((1, tk, N), w_map),
                  _vmem_spec((1, tk, N), w_map)],
        out_specs=_vmem_spec((tm, N), o_map),
        scratch_shapes=[pltpu.VMEM((tm, N), jnp.float32),
                        pltpu.VMEM((tm, N), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_gate_up_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="moe_gmm_gate_up",
    )(tile_expert, n_used, x, w_gate, w_up)


def moe_gmm_down(x, w_down, tile_expert, n_used, tm):
    """x (M, K) as :func:`moe_gmm_gate_up` gives it; w_down (E, K, N)
    -> x W_down[e], (M, N) float32, for the rows of the used tiles."""
    M, K = x.shape
    N = w_down.shape[2]
    tk = _k_block(K, 256)
    nk = K // tk
    x_map, w_map, o_map = _gmm_maps(nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(M // tm, nk),
        in_specs=[_vmem_spec((tm, tk), x_map),
                  _vmem_spec((1, tk, N), w_map)],
        out_specs=_vmem_spec((tm, N), o_map),
        scratch_shapes=[pltpu.VMEM((tm, N), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_down_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="moe_gmm_down",
    )(tile_expert, n_used, x, w_down)
