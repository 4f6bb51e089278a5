"""Hand-written Pallas TPU kernels for the hot fused ops.

This is the framework's user-kernel layer — the TPU equivalent of the
reference's runtime CUDA compilation (``src/common/mxrtc.cc:13-76``,
``python/mxnet/rtc.py``) applied to the two ops SURVEY §7 calls out:

* ``lstm_scan``: the LSTM recurrence as ONE kernel over a sequential
  ``grid=(T,)`` with the hidden/cell state resident in VMEM scratch —
  state never round-trips to HBM between timesteps, the per-step
  ``h @ U`` runs on the MXU, and the gate math fuses on the VPU.
  Differentiable via custom_vjp: backward rematerializes through the
  jax.lax.scan formulation (activations are never stored — remat).
* ``nms``: greedy class-aware non-max suppression over score-sorted
  rows as one kernel — the sequential suppression loop runs on-chip
  over VMEM-resident boxes (MultiBoxDetection is stop_gradient, so no
  VJP is needed).

Kernels run natively on TPU; everywhere else they run in interpreter
mode, which keeps CPU tests meaningful (same kernel code path).
Opt-out / force: ``MXNET_PALLAS=0|1`` (default: on for TPU backends).

Every ``pallas_call`` carries a ``name=``: one word per kernel family
plus the variant (``flash_fwd_packed``, ``paged_attention``,
``lstm_scan``, ``nms``), which is what the TPU compiler names the
kernel's HLO instruction by and so what a device trace shows.  The two
backward kernels of flash attention are ``flash_transpose_dq_*`` /
``flash_transpose_dkv_*`` and not ``flash_bwd_*``: the accepted
benchmark's ``flash_roofline`` reader tells backward from forward by
``transpose`` in the instruction's name (which, unnamed, came from
jax's ``transpose(jvp())`` name stack), and only a ``benchmark`` issue
may change that reader.
"""

from __future__ import annotations

import collections
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError


def enabled() -> bool:
    """Use the Pallas kernels?  Default: only on a real TPU backend."""
    flag = os.environ.get("MXNET_PALLAS")
    if flag is not None:
        return flag != "0"
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _vmem_spec(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


# What a kernel may ask of the chip's VMEM.  Mosaic's default scoped
# limit is 16 MiB; the flash families' (block, H*D) tiles, double
# buffered, need more at block 1024.  A v5e core has 128 MiB and its
# compiler accepts this request (tests/test_tpu_compile.py).
_VMEM_LIMIT = 100 * 1024 * 1024
# what a kernel that sets no limit of its own is given (v5e's default
# scoped VMEM), less room for the compiler's own temporaries
_PAGED_VMEM_BUDGET = 12 * 1024 * 1024


def _compiler_params(*dimension_semantics, vmem_limit_bytes=None):
    """Mosaic parameters for a compiled kernel; None in interpret mode
    (the interpreter takes none)."""
    if _interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


# ---------------------------------------------------------------------------
# LSTM scan
# ---------------------------------------------------------------------------

def _lstm_kernel(xw_ref, h0_ref, c0_ref, ut_ref, y_ref, ht_ref, ct_ref,
                 h_scr, c_scr):
    """One timestep per grid iteration; h/c live in VMEM scratch.

    TPU grids execute sequentially, which is exactly the dependency
    order of the recurrence."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    hidden = h_scr.shape[-1]
    pre = xw_ref[0] + jnp.dot(h_scr[:], ut_ref[:],
                              preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(pre[:, 0 * hidden:1 * hidden])
    f = jax.nn.sigmoid(pre[:, 1 * hidden:2 * hidden])
    g = jnp.tanh(pre[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(pre[:, 3 * hidden:4 * hidden])
    c = f * c_scr[:] + i * g
    h = o * jnp.tanh(c)
    h_scr[:] = h
    c_scr[:] = c
    y_ref[0] = h
    ht_ref[:] = h  # last grid step's write is the final state
    ct_ref[:] = c


def _lstm_pallas_fwd(xw, h0, c0, ut):
    """xw: (T, B, 4H) input projection (+biases); ut: (H, 4H)."""
    T, B, G = xw.shape
    H = G // 4
    dt = xw.dtype
    y, hT, cT = pl.pallas_call(
        _lstm_kernel,
        grid=(T,),
        in_specs=[
            _vmem_spec((1, B, G), lambda t: (t, 0, 0)),
            _vmem_spec((B, H), lambda t: (0, 0)),
            _vmem_spec((B, H), lambda t: (0, 0)),
            _vmem_spec((H, G), lambda t: (0, 0)),
        ],
        out_specs=[
            _vmem_spec((1, B, H), lambda t: (t, 0, 0)),
            _vmem_spec((B, H), lambda t: (0, 0)),
            _vmem_spec((B, H), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((B, H), jnp.float32)],
        interpret=_interpret(),
        name="lstm_scan",
    )(xw, h0, c0, ut)
    return y, hT, cT


def _lstm_reference(xw, h0, c0, ut):
    """The differentiable formulation the VJP remats through — the SAME
    cell step ops/rnn.py scans with, so kernel forward and remat
    backward cannot drift apart."""
    from .rnn import _cell_step

    cell = _cell_step("lstm", h0.shape[-1])

    def step(carry, x_t):
        return cell(carry, x_t + carry[0] @ ut)

    (hT, cT), y = jax.lax.scan(step, (h0, c0), xw)
    return y, hT, cT


@jax.custom_vjp
def lstm_scan(xw, h0, c0, ut):
    """Pallas LSTM recurrence: (T,B,4H), (B,H), (B,H), (H,4H) →
    (y (T,B,H), hT, cT)."""
    return _lstm_pallas_fwd(xw, h0, c0, ut)


def _lstm_fwd_rule(xw, h0, c0, ut):
    outs = _lstm_pallas_fwd(xw, h0, c0, ut)
    return outs, (xw, h0, c0, ut)


def _lstm_bwd_rule(res, cots):
    # rematerialize: forward activations were never stored (VMEM-only),
    # so backward re-runs the scan formulation under jax.vjp
    _, vjp = jax.vjp(_lstm_reference, *res)
    return vjp(cots)


lstm_scan.defvjp(_lstm_fwd_rule, _lstm_bwd_rule)


# ---------------------------------------------------------------------------
# Greedy NMS
# ---------------------------------------------------------------------------

def _nms_kernel(rows_ref, cls_ref, *, nms_threshold, force_suppress,
                n_rows):
    """rows (1, 8, Ap): FIELD-major [cls, score, l, t, r, b, pad, pad]
    with the score-sorted anchors along the lanes — an (A, 6) block
    would pad 6 fields to 128 lanes and overflow VMEM at SSD's 8,732
    anchors; this layout holds them in ~280 KB.  Writes the (1, 1, Ap)
    class row with suppressed anchors set to -1.  The i-loop is
    sequential (each round depends on previous suppressions); each
    round's IoU test is one VPU vector op over all anchors."""
    cls_ref[0] = rows_ref[0, 0:1, :]
    Ap = rows_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Ap), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def round_i(i, _):
        # anchor i's fields as an (8, 1) column: load its aligned
        # 128-lane tile and mask-reduce (no dynamic lane indexing)
        base = pl.multiple_of((i // 128) * 128, 128)
        pick = sub == i - base
        col = jnp.sum(jnp.where(pick, rows_ref[0, :, pl.ds(base, 128)], 0.0),
                      axis=1, keepdims=True)
        cls_i = jnp.sum(jnp.where(pick[0:1], cls_ref[0, :, pl.ds(base, 128)],
                                  0.0), axis=1, keepdims=True)
        l_i, t_i, r_i, b_i = col[2:3], col[3:4], col[4:5], col[5:6]
        cls = cls_ref[0]
        l_a, t_a = rows_ref[0, 2:3, :], rows_ref[0, 3:4, :]
        r_a, b_a = rows_ref[0, 4:5, :], rows_ref[0, 5:6, :]
        inter = jnp.maximum(jnp.minimum(r_a, r_i) - jnp.maximum(l_a, l_i),
                            0.0) \
            * jnp.maximum(jnp.minimum(b_a, b_i) - jnp.maximum(t_a, t_i), 0.0)
        area = (r_a - l_a) * (b_a - t_a)
        area_i = (r_i - l_i) * (b_i - t_i)
        union = area + area_i - inter
        iou = jnp.where(union > 0, inter / jnp.maximum(union, 1e-12), 0.0)
        same = jnp.logical_or(bool(force_suppress), cls == cls_i)
        suppress = (cls_i >= 0) & (lane > i) & same & (cls >= 0) \
            & (iou >= nms_threshold)
        cls_ref[0] = jnp.where(suppress, -1.0, cls)
        return 0

    jax.lax.fori_loop(0, n_rows, round_i, 0)


def nms(rows, nms_threshold, force_suppress):
    """rows (B, A, 6) sorted by score desc → suppressed rows cls=-1."""
    B, A, _ = rows.shape
    # field-major, anchors in lanes; padding is cls = -1 (never alive)
    fields = jnp.pad(jnp.swapaxes(rows, 1, 2),
                     ((0, 0), (0, 2), (0, (-A) % 128)),
                     constant_values=-1.0)
    Ap = fields.shape[2]
    kern = functools.partial(_nms_kernel, nms_threshold=float(nms_threshold),
                             force_suppress=bool(force_suppress), n_rows=A)
    cls = pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[_vmem_spec((1, 8, Ap), lambda b: (b, 0, 0))],
        out_specs=_vmem_spec((1, 1, Ap), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Ap), rows.dtype),
        interpret=_interpret(),
        name="nms",
    )(fields)
    return rows.at[:, :, 0].set(cls[:, 0, :A])


# ---------------------------------------------------------------------------
# Flash attention (blockwise online-softmax partial state)
# ---------------------------------------------------------------------------
#
# The kernel behind ``ops.attention.blockwise_attention_partial`` on
# TPU: q/k/v tiles live in VMEM, scores for one (q-block, k-block)
# tile run on the MXU, and the online-softmax state (o, m, l) is
# accumulated IN the revisited output block across the sequential
# k-block grid dimension — the (Tq, Tk) score matrix never exists in
# HBM.  Returns the UN-normalized partial state so ring attention
# (mxnet_tpu.sequence) can merge per-hop states exactly as with the
# lax.scan formulation.  ``kv_offset`` is a dynamic scalar (the ring
# rotates shards, so each hop's key offset is traced) — delivered via
# scalar prefetch.


def _flash_kernel(koff_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  causal, block_q, block_k, tk_valid, scale):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal tile skip: a k-block whose first key position is beyond
    # this q-block's last query contributes nothing — skip its matmuls
    # entirely (half the tiles for koff=0 causal attention)
    if causal:
        run = (kj * block_k + koff_ref[0]) <= (qi * block_q + block_q - 1)
    else:
        run = kj >= 0  # always

    @pl.when(run)
    def _compute():
        q = q_ref[0]  # (bq, D)
        k = k_ref[0]  # (bk, D)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_local = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_local < tk_valid  # Tk padding
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid &= (k_local + koff_ref[0]) <= q_pos
        s = jnp.where(valid, s, -jnp.inf)

        # m/l blocks are (bq, 128): the scalar-per-row state broadcast
        # over the lane dim (the canonical TPU layout for row
        # statistics — a (1, bq) block would put bq in the lane slot
        # and the leading 1 in the sublane slot, which Mosaic rejects)
        m_prev = m_ref[0, :, 0]  # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe[:, None]), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))
        l_new = l_ref[0, :, 0] * alpha + jnp.sum(p, axis=1)
        l_ref[0] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        o_ref[0] = o_ref[0] * alpha[:, None] + pv
        m_ref[0] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])


def _sds(shape, vma):
    if vma:
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention_partial(q, k, v, causal, block_size, kv_offset):
    """(B, Tq, H, D) q + (B, Tk, H, D) k/v -> partial state
    (o (B,H,Tq,D) f32, m (B,H,Tq) f32, l (B,H,Tq) f32), matching
    ops.attention.blockwise_attention_partial exactly."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(D) ** 0.5
    # q-block rows land in the LAST dim of the (1, bq) m/l blocks, so
    # bq must be a multiple of 128 lanes; k-blocks likewise
    bq = max(128, min(512, (int(block_size) // 128) * 128 or 128))
    bk = max(128, min(512, (int(block_size) // 128) * 128 or 128))

    # (B, T, H, D) -> (B*H, T, D); pad T to block multiples, D to lanes
    def _flat(x, t):
        # jnp functions, not methods: under shard_map+vjp the operands
        # can be vma-typed wrappers without ndarray methods
        return jnp.reshape(jnp.transpose(x, (0, 2, 1, 3)), (B * H, t, D))

    qf = _pad_to(_pad_to(_flat(q, Tq), 1, bq), 2, 128)
    kf = _pad_to(_pad_to(_flat(k, Tk), 1, bk), 2, 128)
    vf = _pad_to(_pad_to(_flat(v, Tk), 1, bk), 2, 128)
    Dp = qf.shape[2]
    Tqp, Tkp = qf.shape[1], kf.shape[1]
    # under shard_map (ring attention) the outputs vary over the same
    # mesh axes as the inputs; pallas_call needs that declared
    try:
        vma = (jax.typeof(qf).vma | jax.typeof(kf).vma
               | jax.typeof(vf).vma)
    except Exception:
        vma = frozenset()
    grid = (B * H, Tqp // bq, Tkp // bk)
    kern = functools.partial(_flash_kernel, causal=causal, block_q=bq,
                             block_k=bk, tk_valid=Tk, scale=scale)
    koff = jnp.asarray(kv_offset, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            _vmem_spec((1, bq, Dp), lambda bh, qi, kj, koff: (bh, qi, 0)),
            _vmem_spec((1, bk, Dp), lambda bh, qi, kj, koff: (bh, kj, 0)),
            _vmem_spec((1, bk, Dp), lambda bh, qi, kj, koff: (bh, kj, 0)),
        ],
        out_specs=[
            _vmem_spec((1, bq, Dp), lambda bh, qi, kj, koff: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, qi, kj, koff: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, qi, kj, koff: (bh, qi, 0)),
        ],
    )
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[_sds((B * H, Tqp, Dp), vma),
                   _sds((B * H, Tqp, 128), vma),
                   _sds((B * H, Tqp, 128), vma)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=_interpret(),
        name="flash_fwd_partial",
    )(koff, qf, kf, vf)
    o = jnp.reshape(o[:, :Tq, :D], (B, H, Tq, D))
    m = jnp.reshape(m[:, :Tq, 0], (B, H, Tq))
    l = jnp.reshape(l[:, :Tq, 0], (B, H, Tq))
    return o, m, l


# -- flash attention backward ----------------------------------------------
#
# Gradients of the UN-normalized partial state (o, m, l) wrt q, k, v.
# Every consumer of the partial state (normalize_attention_state, ring
# attention_state_merge) is invariant under the rescaling
# (o, m, l) -> (o e^{-c}, m + c, l e^{-c}), which makes the cotangent
# identity  m_bar = o_bar·o + l_bar·l  hold, and the argmax-subgradient
# terms of m cancel EXACTLY.  The backward therefore treats m as a
# constant:  ds_ij = p_ij * (o_bar_i · v_j + l_bar_i),  with
# p_ij = exp(q_i·k_j·scale - m_i) under the same masks as forward —
# verified against the lax.scan vjp in tests/test_pallas.py.
#
# Two kernels because the two accumulations need different sequential
# grid axes: dq accumulates over k-blocks (kj innermost, like the
# forward), dk/dv accumulate over q-blocks (qi innermost).


def _flash_bwd_p(q, k, m, koff, qi, kj, *, causal, block_q, block_k,
                 tk_valid, scale):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_local = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_local < tk_valid
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid &= (k_local + koff) <= q_pos
    m_safe = jnp.where(m == -jnp.inf, 0.0, m)
    p = jnp.where(valid, jnp.exp(s - m_safe[:, None]), 0.0)
    return p


def _flash_bwd_dq_kernel(koff_ref, q_ref, k_ref, v_ref, m_ref, ob_ref,
                         lb_ref, dq_ref, *, causal, block_q, block_k,
                         tk_valid, scale):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    if causal:
        run = (kj * block_k + koff_ref[0]) <= (qi * block_q + block_q - 1)
    else:
        run = kj >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        p = _flash_bwd_p(q, k, m_ref[0, :, 0], koff_ref[0], qi, kj,
                         causal=causal, block_q=block_q, block_k=block_k,
                         tk_valid=tk_valid, scale=scale)
        # ds = p * (o_bar @ v^T + l_bar)
        ovt = jax.lax.dot_general(ob_ref[0], v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (ovt + lb_ref[0, :, 0][:, None])
        dq_ref[0] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale


def _flash_bwd_dkv_kernel(koff_ref, q_ref, k_ref, v_ref, m_ref, ob_ref,
                          lb_ref, dk_ref, dv_ref, *, causal, block_q,
                          block_k, tk_valid, scale):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    if causal:
        run = (kj * block_k + koff_ref[0]) <= (qi * block_q + block_q - 1)
    else:
        run = qi >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        ob = ob_ref[0]
        p = _flash_bwd_p(q, k, m_ref[0, :, 0], koff_ref[0], qi, kj,
                         causal=causal, block_q=block_q, block_k=block_k,
                         tk_valid=tk_valid, scale=scale)
        pT = p.astype(ob.dtype)
        dv_ref[0] += jax.lax.dot_general(
            pT, ob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ovt = jax.lax.dot_general(ob, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (ovt + lb_ref[0, :, 0][:, None])
        dk_ref[0] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale


def flash_attention_bwd(q, k, v, m, o_bar, l_bar, causal, block_size,
                        kv_offset):
    """Gradients (dq, dk, dv) of flash_attention_partial's (o, l)
    outputs given cotangents o_bar (B,H,Tq,D) and l_bar (B,H,Tq); the
    m cotangent is absorbed by the rescaling invariance (see above).
    m is the forward's row-max state (B,H,Tq)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(D) ** 0.5
    bq = max(128, min(512, (int(block_size) // 128) * 128 or 128))
    bk = bq

    def _flat(x, t):
        return jnp.reshape(jnp.transpose(x, (0, 2, 1, 3)), (B * H, t, D))

    qf = _pad_to(_pad_to(_flat(q, Tq), 1, bq), 2, 128)
    kf = _pad_to(_pad_to(_flat(k, Tk), 1, bk), 2, 128)
    vf = _pad_to(_pad_to(_flat(v, Tk), 1, bk), 2, 128)
    obf = _pad_to(_pad_to(jnp.reshape(o_bar.astype(jnp.float32),
                                      (B * H, Tq, D)), 1, bq), 2, 128)
    # m / l_bar ride as (BH, T, 128) lane-broadcast tensors (the same
    # layout rule as the forward's m/l outputs)
    mf = _pad_to(jnp.broadcast_to(
        jnp.reshape(m, (B * H, Tq))[..., None], (B * H, Tq, 128)), 1, bq)
    lbf = _pad_to(jnp.broadcast_to(
        jnp.reshape(l_bar.astype(jnp.float32), (B * H, Tq))[..., None],
        (B * H, Tq, 128)), 1, bq)
    # padded q rows contribute nothing because their o_bar/l_bar cotangent
    # rows are zero-padded (m is zero-padded there, so p=1, but every term
    # it multiplies is 0).
    Dp, Tqp, Tkp = qf.shape[2], qf.shape[1], kf.shape[1]
    try:
        vma = (jax.typeof(qf).vma | jax.typeof(kf).vma | jax.typeof(vf).vma
               | jax.typeof(obf).vma)
    except Exception:
        vma = frozenset()
    koff = jnp.asarray(kv_offset, jnp.int32).reshape(1)
    kern_kwargs = dict(causal=causal, block_q=bq, block_k=bk,
                       tk_valid=Tk, scale=scale)
    cparams = _compiler_params("parallel", "parallel", "arbitrary")

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kern_kwargs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, Tqp // bq, Tkp // bk),
            in_specs=[
                _vmem_spec((1, bq, Dp), lambda bh, qi, kj, koff: (bh, qi, 0)),
                _vmem_spec((1, bk, Dp), lambda bh, qi, kj, koff: (bh, kj, 0)),
                _vmem_spec((1, bk, Dp), lambda bh, qi, kj, koff: (bh, kj, 0)),
                _vmem_spec((1, bq, 128), lambda bh, qi, kj, koff: (bh, qi, 0)),
                _vmem_spec((1, bq, Dp), lambda bh, qi, kj, koff: (bh, qi, 0)),
                _vmem_spec((1, bq, 128), lambda bh, qi, kj, koff: (bh, qi, 0)),
            ],
            out_specs=[
                _vmem_spec((1, bq, Dp), lambda bh, qi, kj, koff: (bh, qi, 0)),
            ],
        ),
        out_shape=[_sds((B * H, Tqp, Dp), vma)],
        compiler_params=cparams,
        interpret=_interpret(),
        name="flash_transpose_dq_partial",
    )(koff, qf, kf, vf, mf, obf, lbf)[0]

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kern_kwargs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, Tkp // bk, Tqp // bq),
            in_specs=[
                _vmem_spec((1, bq, Dp), lambda bh, kj, qi, koff: (bh, qi, 0)),
                _vmem_spec((1, bk, Dp), lambda bh, kj, qi, koff: (bh, kj, 0)),
                _vmem_spec((1, bk, Dp), lambda bh, kj, qi, koff: (bh, kj, 0)),
                _vmem_spec((1, bq, 128), lambda bh, kj, qi, koff: (bh, qi, 0)),
                _vmem_spec((1, bq, Dp), lambda bh, kj, qi, koff: (bh, qi, 0)),
                _vmem_spec((1, bq, 128), lambda bh, kj, qi, koff: (bh, qi, 0)),
            ],
            out_specs=[
                _vmem_spec((1, bk, Dp), lambda bh, kj, qi, koff: (bh, kj, 0)),
                _vmem_spec((1, bk, Dp), lambda bh, kj, qi, koff: (bh, kj, 0)),
            ],
        ),
        out_shape=[_sds((B * H, Tkp, Dp), vma),
                   _sds((B * H, Tkp, Dp), vma)],
        compiler_params=cparams,
        interpret=_interpret(),
        name="flash_transpose_dkv_partial",
    )(koff, qf, kf, vf, mf, obf, lbf)

    def _unflat(x, t):
        return jnp.transpose(
            jnp.reshape(x[:, :t, :D], (B, H, t, D)), (0, 2, 1, 3))

    return (_unflat(dq, Tq).astype(q.dtype),
            _unflat(dk, Tk).astype(k.dtype),
            _unflat(dv, Tk).astype(v.dtype))


# ---------------------------------------------------------------------------
# Normalized flash MHA — the fast path for plain (non-ring) attention.
#
# The partial-state kernel above serves ring attention, which must merge
# un-normalized (o, m, l) across hops; for ordinary self-attention that
# API costs real HBM: o leaves as f32, m and l leave as (BH, T, 128)
# lane-broadcast f32 tensors, the normalize pass re-reads everything,
# and the head dim is padded to 128 lanes IN HBM.  This kernel instead
# keeps the online-softmax state in VMEM scratch across the k-block
# grid axis, normalizes in-register at the last k-block, and writes the
# output ONCE in the input dtype at the unpadded head dim — I/O drops
# ~6x for d_head=64 models.  The residual saved for backward is the
# single logsumexp tensor; the backward kernels rematerialize p from
# (q, k, lse), the standard flash backward (ds = p ∘ (do·vT − Δ) with
# Δ = rowsum(do ∘ o) computed outside).
# ---------------------------------------------------------------------------


def _mha_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                    l_ref, *, causal, block_q, block_k, tq_valid, tk_valid,
                    scale, nk):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        run = (kj * block_k) <= (qi * block_q + block_q - 1)
        last_kj = jnp.minimum(nk - 1, (qi * block_q + block_q - 1)
                              // block_k)
    else:
        run = kj >= 0
        last_kj = nk - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < tk_valid
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid &= k_pos <= q_pos
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe[:, None]), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)

    @pl.when(kj == last_kj)
    def _finalize():
        l = l_ref[:, 0]
        m = m_ref[:, 0]
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _mha_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, acc_ref, *, causal, block_q, block_k,
                       tq_valid, tk_valid, scale, nk):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if causal:
        run = (kj * block_k) <= (qi * block_q + block_q - 1)
        last_kj = jnp.minimum(nk - 1, (qi * block_q + block_q - 1)
                              // block_k)
    else:
        run = kj >= 0
        last_kj = nk - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = (k_pos < tk_valid) & (q_pos < tq_valid)
        if causal:
            valid &= k_pos <= q_pos
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, :, 0][:, None]), 0.0)
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0, :, 0][:, None])
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kj == last_kj)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _mha_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dka_ref, dva_ref, *, causal,
                        block_q, block_k, tq_valid, tk_valid, scale, nq):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dka_ref[...] = jnp.zeros_like(dka_ref)
        dva_ref[...] = jnp.zeros_like(dva_ref)

    if causal:
        run = (kj * block_k) <= (qi * block_q + block_q - 1)
    else:
        run = qi >= 0

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = (k_pos < tk_valid) & (q_pos < tq_valid)
        if causal:
            valid &= k_pos <= q_pos
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, :, 0][:, None]), 0.0)
        pT = p.astype(do.dtype)
        dva_ref[...] += jax.lax.dot_general(
            pT, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0, :, 0][:, None])
        dka_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dka_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dva_ref[...].astype(dv_ref.dtype)


def _mha_block(block_size, t):
    if int(block_size) <= 0:  # auto: larger tiles amortize the online-
        # softmax state updates — 1024 from T = 2048 on, 512 below.  The
        # normalized kernels have no sweep of their own on record; the
        # packed family's (PERF.md §6, PR 32) and the windowed family's
        # (PR 43: `_mha_window_tiles` picks its own) read the same way:
        # the wider tile wins wherever it fits
        block_size = 1024 if t >= 2048 else 512
    b = max(128, min(2048, (int(block_size) // 128) * 128 or 128))
    return min(b, max(128, ((t + 127) // 128) * 128))


def _mha_blocks(block_size, tq, tk):
    """(block_q, block_k) for the normalized flash_mha kernels:
    symmetric.  What block_q != block_k buys was measured on the packed
    family (PERF.md §6, PR 32: at T = 4096 a (512, 2048) tile runs the
    forward in 2.27 ms beside the square 1024's 2.95); nothing of the
    kind has been reproduced through this API."""
    return (_mha_block(block_size, tq), _mha_block(block_size, tk))


@functools.lru_cache(maxsize=None)
def _flash_mha_fn(causal, block_size):
    """custom_vjp per (causal, block_size): normalized Pallas forward +
    Pallas backward from the lse residual."""

    @jax.custom_vjp
    def f(q, k, v):
        o, _ = _mha_fwd(q, k, v, causal, block_size)
        return o

    def fwd(q, k, v):
        o, lse = _mha_fwd(q, k, v, causal, block_size)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        return _mha_bwd(q, k, v, o, lse, do, causal, block_size)

    f.defvjp(fwd, bwd)
    return f


def flash_mha(q, k, v, causal=False, block_size=512):
    """Normalized flash attention: (BH, T, D) q/k/v (any D; bf16/f32)
    → (BH, T, D) output in q.dtype.  Differentiable (custom Pallas
    backward)."""
    return _flash_mha_fn(bool(causal), int(block_size))(q, k, v)


def _sds_t(shape, dtype, vma):
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _mha_fwd(q, k, v, causal, block_size):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(D) ** 0.5
    # under shard_map (Ulysses sequence parallelism) the outputs vary
    # over the same mesh axes as the inputs; pallas_call must declare it
    try:
        vma = jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma
    except Exception:
        vma = frozenset()
    bq, bk = _mha_blocks(block_size, Tq, Tk)
    qf = _pad_to(q, 1, bq)
    kf = _pad_to(k, 1, bk)
    vf = _pad_to(v, 1, bk)
    Tqp, Tkp = qf.shape[1], kf.shape[1]
    nq, nk = Tqp // bq, Tkp // bk
    kern = functools.partial(
        _mha_fwd_kernel, causal=causal, block_q=bq, block_k=bk,
        tq_valid=Tq, tk_valid=Tk, scale=scale, nk=nk)
    scratch = [pltpu.VMEM((bq, D), jnp.float32),
               pltpu.VMEM((bq, 128), jnp.float32),
               pltpu.VMEM((bq, 128), jnp.float32)]
    o, lse = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            _vmem_spec((1, bq, D), lambda bh, qi, kj: (bh, qi, 0)),
            _vmem_spec((1, bk, D), lambda bh, qi, kj: (bh, kj, 0)),
            _vmem_spec((1, bk, D), lambda bh, qi, kj: (bh, kj, 0)),
        ],
        out_specs=[
            _vmem_spec((1, bq, D), lambda bh, qi, kj: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[_sds_t((BH, Tqp, D), q.dtype, vma),
                   _sds_t((BH, Tqp, 128), jnp.float32, vma)],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(
            "parallel", "parallel", "arbitrary",
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="flash_fwd_mha",
    )(qf, kf, vf)
    return o[:, :Tq], lse[:, :Tq]


# ---------------------------------------------------------------------------
# Windowed flash MHA — causal attention in which a query sees itself and
# the W - 1 keys before it.  The normalized kernel above with a lower
# bound beside the diagonal: a query tile walks only the key tiles its
# band [first query - W + 1, last query] touches (the grid's key axis is
# as long as the widest band, counted from the band's first tile) and
# never fetches a tile outside the band.  A tile wholly inside the band
# runs without a mask; a tile an edge of the band crosses — the
# diagonal, the window's lower edge — is walked in sub-blocks: those no
# row can see are not computed, only those an edge cuts are masked
# (`_band_walk`; the packed family's `_walk` with a second edge).  The
# schedule is the kernel's own choice from the shape
# (`_mha_window_tiles`).  Grouped queries need no repeated K/V: query
# head h reads KV head h // group through the index map.  Forward only
# (the family that has windows serves, it does not train).
# ---------------------------------------------------------------------------


def _tile_of(row, block):
    """``row // block`` of a row >= 0 on the scalar core, which has no
    divider (PERF.md §6, PR 39): a shift where the block is a power of
    two, as the tiles of every served bucket are."""
    if block & (block - 1) == 0:
        return jnp.right_shift(row, block.bit_length() - 1)
    return jax.lax.div(row, jnp.int32(block))


def _last_live_tile(length, block):
    """The last query tile that holds a row of a prompt of ``length``
    rows (tile 0 for an empty one): the tiles after it are DEAD — they
    hold only the bucket's padding."""
    return _tile_of(jnp.maximum(length - 1, 0), block)


def _window_first_tile(qi, block_q, block_k, window):
    """The key tile that holds the lowest key query tile ``qi`` sees
    (``window`` 0: no lower edge, tile 0)."""
    if not window:
        return 0
    return _tile_of(jnp.maximum(qi * block_q - (window - 1), 0), block_k)


def _diagonal_tile(qi, block_q, block_k):
    """The key tile that holds query tile ``qi``'s last row: where its
    walk ends (``qi`` itself where the tiles are square)."""
    if block_q == block_k:
        return qi
    return _tile_of(qi * block_q + (block_q - 1), block_k)


def _walked_key_tile(qi, step, last_live, first, dead_step,
                     last=lambda qi: qi):
    """The key tile whose blocks grid step (qi, step) of a prompt kernel
    holds: a live query tile walks its band's key tiles from
    ``first(qi)`` up to the diagonal's (``last(qi)``: key tile qi where
    the tiles are square) and stands still there for the steps that are
    left; a dead one stands on the last live tile's last blocks through
    all its steps, so nothing is fetched for it.  ``dead_step``: any
    step count past a band's width."""
    row = jnp.minimum(qi, last_live)
    kj = first(row) + jnp.where(qi > last_live, dead_step, step)
    return jnp.minimum(kj, last(row))


def _band_walk(off, block_q, block_k, sub, window=0):
    """The sub-blocks of a (block_q, block_k) tile that an edge of the
    band crosses — ``_walk`` with a lower edge beside the diagonal:
    ``[(rows, [(columns, cut)])]`` as slices, a row sub-block of ``sub``
    rows with the spans of the tile's columns it multiplies.  ``off`` is
    the tile's first column less its first row; row i sees column j
    where ``-window < j + off - i <= 0`` (``window`` 0: every column up
    to the diagonal).  A (sub x sub) block no row of which sees a column
    is left out, the blocks every row sees whole are joined into one
    span with ``cut`` None, and a block an edge cuts comes alone with
    ``cut = (lo, hi)``: inside it local column j' is seen by local row
    i' where ``lo < j' - i' <= hi`` (a bound that cannot cut the block
    is None)."""
    top = -off                                # j - i <= top: the diagonal
    floor = top - window if window else None  # j - i > floor: the window
    out = []
    for b0 in range(0, block_q, sub):
        pieces = []
        for c0 in range(0, block_k, sub):
            # j - i over this block: c0 - b0 - sub < j - i < c0 - b0 + sub
            d = c0 - b0
            if d - sub >= top or (floor is not None and d + sub - 1 <= floor):
                continue            # no row of the block sees a column
            hi = top - d if d + sub - 1 > top else None
            lo = floor - d if floor is not None and d - sub < floor else None
            if lo is None and hi is None and pieces \
                    and pieces[-1][1] is None and pieces[-1][0].stop == c0:
                pieces[-1] = (slice(pieces[-1][0].start, c0 + sub), None)
            else:
                pieces.append((slice(c0, c0 + sub),
                               None if lo is None and hi is None
                               else (lo, hi)))
        if pieces:
            out.append((slice(b0, b0 + sub), pieces))
    return out


def _mha_window_tiles(t, window):
    """(block_q, block_k, sub, inner) of ``flash_mha_window`` for a
    prompt bucket of ``t`` rows under ``window`` (0: global): the tile a
    grid step holds, the sub-block an edge tile is walked in, and the
    rows of a tile wholly inside the band that are updated at a time.
    From the shape alone, each choice a row of the kernel-alone sweep in
    PERF.md section 6, PR 43 (``tools/verify_kernels.py --gqa-tiles``
    prints it again, patching this chooser): query tiles of 1,024 rows
    (one tile where the bucket is smaller); key tiles of 2,048 where
    they pad the rows no further — half the grid steps and state
    updates of the square tile; 512 rows of an interior tile at a time;
    edge tiles in sub-blocks of 256 (128 read 1-4% faster alone and
    twice as long to trace and lower, which every process pays)."""
    t128 = t + (-t) % 128
    bq = min(1024, t128)
    rows = t + (-t) % bq
    return (bq, 2048 if rows % 2048 == 0 else bq,
            256 if bq % 256 == 0 else 128, 512 if bq % 512 == 0 else bq)


_BandSchedule = collections.namedtuple(
    "_BandSchedule", "band first last masked scores edges interior")


@functools.lru_cache(maxsize=None)
def _band_schedule(rows, block_q, block_k, sub, window):
    """What each query tile of a prompt kernel's grid does over a bucket
    of ``rows`` (whole tiles): ``band``, the window where it is a lower
    edge at all (0 where it is as wide as the rows, or none); numpy
    arrays a query tile — ``first`` and ``last`` key tile of its walk,
    the tiles of it that take a ``masked`` body (an edge of the band
    crosses them) and the ``scores`` it computes (an edge tile only its
    ``_band_walk``); ``edges``, the offsets (first column less first
    row) of the grid's edge tiles, and ``interior``, whether it has a
    tile wholly inside the band."""
    import numpy as np

    band = window if 0 < window < rows else 0
    r0 = np.arange(rows // block_q) * block_q
    first = np.maximum(r0 - (band - 1), 0) // block_k if band \
        else np.zeros_like(r0)
    last = (r0 + block_q - 1) // block_k
    masked = np.zeros_like(r0)
    scores = np.zeros_like(r0)
    edges, interior = {}, False
    for i, (row, lo, hi) in enumerate(zip(r0, first, last)):
        for kj in range(lo, hi + 1):
            off = int(kj * block_k - row)
            if off <= -block_k and not (band and off < block_q - band):
                interior = True
                scores[i] += block_q * block_k
                continue
            if off not in edges:
                edges[off] = sum(
                    (r.stop - r.start) * (c.stop - c.start)
                    for r, pieces in _band_walk(off, block_q, block_k, sub,
                                                band)
                    for c, _ in pieces)
            masked[i] += 1
            scores[i] += edges[off]
    return _BandSchedule(band, first, last, masked, scores,
                         tuple(sorted(edges)), interior)


def _prompt_schedule(rows, window, latent):
    """(query tile, padding block, schedule) of the kernel a layer's
    prefill runs over a bucket of ``rows`` — ``flash_mha_window``, or
    ``mla_flash`` where ``latent`` (no window; its walk follows from
    the bucket alone, so no width is asked for): ``schedule(block)`` is
    the ``_band_schedule`` of the bucket's rows in query blocks of
    ``block`` rows over the kernel's key tiles and sub-blocks, and the
    padding block the rows by which a kernel leaves its last live
    tile's padding out (``flash_mha_window``: the tile, nothing left
    out)."""
    if latent:
        (bq, bk, sub, guard), window = _mla_tiles(rows, 0, 0, 0, 0)[:4], 0
    else:
        bq, bk, sub, _ = _mha_window_tiles(rows, window)
        guard = bq
    padded = rows + (-rows) % max(bq, bk)
    return bq, guard, lambda block: _band_schedule(padded, block, bk, sub,
                                                   window)


def prompt_tile_visits(length, rows, window=0, latent=False):
    """(walked, skipped) key-tile visits of one head of a prompt kernel
    — ``flash_mha_window`` (``window`` 0: global), or ``mla_flash``
    where ``latent`` (the same walk without a window, over the tiles
    ``_mla_tiles`` picks) — over a prompt of ``length`` rows in a bucket
    of ``rows``: a live query tile walks its band's tiles up to the
    diagonal's, a dead one (only padding) walks none, and what the dead
    ones would have walked is ``skipped``.  Their sum is the bucket's
    tiles, what a caller without ``lengths`` walks.  Host arithmetic
    (the engine's ``prefill_tiles_*`` counters);
    tests/test_prompt_lengths.py holds it to the interpreted kernels'
    own steps."""
    bq, _, schedule = _prompt_schedule(rows, window, latent)
    plan = schedule(bq)
    walk = plan.last - plan.first + 1
    live = -(-min(max(int(length), 0), rows) // bq)
    return int(walk[:live].sum()), int(walk[live:].sum())


def prompt_tile_work(length, rows, window=0, latent=False):
    """(masked, computed, needed) of one head of a prompt kernel
    (``latent``: ``mla_flash``, whose only edge is the diagonal) over a
    prompt of ``length`` rows in a bucket of ``rows``: the walked tiles
    that took a MASKED body (an edge of the band crosses them; the
    others run without one), the score elements the schedule computes
    (a whole tile inside the band, only the sub-blocks a row can see of
    an edge tile; the last live tile's padding rows count — in
    ``mla_flash`` up to the end of the prompt's last block of ``inner``
    rows, the blocks after it are left out) and the pairs the band
    holds, the least there is to compute.  Host arithmetic beside
    :func:`prompt_tile_visits` (the engine's ``prefill_tiles_masked`` /
    ``prefill_scores_computed_over_needed``), held to the interpreted
    kernels' own blocks by the same test."""
    bq, guard, schedule = _prompt_schedule(rows, window, latent)
    n = min(max(int(length), 0), rows)
    w = min(n, window) if window else n
    # a query tile's scores are its blocks' (a sub-block is computed
    # where a row sees a column, whatever tile holds it)
    return (int(schedule(bq).masked[:-(-n // bq)].sum()),
            int(schedule(guard).scores[:-(-n // guard)].sum()),
            w * (w + 1) // 2 + (n - w) * w)


def _band_mask(sub, lo, hi):
    """A cut block's mask, (sub, sub): ``lo < column - row <= hi``."""
    d = (jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
         - jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0))
    if lo is None:
        return d <= hi
    return d > lo if hi is None else (d > lo) & (d <= hi)


def _band_update(scores, values, acc_ref, m_ref, l_ref, blk, pieces):
    """One update of the rows ``blk``'s online-softmax state (``acc``,
    ``m``, ``l``: a head's) over the column spans ``pieces`` =
    [(columns, mask or None)], in the exp2 domain: ``scores(columns)``
    the rows' float32 scores x scale x log2(e), ``values(columns)`` the
    value rows their probabilities multiply."""
    ss = []
    for cols, mask in pieces:
        s = scores(cols)
        ss.append(s if mask is None else jnp.where(mask, s, -jnp.inf))
    m_prev = m_ref[blk, :1]
    m_new = m_prev
    for s in ss:
        m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
    if any(mask is not None for _, mask in pieces):
        # a row may have seen no key yet (under the window's edge)
        m_use = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0,
                          jnp.exp2(m_prev - m_use))
    else:       # every key is seen: the maximum is finite, and
        m_use = m_new       # exp2(-inf - m) is 0 at the first tile
        alpha = jnp.exp2(m_prev - m_new)
    l_new = l_ref[blk, :1] * alpha
    pv = None
    for s, (cols, _) in zip(ss, pieces):
        p = jnp.exp2(s - m_use)     # a masked score is -inf: 0
        l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
        v = values(cols)
        d = _dot(p.astype(v.dtype), v, 1, 0)
        pv = d if pv is None else pv + d
    n = blk.stop - blk.start
    l_ref[blk, :] = jnp.broadcast_to(l_new, (n, l_ref.shape[1]))
    m_ref[blk, :] = jnp.broadcast_to(m_new, (n, m_ref.shape[1]))
    acc_ref[blk, :] = acc_ref[blk, :] * alpha + pv


def _mha_window_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                       l_ref, *, block_q, block_k, sub, inner, scale, window,
                       edges, interior):
    qi = pl.program_id(2)
    step = pl.program_id(3)
    length = len_ref[pl.program_id(0)]
    live = qi * block_q < length
    # a live tile's walk ends on the diagonal's tile: the sub-blocks of
    # it that are computed lie at or before the query tile's last row
    kj = _window_first_tile(qi, block_q, block_k, window) + step
    last = _diagonal_tile(qi, block_q, block_k)
    walked = live & (kj <= last)
    off = kj * block_k - qi * block_q   # first column less first row

    @pl.when(jnp.logical_not(live) & (step == 0))
    def _dead():
        # only the bucket's padding: zeros, never what the buffer held
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(live & (step == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(blk, pieces):
        q = q_ref[0, blk, :]
        _band_update(
            lambda cols: _dot(q, k_ref[0, cols, :], 1, 1)
            * (scale * _LOG2E),
            lambda cols: v_ref[0, cols, :], acc_ref, m_ref, l_ref, blk,
            pieces)

    if interior:    # wholly inside the band: no mask, no guard
        inside = walked & (off <= -block_k)
        if window:
            inside &= off >= block_q - window

        @pl.when(inside)
        def _interior():
            for r0 in range(0, block_q, inner):
                update(slice(r0, r0 + inner), [(slice(0, block_k), None)])

    # an edge of the band crosses the tile: walked in sub-blocks, only
    # what a row can see computed, only the blocks an edge cuts masked.
    # Static slices: one body an offset this grid can reach
    for o in edges:
        def _edge(o=o):
            masks = {}
            for blk, pieces in _band_walk(o, block_q, block_k, sub, window):
                for _, cut in pieces:
                    if cut is not None and cut not in masks:
                        masks[cut] = _band_mask(sub, *cut)
                update(blk, [(cols, cut and masks[cut])
                             for cols, cut in pieces])

        pl.when(walked & (off == o))(_edge)

    whole = (qi + 1) * block_q <= length  # no row of the tile is padding

    def out():
        return acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)

    @pl.when(whole & (kj == last))
    def _finalize():
        o_ref[0] = out().astype(o_ref.dtype)

    @pl.when(live & jnp.logical_not(whole) & (kj == last))
    def _finalize_last():
        # the prompt ends inside this tile: its padding rows as zeros
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        o_ref[0] = jnp.where(row < length, out(), 0.0).astype(o_ref.dtype)


def _prompt_lengths(lengths, batch, rows):
    """(batch,) int32 prompt lengths within the bucket's ``rows``;
    None: every row is the prompt's."""
    if lengths is None:
        return jnp.full((batch,), rows, jnp.int32)
    return jnp.clip(lengths.astype(jnp.int32).reshape(batch), 0, rows)


def flash_mha_window(q, k, v, window, heads=1, kv_heads=1, lengths=None):
    """Causal attention under a sliding window: q (B·H, T, D); k, v
    (B·Hkv, T, D), query head h on KV head ``h // (H / Hkv)``; query i
    sees keys ``i - window + 1 .. i`` -> (B·H, T, D) in q.dtype.

    ``window`` 0: every key up to the query — a grouped-query model's
    GLOBAL layers, by the same walk with a band as wide as the prompt
    (a tile above the diagonal neither fetched nor computed, K and V
    read once a KV head's query heads through the index map, no
    ``lse`` written: serving reads none), under the name the
    normalized forward has, ``flash_fwd_mha``.

    The schedule (``_mha_window_tiles``, ``_band_schedule``): a tile
    wholly inside the band runs without a mask; a tile an edge of the
    band crosses — the diagonal's, and the one the window's lower edge
    cuts — is walked in sub-blocks (``_band_walk``), of which only
    those a row can see are computed and only those an edge cuts are
    masked.  :func:`prompt_tile_work` counts both kinds.

    ``lengths`` (B,): the rows of each prompt in its bucket of T (None:
    T), a scalar operand read at run time.  Rows below a length are
    computed by the tiles, in the order, they are computed without it;
    rows at and past it come out 0, and a query tile that holds only
    such rows walks no key tile and fetches nothing
    (:func:`prompt_tile_visits` counts both kinds)."""
    BH, T, D = q.shape
    window = int(window)
    if window < 0 or int(heads) % int(kv_heads) \
            or k.shape[0] * (int(heads) // int(kv_heads)) != BH:
        raise MXNetError(
            f"flash_mha_window: window {window} must be >= 0 and q "
            f"{tuple(q.shape)} hold {heads} query heads over the "
            f"{kv_heads} KV heads of k {tuple(k.shape)}")
    return _flash_mha_window(
        q, k, v, _prompt_lengths(lengths, BH // int(heads), T),
        window=window, heads=int(heads), kv_heads=int(kv_heads),
        tiles=_mha_window_tiles(T, window))


# jitted: the layers of a program that call it at one shape share ONE
# trace of the kernel and one lowered function (a windowed layer's walk
# is a few hundred operations unrolled: PERF.md section 6, PR 43)
@functools.partial(jax.jit, static_argnames=("window", "heads", "kv_heads",
                                             "tiles"))
def _flash_mha_window(q, k, v, lens, *, window, heads, kv_heads, tiles):
    BH, T, D = q.shape
    group = heads // kv_heads
    bq, bk, sub, inner = tiles
    qf, kf, vf = (_pad_to(x, 1, max(bq, bk)) for x in (q, k, v))
    rows = qf.shape[1]
    plan = _band_schedule(rows, bq, bk, sub, window)
    steps = int((plan.last - plan.first + 1).max())
    first = functools.partial(_window_first_tile, block_q=bq, block_k=bk,
                              window=plan.band)
    last = functools.partial(_diagonal_tile, block_q=bq, block_k=bk)
    # one length a KV head, and the grid's first axis the KV heads: the
    # scalar core then divides by nothing but the (power of two) tile
    lens = jnp.repeat(lens, kv_heads)

    def q_map(kvh, g, qi, step, len_ref):
        return (kvh * group + g,
                jnp.minimum(qi, _last_live_tile(len_ref[kvh], bq)), 0)

    def kv_map(kvh, g, qi, step, len_ref):
        return (kvh, _walked_key_tile(
            qi, step, _last_live_tile(len_ref[kvh], bq), first, steps,
            last), 0)

    kern = functools.partial(
        _mha_window_kernel, block_q=bq, block_k=bk, sub=sub, inner=inner,
        scale=1.0 / float(D) ** 0.5, window=plan.band, edges=plan.edges,
        interior=plan.interior)
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH // group, group, rows // bq, steps),
            in_specs=[
                _vmem_spec((1, bq, D), q_map),
                _vmem_spec((1, bk, D), kv_map),
                _vmem_spec((1, bk, D), kv_map),
            ],
            out_specs=_vmem_spec(
                (1, bq, D),
                lambda kvh, g, qi, step, len_ref: (kvh * group + g, qi, 0)),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary",
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="flash_fwd_window" if window else "flash_fwd_mha",
    )(lens, qf, kf, vf)
    return o[:, :T]


def _mha_bwd(q, k, v, o, lse, do, causal, block_size):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(D) ** 0.5
    try:
        vma = (jax.typeof(q).vma | jax.typeof(k).vma | jax.typeof(v).vma
               | jax.typeof(do).vma)
    except Exception:
        vma = frozenset()
    bq, bk = _mha_blocks(block_size, Tq, Tk)
    qf = _pad_to(q, 1, bq)
    kf = _pad_to(k, 1, bk)
    vf = _pad_to(v, 1, bk)
    dof = _pad_to(do.astype(q.dtype), 1, bq)
    # Δ = rowsum(do ∘ o) — one cheap fused elementwise+reduce outside
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    deltaf = _pad_to(jnp.broadcast_to(delta[..., None],
                                      (BH, Tq, 128)), 1, bq)
    lsef = _pad_to(lse, 1, bq)  # already (BH, Tq, 128) lane-broadcast
    Tqp, Tkp = qf.shape[1], kf.shape[1]
    nq, nk = Tqp // bq, Tkp // bk
    kw = dict(causal=causal, block_q=bq, block_k=bk, tq_valid=Tq,
              tk_valid=Tk, scale=scale)
    cparams = _compiler_params("parallel", "parallel", "arbitrary",
                               vmem_limit_bytes=_VMEM_LIMIT)

    dq = pl.pallas_call(
        functools.partial(_mha_bwd_dq_kernel, nk=nk, **kw),
        grid=(BH, nq, nk),
        in_specs=[
            _vmem_spec((1, bq, D), lambda bh, qi, kj: (bh, qi, 0)),
            _vmem_spec((1, bk, D), lambda bh, qi, kj: (bh, kj, 0)),
            _vmem_spec((1, bk, D), lambda bh, qi, kj: (bh, kj, 0)),
            _vmem_spec((1, bq, D), lambda bh, qi, kj: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, qi, kj: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_specs=[_vmem_spec((1, bq, D), lambda bh, qi, kj: (bh, qi, 0))],
        out_shape=[_sds_t((BH, Tqp, D), q.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=cparams,
        interpret=_interpret(),
        name="flash_transpose_dq_mha",
    )(qf, kf, vf, dof, lsef, deltaf)[0]

    dk, dv = pl.pallas_call(
        functools.partial(_mha_bwd_dkv_kernel, nq=nq, **kw),
        grid=(BH, nk, nq),
        in_specs=[
            _vmem_spec((1, bq, D), lambda bh, kj, qi: (bh, qi, 0)),
            _vmem_spec((1, bk, D), lambda bh, kj, qi: (bh, kj, 0)),
            _vmem_spec((1, bk, D), lambda bh, kj, qi: (bh, kj, 0)),
            _vmem_spec((1, bq, D), lambda bh, kj, qi: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, kj, qi: (bh, qi, 0)),
            _vmem_spec((1, bq, 128), lambda bh, kj, qi: (bh, qi, 0)),
        ],
        out_specs=[
            _vmem_spec((1, bk, D), lambda bh, kj, qi: (bh, kj, 0)),
            _vmem_spec((1, bk, D), lambda bh, kj, qi: (bh, kj, 0)),
        ],
        out_shape=[_sds_t((BH, Tkp, D), k.dtype, vma),
                   _sds_t((BH, Tkp, D), v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=cparams,
        interpret=_interpret(),
        name="flash_transpose_dkv_mha",
    )(qf, kf, vf, dof, lsef, deltaf)

    return dq[:, :Tq], dk[:, :Tk], dv[:, :Tk]


# ---------------------------------------------------------------------------
# Packed-heads flash MHA — attention straight off the fused QKV matmul.
#
# The (BH, T, D) layouts above still require a T↔H relayout between the
# model's (B, T, H·D) activations and the kernel — measured at ~20 ms
# per transformer step (the July per-op profile, git history), because narrow
# d_head transposes run far below HBM speed.  This kernel removes the
# relayout entirely: q, k, v are LANE-BLOCK VIEWS of the fused QKV
# projection output (B, T, 3·H·D) — the same array is passed three
# times with different lane-block index maps — and every head occupies
# its own 64/128-lane span inside the block.  A grid step holds one
# HEAD GROUP — the heads of whole lane tiles, a pair at D = 64 — and
# loops over its heads per (q-block, k-block) tile, keeping each head's
# online-softmax state broadcast over that head's lane span in VMEM
# scratch.  The output is written directly in (B, T, H·D) — the layout
# the following projection matmul wants.  Zero transposes in forward
# or backward.
#
# The schedule is the kernels' own choice (`_mhap_tiles`, from (T, H·D,
# D) alone; the kernel-alone table that decided it is in PERF.md §6,
# PR 32, and `tools/verify_kernels.py --tiles` prints it again).  The
# grid is (row, head group, q tile, k tile) — (…, k tile, q tile) in
# dkv — and a grid step is a (block_q, block_k) tile of a head group's
# scores.  Under the causal mask a tile wholly above the diagonal is
# skipped, a tile wholly under it runs the unmasked body, and a tile
# the diagonal crosses is WALKED in sub-blocks of `sub` rows (columns,
# in dkv): a sub-block multiplies only the columns it can see — the
# span under the diagonal unmasked, the one `sub`-wide span on it under
# a triangle — in ONE update of its rows' state, so a sub-tile wholly
# above the diagonal is never computed.  All slices are static: the
# walk is unrolled once for every offset a crossing tile of the grid
# can have (one, 0, when block_q == block_k).
#
# Padding rows need no mask under the causal mask: q, do, o and lse are
# padded with zeros, a real row sees no padded column, and a padded row
# adds p·(0 − 0) to dk and pᵀ·0 to dv with p = exp2(0 − 0) finite.
# ---------------------------------------------------------------------------

_LOG2E = 1.4426950408889634


def _crossing_offsets(block_q, block_k):
    """The offsets (tile's first column − tile's first row) at which the
    diagonal crosses a (block_q, block_k) tile of the grid.  Further
    left (≤ −block_k) the tile is wholly visible, further right
    (≥ block_q) wholly masked."""
    g = math.gcd(block_q, block_k)
    return range(-block_k + g, block_q, g)


def _walk(off, n_sub, n_full, sub, transposed=False):
    """The sub-blocks of a crossing tile: ``[(sub-block, whole span or
    None, diagonal span or None)]`` as slices.  A sub-block is ``sub``
    of the tile's ``n_sub`` rows and the spans are of its ``n_full``
    columns; ``transposed`` (dkv) walks columns against rows, where a
    column sees the rows FROM its own to the tile's last.  ``off`` is
    the tile's first column less its first row."""
    out = []
    for b0 in range(0, n_sub, sub):
        # where the diagonal enters this sub-block, on the other axis
        lo = b0 + off if transposed else b0 - off
        blk = slice(b0, b0 + sub)
        if transposed:
            if lo >= n_full:
                continue  # no row of the tile sees these columns
            if lo + sub <= 0:
                out.append((blk, slice(0, n_full), None))
                continue
            rest = slice(lo + sub, n_full) if lo + sub < n_full else None
            out.append((blk, rest, slice(lo, lo + sub)))
        else:
            if lo + sub <= 0:
                continue  # wholly above the diagonal: never computed
            if lo >= n_full:
                out.append((blk, slice(0, n_full), None))
                continue
            out.append((blk, slice(0, lo) if lo else None,
                        slice(lo, lo + sub)))
    return out


def _mhap_scores(t, block_q, block_k, sub, causal):
    """Scores a head-row computes under the schedule, and the scores it
    needs (on or under the diagonal) — what the counter
    ``flash.scores_computed_over_needed`` divides."""
    tp = t + (-t) % max(block_q, block_k)
    if not causal:
        return tp * tp, t * t
    crossing = _crossing_offsets(block_q, block_k)
    done = 0
    for r0 in range(0, tp, block_q):
        for c0 in range(0, tp, block_k):
            off = c0 - r0
            if off <= -block_k:
                done += block_q * block_k
            elif off in crossing:
                done += sum(
                    sub * ((w.stop - w.start if w else 0)
                           + (d.stop - d.start if d else 0))
                    for _, w, d in _walk(off, block_q, block_k, sub))
    return done, t * (t + 1) // 2


def _mhap_tiles(t, hd, d):
    """(block_q, block_k, sub, lanes) of the packed kernels for T
    positions of H·D lanes in heads of D: the shape decides, nothing
    else (a page size, an option and a model's name never reach here).
    Each choice is a row of the kernel-alone table in PERF.md §6, PR 32.

    ``lanes``: the heads a grid step holds, as lanes of q (of k, of v):
    whole lane tiles of whole heads — a pair at D = 64 — so a step's
    body is unrolled over two heads, not twenty (Mosaic compiles it in
    seconds, not minutes), and the next group's q, k and v arrive
    while this one is multiplied; H·D itself where the heads do not
    fill lane tiles.  The tile: the widest that fits — a row's m / l /
    acc update costs as much as a good part of a tile's scores, so
    fewer, wider updates win — and T ≤ 1024 is ONE tile: a row meets
    all its keys in one update and carries no state at all; 512 where
    that pads a longer T less; narrower where the operands of the
    widest kernel (dkv: five bf16 and two outputs double-buffered,
    lse, three float32 scratches — 48 bytes a row and lane) would not
    fit VMEM.  ``sub``: 256."""
    g = math.lcm(d, 128)
    lanes = g if hd % g == 0 else hd
    fits = [b for b in (1024, 512, 256, 128)
            if b * lanes * 48 <= (_VMEM_LIMIT * 3) // 4] or [128]
    t128 = t + (-t) % 128
    if t128 <= fits[0]:
        tile = t128
    else:
        tile = min(fits[:2], key=lambda b: (t + (-t) % b, -b))
    sub = 256 if tile % 256 == 0 else 128
    return tile, tile, sub, lanes


def _tri(sub):
    """The diagonal span's mask: column ≤ row, (sub, sub)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
            <= jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0))


def _tile_schedule(qi, kj, H, *, causal, block_q, block_k, sub, nq, nk,
                   t_valid, update, mask_rows=True, transposed=False):
    """Run ``update(h, block, pieces)`` for every head over the tile
    (qi, kj) as the schedule above says: ``block`` is the slice of q
    rows (of k columns, ``transposed``) a head's state is updated for,
    ``pieces`` the ``[(slice of the other axis, mask or None)]`` it
    multiplies.  Without the causal mask, where the ``t_valid``
    positions do not fill their tiles, the edge tiles run under a mask
    of the padded columns — and rows, unless the kernel's padded rows
    are cut off anyway (``mask_rows`` False: the forward)."""
    n_blk, n_other = ((block_k, block_q) if transposed
                      else (block_q, block_k))
    blk, span = slice(0, n_blk), slice(0, n_other)

    def run(walk, mask=None):
        m = mask() if mask is not None else None
        for h in range(H):
            for b, whole, diag in walk:
                update(h, b, [(sp, mk) for sp, mk in
                              ((whole, None), (diag, m)) if sp is not None])

    if causal:
        off = kj * block_k - qi * block_q
        # only what this grid can reach is built: at T = one tile there
        # is no tile under the diagonal, and its body is not compiled
        reach = {c * block_k - r * block_q
                 for r in range(nq) for c in range(nk)}
        if min(reach) <= -block_k:
            pl.when(off <= -block_k)(
                functools.partial(run, [(blk, span, None)]))
        for o in _crossing_offsets(block_q, block_k):
            if o in reach:
                pl.when(off == o)(functools.partial(
                    run, _walk(o, n_blk, n_other, sub, transposed),
                    functools.partial(_tri, sub)))
    elif not (t_valid % block_k or (mask_rows and t_valid % block_q)):
        run([(blk, span, None)])
    else:
        edge = (kj == nk - 1) | (qi == nq - 1)
        pl.when(edge)(functools.partial(
            run, [(blk, None, span)], functools.partial(
                _pad_valid, qi, kj, block_q, block_k, t_valid, mask_rows)))
        pl.when(jnp.logical_not(edge))(
            functools.partial(run, [(blk, span, None)]))


def _dot(a, b, ca, cb):
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pad_valid(qi, kj, block_q, block_k, t_valid, mask_rows):
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < t_valid
    if mask_rows:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = valid & (q_pos < t_valid)
    return valid


def _mhap_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                     l_ref, *, H, D, causal, block_q, block_k, sub,
                     t_valid, scale, nq, nk):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    # one k tile: a row meets all its keys in ONE update, so there is no
    # state to start, rescale or finish — the scratches go unused
    carried = nk > 1

    if carried:
        @pl.when(kj == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

    def update(h, rows, pieces):
        sl = slice(h * D, (h + 1) * D)
        n = rows.stop - rows.start
        q = q_ref[0, rows, sl]
        ss = []
        for cols, mask in pieces:
            s = _dot(q, k_ref[0, cols, sl], 1, 1) \
                * (scale * _LOG2E)  # exp2 domain
            ss.append(s if mask is None else jnp.where(mask, s, -jnp.inf))
        m_new = m_prev = m_ref[rows, h * D] if carried else None
        for s in ss:
            m = jnp.max(s, axis=1)
            m_new = m if m_new is None else jnp.maximum(m_new, m)
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        l_new = pv = None
        for s, (cols, _) in zip(ss, pieces):
            # masked entries hold -inf, so exp2 gives exactly 0 — no
            # second where needed.  (bf16 exp was tried and measured
            # slower: Mosaic upcasts transcendentals, so the converts
            # were pure overhead.)
            p = jnp.exp2(s - m_safe[:, None])
            l = jnp.sum(p, axis=1)
            l_new = l if l_new is None else l_new + l
            v = v_ref[0, cols, sl]
            d = _dot(p.astype(v.dtype), v, 1, 0)
            pv = d if pv is None else pv + d
        if not carried:
            l = jnp.maximum(l_new, 1e-30)
            o_ref[0, rows, sl] = (pv / l[:, None]).astype(o_ref.dtype)
            lse_ref[0, rows, sl] = jnp.broadcast_to(
                (m_new + jnp.log2(l))[:, None], (n, D))
            return
        alpha = jnp.where(m_prev == -jnp.inf, 0.0,
                          jnp.exp2(m_prev - m_safe))
        l_new = l_ref[rows, h * D] * alpha + l_new
        l_ref[rows, sl] = jnp.broadcast_to(l_new[:, None], (n, D))
        acc_ref[rows, sl] = acc_ref[rows, sl] * alpha[:, None] + pv
        m_ref[rows, sl] = jnp.broadcast_to(m_new[:, None], (n, D))

    _tile_schedule(qi, kj, H, causal=causal, block_q=block_q,
                   block_k=block_k, sub=sub, nq=nq, nk=nk,
                   t_valid=t_valid, update=update, mask_rows=False)

    if carried:
        if causal:
            last_kj = jnp.minimum(nk - 1, (qi * block_q + block_q - 1)
                                  // block_k)
        else:
            last_kj = nk - 1

        @pl.when(kj == last_kj)
        def _finalize():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
            lse_ref[0] = m_ref[...] + jnp.log2(l)


def _mhap_delta(delta_ref, do_ref, o_ref, H, D):
    """Δ = per-(row, head) rowsum(do ∘ o) of the q tile into scratch,
    instead of materializing a (B, T, H·D) f32 broadcast tensor in
    HBM."""
    prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    for h in range(H):
        sl = slice(h * D, (h + 1) * D)
        dh = jnp.sum(prod[:, sl], axis=1)
        delta_ref[:, sl] = jnp.broadcast_to(dh[:, None],
                                            (prod.shape[0], D))


def _mhap_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                        dq_ref, acc_ref, delta_ref, *, H, D, causal,
                        block_q, block_k, sub, t_valid, scale, nq, nk):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        _mhap_delta(delta_ref, do_ref, o_ref, H, D)  # once per q-block

    if causal:
        last_kj = jnp.minimum(nk - 1, (qi * block_q + block_q - 1)
                              // block_k)
    else:
        last_kj = nk - 1

    def update(h, rows, pieces):
        sl = slice(h * D, (h + 1) * D)
        q = q_ref[0, rows, sl]
        do = do_ref[0, rows, sl]
        lse = lse_ref[0, rows, h * D][:, None]
        delta = delta_ref[rows, h * D][:, None]
        dq = None
        for cols, mask in pieces:
            k = k_ref[0, cols, sl]
            s = _dot(q, k, 1, 1) * (scale * _LOG2E)  # exp2-domain lse
            p = jnp.exp2(s - lse)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            ds = p * (_dot(do, v_ref[0, cols, sl], 1, 1) - delta)
            d = _dot(ds.astype(k.dtype), k, 1, 0)
            dq = d if dq is None else dq + d
        acc_ref[rows, sl] += dq * scale

    _tile_schedule(qi, kj, H, causal=causal, block_q=block_q,
                   block_k=block_k, sub=sub, nq=nq, nk=nk,
                   t_valid=t_valid, update=update)

    @pl.when(kj == last_kj)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _mhap_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                         dk_ref, dv_ref, dka_ref, dva_ref, delta_ref, *,
                         H, D, causal, block_q, block_k, sub, t_valid,
                         scale, nq, nk):
    kj = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dka_ref[...] = jnp.zeros_like(dka_ref)
        dva_ref[...] = jnp.zeros_like(dva_ref)

    run = (kj * block_k < (qi + 1) * block_q) if causal else None

    # Δ rows of this q-block: qi is the inner axis, so they are made
    # again for every tile that runs — once, not once a sub-block
    if run is None:
        _mhap_delta(delta_ref, do_ref, o_ref, H, D)
    else:
        pl.when(run)(lambda: _mhap_delta(delta_ref, do_ref, o_ref, H, D))

    def update(h, cols, pieces):
        sl = slice(h * D, (h + 1) * D)
        k = k_ref[0, cols, sl]
        v = v_ref[0, cols, sl]
        dv = dk = None
        for rows, mask in pieces:
            q = q_ref[0, rows, sl]
            do = do_ref[0, rows, sl]
            s = _dot(q, k, 1, 1) * (scale * _LOG2E)  # exp2-domain lse
            p = jnp.exp2(s - lse_ref[0, rows, h * D][:, None])
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            d = _dot(p.astype(do.dtype), do, 0, 0)
            dv = d if dv is None else dv + d
            ds = p * (_dot(do, v, 1, 1) - delta_ref[rows, h * D][:, None])
            d = _dot(ds.astype(q.dtype), q, 0, 0)
            dk = d if dk is None else dk + d
        dva_ref[cols, sl] += dv
        dka_ref[cols, sl] += dk * scale

    _tile_schedule(qi, kj, H, causal=causal, block_q=block_q,
                   block_k=block_k, sub=sub, nq=nq, nk=nk,
                   t_valid=t_valid, update=update, transposed=True)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dka_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dva_ref[...].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _flash_mha_packed_fn(H, D, causal, tiles, interpret):
    """custom_vjp per (H, D, causal, tiles), jitted: a model's layers
    call it with one shape, so the kernels' bodies — unrolled over heads
    and sub-blocks — are traced and lowered once a program, not once a
    layer.  ``interpret`` is in the key because jit keeps the trace: one
    made for the chip must not answer a later interpreted call."""
    @jax.custom_vjp
    def f(qkv):
        o, _ = _mhap_fwd(qkv, H, D, causal, tiles, interpret)
        return o

    def fwd(qkv):
        o, lse = _mhap_fwd(qkv, H, D, causal, tiles, interpret)
        return o, (qkv, o, lse)

    def bwd(res, do):
        qkv, o, lse = res
        return (_mhap_bwd(qkv, o, lse, do, H, D, causal, tiles, interpret),)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def flash_mha_packed(qkv, num_heads, causal=False):
    """Fused-QKV flash attention: qkv (B, T, 3·H·D) — the raw output of
    the fused projection matmul, laid out [q | k | v] with each head on
    its own D-lane span — → (B, T, H·D).  Differentiable; the qkv
    cotangent comes back packed the same way.  The tiles are the
    kernels' own choice from (T, H·D, D): ``_mhap_tiles``."""
    B, T, HD3 = qkv.shape
    if HD3 % (3 * num_heads):
        raise ValueError(f"qkv last dim {HD3} not 3*H*D for H={num_heads}")
    D = HD3 // (3 * num_heads)
    HD = HD3 // 3
    bq, bk, sub, lanes = tiles = tuple(
        int(x) for x in _mhap_tiles(T, HD, D))
    if max(bq, bk) % min(bq, bk) or math.gcd(bq, bk) % sub or sub % 8 \
            or HD % lanes or lanes % D or (lanes % 128 and lanes != HD):
        raise ValueError(
            f"packed flash tiles {tiles}: one of block_q / block_k must "
            f"divide the other, sub both, and lanes be whole heads of "
            f"{D} in whole lane tiles of {HD}")
    _mhap_record(T, tiles, causal)
    return _flash_mha_packed_fn(int(num_heads), int(D), bool(causal),
                                tiles, _interpret())(qkv)


def _mhap_record(T, tiles, causal):
    """The schedule a build chose, on ``/metrics`` (every trace of a
    program that holds the kernels passes here; the jitted bodies are
    traced once)."""
    from .. import profiler

    bq, bk, sub, lanes = tiles
    done, needed = _mhap_scores(T, bq, bk, sub, causal)
    profiler.set_gauge("flash.tile_q", bq)
    profiler.set_gauge("flash.tile_k", bk)
    profiler.set_gauge("flash.subtile", sub)
    profiler.set_gauge("flash.head_group_lanes", lanes)
    profiler.set_gauge("flash.scores_computed_over_needed", done / needed)


def _mhap_specs(lanes, HD, order):
    """BlockSpec makers of a packed kernel's operands over the grid
    (row, head group, *``order``): ``part`` 0 / 1 / 2 is the q / k / v
    third of the packed dim (0 too for an operand H·D wide), ``axis``
    whether the block follows the grid's q tiles or its k tiles."""
    n = HD // lanes
    at = {name: i for i, name in enumerate(order)}

    def spec(block, axis, part=0):
        return _vmem_spec(
            (1, block, lanes),
            lambda b, g, *ij: (b, ij[at[axis]], part * n + g))
    return n, spec


def _mhap_fwd(qkv, H, D, causal, tiles, interpret):
    B, T, _ = qkv.shape
    HD = H * D
    scale = 1.0 / float(D) ** 0.5
    bq, bk, sub, lanes = tiles
    qkvf = _pad_to(qkv, 1, max(bq, bk))
    Tp = qkvf.shape[1]
    nq, nk = Tp // bq, Tp // bk
    n, spec = _mhap_specs(lanes, HD, "qk")
    kern = functools.partial(
        _mhap_fwd_kernel, H=lanes // D, D=D, causal=causal, block_q=bq,
        block_k=bk, sub=sub, t_valid=T, scale=scale, nq=nq, nk=nk)
    o, lse = pl.pallas_call(
        kern,
        grid=(B, n, nq, nk),
        in_specs=[spec(bq, "q", 0), spec(bk, "k", 1), spec(bk, "k", 2)],
        out_specs=[spec(bq, "q"), spec(bq, "q")],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, HD), qkv.dtype),
                   jax.ShapeDtypeStruct((B, Tp, HD), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, lanes), jnp.float32)] * 3,
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary",
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_fwd_packed",
    )(qkvf, qkvf, qkvf)
    return o[:, :T], lse[:, :T]


def _mhap_bwd(qkv, o, lse, do, H, D, causal, tiles, interpret):
    B, T, _ = qkv.shape
    HD = H * D
    scale = 1.0 / float(D) ** 0.5
    bq, bk, sub, lanes = tiles
    pad = max(bq, bk)
    qkvf = _pad_to(qkv, 1, pad)
    dof = _pad_to(do.astype(qkv.dtype), 1, pad)
    of = _pad_to(o, 1, pad)  # Δ = rowsum(do∘o) computed inside the kernels
    lsef = _pad_to(lse, 1, pad)
    Tp = qkvf.shape[1]
    nq, nk = Tp // bq, Tp // bk
    kw = dict(H=lanes // D, D=D, causal=causal, block_q=bq, block_k=bk,
              sub=sub, t_valid=T, scale=scale, nq=nq, nk=nk)
    cparams = _compiler_params("parallel", "parallel", "parallel",
                               "arbitrary", vmem_limit_bytes=_VMEM_LIMIT)

    def operands(spec):
        return [spec(bq, "q", 0), spec(bk, "k", 1), spec(bk, "k", 2),
                spec(bq, "q"), spec(bq, "q"), spec(bq, "q")]

    n, spec = _mhap_specs(lanes, HD, "qk")
    dq = pl.pallas_call(
        functools.partial(_mhap_bwd_dq_kernel, **kw),
        grid=(B, n, nq, nk),
        in_specs=operands(spec),
        out_specs=[spec(bq, "q")],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, HD), qkv.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, lanes), jnp.float32)] * 2,
        compiler_params=cparams,
        interpret=interpret,
        name="flash_transpose_dq_packed",
    )(qkvf, qkvf, qkvf, dof, lsef, of)[0]

    n, spec = _mhap_specs(lanes, HD, "kq")
    dk, dv = pl.pallas_call(
        functools.partial(_mhap_bwd_dkv_kernel, **kw),
        grid=(B, n, nk, nq),
        in_specs=operands(spec),
        out_specs=[spec(bk, "k"), spec(bk, "k")],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, HD), qkv.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, lanes), jnp.float32),
                        pltpu.VMEM((bk, lanes), jnp.float32),
                        pltpu.VMEM((bq, lanes), jnp.float32)],
        compiler_params=cparams,
        interpret=interpret,
        name="flash_transpose_dkv_packed",
    )(qkvf, qkvf, qkvf, dof, lsef, of)

    return jnp.concatenate([dq[:, :T], dk[:, :T], dv[:, :T]], axis=-1)


# ---------------------------------------------------------------------------
# Paged attention: W query positions per stream attending a KV cache
# scattered over fixed-size pages, gathered chunk-by-chunk INTO VMEM
# through a scalar-prefetched block table (the PagedAttention pattern,
# Kwon et al. SOSP '23).  The gathered cache never materializes in
# HBM — HBM traffic per step is exactly the pages a stream actually
# holds.
#
# The pools are LANE-DENSE, (P, KVB, H·D): a head is a D-lane span of
# a page's rows, as in the fused QKV projection the rows were cut
# from.  D = 64 is half a lane tile, so a (…, H, D) pool has no tiled
# layout without padding (20 heads x 64 pad to 32 x 128, 3.2 x the
# bytes); the backend then holds it pages-minor and every program that
# scatters into it or hands it to this kernel re-lays-out the whole
# pool on the way in and again on the way out.  At (P, KVB, H·D) a
# bf16 page of 16 rows x 1280 lanes is ten whole tiles, the scatter
# runs in place and the kernel takes the pool as it is.
#
# ONE kernel family serves the decode step (W = 1), the quantized-
# cache decode step (W = 1 plus per-slot scales dequantized in VMEM),
# the speculative-verify window (W = 1 + k) and grouped queries (Hq
# query heads over Hkv KV heads).  Grid (B,): a grid step is a ROW, and
# inside it a loop walks the row's keys a CHUNK of C = K pages x KVB at
# a time — only the cdiv(start[b] + W, C) chunks a window position can
# see, so a page past the live length costs no copy and no step.  The
# pools stay in HBM; the K pages of a chunk are scattered there, so
# they arrive as K copies of K and K of V (page ids from the prefetched
# table) into one (K, KVB, H·D) buffer each, and a second buffer takes
# chunk c + 1 — or the next row's first chunk — while chunk c is
# multiplied.  A page of the last chunk that lies past the live length
# is not copied: its rows of the buffer keep what an earlier chunk left
# there (the buffers are zeroed once, so that is never a NaN), and the
# DIAGONAL mask k_pos < start[b] + 1 + w gives those keys a probability
# of exactly 0.  Row w under that mask reproduces the mask and the chain
# of merges of a single-query decode at length start[b] + 1 + w: a
# chunk fully masked for a row is an exact no-op of that row's state
# (alpha == 1, p == 0).
#
# One online-softmax merge a chunk.  Heads are contracted WITHOUT
# reshaping the page rows: at the start of a row the W query rows are
# spread over HP = H rounded up to a sublane tile rows each, row (w, h)
# holding q[w] on head h's lane span and exact zeros elsewhere.  One
# (W·HP, H·D) x (C, H·D)^T matmul then gives every head's scores (the
# zeros add nothing to the float32 accumulation) as a (W·HP, C) tile —
# lane-dense at C >= 128 — and one (W·HP, C) x (C, H·D) matmul every
# head's P·V on its own span of the row; the finish keeps row (w, h)'s
# span h and sums the rows of a w.  The MXU multiplies H times the
# needed products, but what a matmul with so few rows costs is loading
# its weight tiles, and K and V are the weights: each of their
# 128 x 128 tiles is loaded once a chunk in this form and once in a
# loop over heads (twice there at D = 64, a head being half a tile), so
# the all-heads form is the one with the fewest tile loads at every
# shape, and with two matmuls and a dozen whole-tile vector ops a chunk
# it is the one with the fewest instructions.  What it costs is VMEM,
# W·HP rows of H·D lanes three times over; that is what the chunk gives
# way to and, past the budget, what is refused by name.
# ---------------------------------------------------------------------------

# Keys a chunk, where VMEM allows.  Read on a v5e, a layer's kernel over
# the serving cells' rows (48 x ~760 keys of 20 x 64; 128 x ~1,500 of
# 64 / 8 x 128): 0.349 / 1.55 ms at 128 keys, 0.297 / 1.25 at 256,
# 0.289 / 1.15 at 512, beside 0.276 / 1.09 for the copies alone and a
# need of 0.228 / 0.966 (PERF.md §6, PR 30).  At 128 the merge is not
# yet hidden under the copies; from 256 on the copies bind, and a larger
# chunk buys its last few percent with twice the VMEM and with keys
# multiplied past the live length.
_PAGED_CHUNK_KEYS = 256


def paged_enabled(lanes) -> bool:
    """Use the paged kernel over pools whose page rows are ``lanes``
    wide?  Compiled for the chip it copies a page's rows by hand, and
    Mosaic slices an HBM array in whole lane tiles only: a width that
    is no multiple of 128 (a toy model, 5 heads x 64 of a tp shard)
    takes the callers' lax body there.  Interpreted, any width goes."""
    return enabled() and (_interpret() or lanes % 128 == 0)


def _paged_pages_per_chunk(w, heads, kv_heads, d, kvb, table_pages, q_bytes,
                           kv_bytes, quant=False):
    """(HP, K, bytes of VMEM): the rows a window position's heads are
    spread over, the pages a chunk holds and what the kernel then keeps
    in VMEM — the ONE place K is derived.

    The spread query is ``rows`` x ``lanes`` = W·HP x Hkv·D (HP: the
    heads in whole sublane tiles of a 16-bit q).  It is held three times over whatever the
    chunk: itself, the float32 accumulator and the P·V product (a
    fourth, q in float32, over quantized pools).  A chunk of C = K·KVB keys adds two buffers each
    of K and V pages and the (rows, C) scores, probabilities and mask;
    over quantized pools the two float32 dequantized chunks too, and
    the scales of the row's table in whole chunks, K's and V's, twice
    each and a lane tile wide.  K is the most pages up to
    ``_PAGED_CHUNK_KEYS`` keys (and the row's table) that keeps the sum
    inside ``_PAGED_VMEM_BUDGET``, halved until it does; a kernel that
    is over at K = 1 is the caller's to refuse.

    The widest the benchmark runs (longdoc: 48 query heads over 8 KV
    heads of 128, 24 rows over 2,080-page tables): HP 48, K 16, 2.74 MB
    of VMEM — 0.49 MB of spread query, accumulator and product, 2.10 MB
    of page buffers, 0.15 MB of scores — and the whole (24, 2080) int32
    table as ONE scalar-prefetch operand, 200 KB of SMEM, which the
    chip's compiler takes (``tests/test_tpu_compile.py``)."""
    hp = -(-heads // 16) * 16
    rows, lanes = w * hp, kv_heads * d
    fixed = rows * lanes * (q_bytes + 4 + 4 + 4 * quant)

    def need(pages):
        keys = pages * kvb
        chunk = 2 * 2 * keys * lanes * kv_bytes
        if quant:
            chunk += 2 * keys * lanes * 4
            chunk += 2 * 2 * -(-table_pages // pages) * keys * 128 * 4
        return fixed + chunk + 3 * rows * max(keys, 128) * 4

    pages = max(1, min(_PAGED_CHUNK_KEYS // kvb, table_pages))
    while pages > 1 and need(pages) > _PAGED_VMEM_BUDGET:
        pages //= 2
    return hp, pages, need(pages)


def _paged_kernel(table_ref, start_ref, q_ref, k_hbm, v_hbm, *rest, scale,
                  kvb, pages, mb, w, h, d, hp, quant, g=1, window=0):
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, k_buf, v_buf, sem, turn_scr, qx_scr, acc_scr, m_scr, l_scr = rest
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    hd = h * d
    keys = pages * kvb

    def head_span(rows):
        # (rows, H·D) bool: lane belongs to the row's head (rows >= H,
        # HP's padding up to a sublane tile, select nothing)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
        return (lane >= row * d) & (lane < (row + 1) * d)

    def group_span(rows):
        # grouped queries (g > 1): row r is query head r % hp, on the
        # lane span of KV head (r % hp) // g
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
        head = row if w == 1 else row % hp
        kv = sum((head >= i * g).astype(jnp.int32) for i in range(1, h))
        return (lane >= kv * d) & (lane < (kv + 1) * d)

    def live(row):
        # keys some window position of the row can see
        return jnp.clip(start_ref[row] + w, 0, mb * kvb)

    def lowest(row):
        # the lowest key the row's query sees: 0, or under a sliding
        # window (w = 1) the query's own position less window - 1
        if not window:
            return 0
        return jnp.maximum(start_ref[row] + 1 - window, 0)

    def first_chunk(row):
        # the walk starts at the chunk that holds that key: the pages
        # behind it are never touched (their table entries may be the
        # scratch page: the engine gives such pages back)
        return lowest(row) // keys

    def copies(row, chunk, slot, op):
        # the chunk's live pages, a copy of K and one of V a page, page
        # i into span i of the slot's buffers.  ``op`` is "start" or
        # "wait": the same pages, by the same count, whichever it is.
        # A loop the compiler keeps rolled: this body is traced three
        # times a kernel, not three times a page
        first = chunk * pages

        def page(i, _):
            pid = table_ref[row, first + i]
            for j, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(
                    pool.at[pid], buf.at[slot, i], sem.at[j, slot]), op)()

        jax.lax.fori_loop(
            jnp.clip(lowest(row) // kvb - first, 0, pages),
            jnp.clip(pl.cdiv(live(row), kvb) - first, 0, pages), page,
            None)

    @pl.when(b == 0)
    def _first_row():
        turn_scr[0] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    # chunks walked before this row: chunk c of the row is in buffer
    # (turn + c) % 2.  Who starts whose copies: a row its chunk c + 1
    # under chunk c and, under its last chunk, the NEXT row's first —
    # which a row with no chunk of its own starts at once, and row 0
    # starts for itself
    turn = turn_scr[0]
    c0 = first_chunk(b)
    n = pl.cdiv(live(b), keys) - c0
    after = jnp.minimum(b + 1, nb - 1)

    @pl.when((b == 0) | ((n == 0) & (b + 1 < nb)))
    def _first_chunk():
        row = jnp.where(n == 0, after, b)
        copies(row, first_chunk(row), turn % 2, "start")

    if g > 1:
        # q arrives as (W·Hq, D) rows, one query head each: every
        # row is repeated over the KV spans and kept on its own
        q = q_ref[0].astype(jnp.float32)
        qx_scr[...] = jnp.where(
            group_span(w * hp), jnp.concatenate([q] * h, axis=1),
            0.0).astype(qx_scr.dtype)
    else:
        span = head_span(hp)
        q = q_ref[0].astype(jnp.float32)              # (W, H·D)
        for i in range(w):
            qx_scr[i * hp:(i + 1) * hp, :] = jnp.where(
                span, q[i:i + 1, :], 0.0).astype(qx_scr.dtype)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)

    def chunk_step(i, _):
        slot = (turn + i) % 2
        more = i + 1 < n
        c = c0 + i                  # the chunk's place in the row's table

        @pl.when(more | (b + 1 < nb))
        def _ahead():
            copies(jnp.where(more, b, after),
                   jnp.where(more, c + 1, first_chunk(after)),
                   1 - slot, "start")

        copies(b, c, slot, "wait")
        qx = qx_scr[...]                              # (W·HP, H·D)
        k = k_buf[slot]                               # (K, KVB, H·D)
        v = v_buf[slot]
        if quant:
            # pages arrive as int8/fp8 and are dequantized to float32
            # right after the DMA — the narrow dtype is what crosses
            # HBM — by the chunk's rows of the row's (MB·KVB, H)
            # float32 scales.  A scale reaches its head's D lanes
            # through an exact 0/1 matmul (one non-zero term a lane),
            # and from here on every operand is float32: q, the values,
            # the probabilities.  The scales came for the whole table:
            # past the live length they are whatever the table's
            # padding points at, and count as 0
            lanes = head_span(h).astype(jnp.float32)      # (H, H·D)
            at = pl.ds(pl.multiple_of(c * keys, keys), keys)
            seen = c * keys + jax.lax.broadcasted_iota(
                jnp.int32, (keys, h), 0) < live(b)

            def dequant(x, scale_ref):
                return (x.astype(jnp.float32).reshape(keys, hd)
                        * jax.lax.dot_general(
                            jnp.where(seen, scale_ref[0, at, :], 0.0), lanes,
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32))

            k = dequant(k, ks_ref)
            v = dequant(v, vs_ref)
            qx = qx.astype(jnp.float32)
        else:
            k = k.reshape(keys, hd)
            v = v.reshape(keys, hd)
        # s[(w, h), t] = q[w, span h] . k[t, span h]
        s = jax.lax.dot_general(
            qx, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = c * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        win = sum((row >= i * hp).astype(jnp.int32) for i in range(1, w))
        valid = k_pos < start_ref[b] + 1 + win
        if window:
            valid &= k_pos >= lowest(b)
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_scr[:, :1]                         # (W·HP, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        # pv[(w, h), :] = sum_t p[(w, h), t] * v[t, :] — right on span h
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    jax.lax.fori_loop(0, n, chunk_step, None)
    turn_scr[0] = turn + n

    out = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
    if g > 1:
        # row r keeps the D lanes of its KV span
        keep = jnp.where(group_span(w * hp), out, 0.0)
        o_ref[0] = sum(keep[:, i * d:(i + 1) * d]
                       for i in range(h)).astype(o_ref.dtype)
    else:
        span = head_span(hp)
        rows = [jnp.sum(jnp.where(span, out[i * hp:(i + 1) * hp, :], 0.0),
                        axis=0, keepdims=True) for i in range(w)]
        out = jnp.concatenate(rows, axis=0) if w > 1 else rows[0]
        o_ref[0] = out.astype(o_ref.dtype)            # (W, H·D)


def _paged_attention(q, k_pool, v_pool, scales, block_table, start,
                     num_heads, kv_heads=None, window=0):
    """q (B, W, H·D) at absolute positions ``start[b] + i``; pools
    (P, KVB, H·D); scales is () or (k_scale, v_scale), each
    (P, KVB, H) float32.  ``kv_heads`` < ``num_heads``: grouped
    queries, pools (P, KVB, kv_heads·D), query head i on KV head
    ``i // (num_heads // kv_heads)``: the same walk, chunks and
    online-softmax state, the spread query a row per QUERY head on its
    KV head's span of the (kv_heads·D)-lane page rows, so one matmul a
    chunk still gives every head's scores (query heads that fill no
    whole sublane tiles are padded to them with rows of zeros, which
    are dropped on the way out).  ``window`` > 0 (W = 1, unquantized):
    the query sees its own key and the ``window - 1`` before it; the
    walk starts at the chunk that holds the lowest of them."""
    B, W, HD = q.shape
    Hq = int(num_heads)
    Hkv = Hq if kv_heads is None else int(kv_heads)
    D = HD // Hq
    KD = Hkv * D
    KVB = k_pool.shape[1]
    MB = block_table.shape[1]
    quant = bool(scales)
    if Hkv == Hq:
        what = f"{Hq} heads x {D}"
        q_block = (1, W, HD)
    else:
        what = f"{Hq} query heads over {Hkv} KV heads x {D}"
        if quant or Hq % Hkv or k_pool.shape[2] != KD:
            raise MXNetError(
                f"paged_attention: {what} wants unquantized (P, KVB, "
                f"{KD}) pools and {Hkv} | {Hq}; got pools "
                f"{tuple(k_pool.shape)}, scales {len(scales)}")
        # q enters and leaves as (B, W·HP, D) rows, HP the query heads
        # in whole sublane tiles — at Hq % 16 == 0 the (B, W, Hq·D)
        # activation seen through a free reshape
        q_block = (1, W * (-(-Hq // 16) * 16), D)
    if window and (W != 1 or quant):
        raise MXNetError(
            f"paged_attention: a sliding window of {window} keys is built "
            f"for the one-query decode step over unquantized pools; got "
            f"a {W}-row window, scales {len(scales)}")
    if KD % 128 and not _interpret():
        raise MXNetError(
            f"paged_attention: {what} are page rows of {KD} lanes, and the "
            f"kernel copies page rows in whole lane tiles (a multiple of "
            f"128); ops.attention's lax body serves such a width "
            f"(pallas_kernels.paged_enabled)")
    # The all-heads form's VMEM grows as W·H²·D: 0.4 MB of spread
    # query, accumulator and product at the benchmark's 20 x 64, W = 1,
    # beside 2.6 MB of page buffers at 256 keys a chunk (3.1 MB in
    # all; 2.9 MB at 64 / 8 x 128); every GPT-2 size at W <= 8 stays
    # under 9 MB.  It does NOT fit every head count — 64 heads x 128 at
    # W = 5 would want 26 MB of the 16 MB a kernel is given — and is
    # refused here by name rather than by a Mosaic allocation error.  A
    # model that wide needs its heads sharded over tp, or fewer window
    # rows.
    HP, pages, vmem = _paged_pages_per_chunk(
        W, Hq, Hkv, D, KVB, MB, q.dtype.itemsize, k_pool.dtype.itemsize,
        quant)
    rows = W * HP
    if vmem > _PAGED_VMEM_BUDGET:
        raise MXNetError(
            f"paged_attention: {what} with a {W}-row window needs about "
            f"{vmem >> 20} MB of VMEM in the all-heads form ({rows} rows "
            f"of {KD} lanes, three times over, beside one page of {KVB} "
            f"keys a chunk), more than the {_PAGED_VMEM_BUDGET >> 20} MB "
            f"it may count on; fewer window rows fit, or heads sharded "
            f"over tp")
    kern = functools.partial(_paged_kernel, scale=1.0 / float(D) ** 0.5,
                             kvb=KVB, pages=pages, mb=MB, w=W, h=Hkv, d=D,
                             hp=HP, quant=quant, g=Hq // Hkv,
                             window=int(window))

    def rows_spec():
        return _vmem_spec(q_block, lambda b, tr, sr: (b, 0, 0))

    table = block_table.astype(jnp.int32)
    rows_in = q.reshape((B,) + q_block[1:]) if Hkv == Hq or HP == Hq else \
        jnp.pad(q.reshape(B, W, Hq, D),
                ((0, 0), (0, 0), (0, HP - Hq), (0, 0))).reshape(B, rows, D)
    if quant:
        # a (KVB, H) page of scales has no lane-aligned slice to copy
        # by hand, so the scales of a row's table are gathered out here
        # and arrive with the row; the table is padded (scratch page)
        # to whole chunks, so that every chunk has its rows of them
        whole = jnp.pad(table, ((0, 0), (0, -MB % pages)))
        scales = tuple(s[whole].reshape(B, -1, Hkv) for s in scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[rows_spec(), pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)] + [
            _vmem_spec((1,) + s.shape[1:], lambda b, tr, sr: (b, 0, 0))
            for s in scales],
        out_specs=rows_spec(),
        scratch_shapes=[
            pltpu.VMEM((2, pages) + k_pool.shape[1:], k_pool.dtype),
            pltpu.VMEM((2, pages) + v_pool.shape[1:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, KD), q.dtype),
            pltpu.VMEM((rows, KD), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32)],
    )
    # a windowed walk is sized by the window, not the context: the
    # readers of the full walk must not take it for one
    kernel_name = "paged_window" if window else \
        "paged_attention_q" if scales else "paged_attention"
    # rows in order ("arbitrary"): a row starts the next row's first
    # copies and hands on which buffer they went to
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + q_block[1:], q.dtype),
        compiler_params=_compiler_params("arbitrary"),
        interpret=_interpret(),
        name=kernel_name,
    )(table, start.astype(jnp.int32), rows_in, k_pool, v_pool, *scales)
    if Hkv != Hq and HP != Hq:
        out = out.reshape(B, W, HP, D)[:, :, :Hq]
    return out.reshape(B, W, HD)


def paged_attention_decode(q, k_pool, v_pool, block_table, lengths,
                           num_heads):
    """q (B, H·D) at position lengths-1; k_pool/v_pool (P, KVB, H·D);
    block_table (B, MB) int32 page ids (page 0 = scratch); lengths (B,)
    int32 counting the current token -> (B, H·D) in q.dtype.

    The W = 1 case of the paged kernel.  H here is whatever the caller
    holds — under the serving mesh's shard_map it is the LOCAL head
    count H/tp with pools sliced on their lane dim (whole heads), and
    the kernel is head-wise independent, so the grid/DMA structure (and
    per-step VMEM footprint) just shrinks with the shard."""
    return _paged_attention(q[:, None], k_pool, v_pool, (), block_table,
                            lengths - 1, num_heads)[:, 0]


def paged_attention_decode_quant(q, k_pool, v_pool, k_scale, v_scale,
                                 block_table, lengths, num_heads):
    """Quantized-cache paged decode: like :func:`paged_attention_decode`
    but k_pool/v_pool hold int8 (or fp8) values and
    k_scale/v_scale (P, KVB, H) float32 hold the per-slot-per-head
    dequantization scales, applied in kernel after each page's DMA.
    Softmax statistics and the P·V accumulation stay float32."""
    return _paged_attention(q[:, None], k_pool, v_pool, (k_scale, v_scale),
                            block_table, lengths - 1, num_heads)[:, 0]


def paged_attention_verify(q, k_pool, v_pool, block_table, start,
                           num_heads):
    """q (B, W, H·D): the verify window's queries at absolute
    positions ``start[b] + i`` (window K/V already in the pools);
    k_pool/v_pool (P, KVB, H·D); block_table (B, MB) int32 page ids
    (page 0 = scratch); start (B,) int32 tokens cached BEFORE the
    window -> (B, W, H·D) in q.dtype, row i the single-query decode
    at length ``start[b] + i + 1``."""
    return _paged_attention(q, k_pool, v_pool, (), block_table, start,
                            num_heads)


# ---------------------------------------------------------------------------
# kv_pages_write: a prompt's K/V rows into the pools, a page a copy
# ---------------------------------------------------------------------------

# Page copies in flight, K's and V's each (never more than the call
# has).  A copy goes straight from the rows to the pool, wherever XLA
# keeps the rows (a page is one whole tile row of both arrays: no
# buffer of the kernel's own between them); a few in flight hide a
# copy's latency, and the rest of the loop only issues.
_KV_WRITE_DEPTH = 8


def _pages_kernel(pages_ref, *refs, n, kvb, blocks, depth):
    # refs: n arrays of rows, the n pools (aliased to the outputs:
    # written through those), the n outputs, the semaphores
    srcs, outs, sem = refs[:n], refs[2 * n:3 * n], refs[3 * n]
    total = pages_ref.shape[0] * blocks

    def copies(i, op):
        # block i of the call: rows j·KVB .. of row b to their page.
        # The scratch page is nobody's: a block that would go there
        # (padding past the length, a block behind a window) is not
        # copied at all
        b, j = i // blocks, i % blocks
        page = pages_ref[b, j]

        @pl.when(page > 0)
        def _():
            rows = pl.ds(pl.multiple_of(j * kvb, kvb), kvb)
            for at, (src, dst) in enumerate(zip(srcs, outs)):
                getattr(pltpu.make_async_copy(
                    src.at[b, rows], dst.at[page], sem.at[at]), op)()

    def issue(i, _):
        @pl.when(i >= depth)
        def _():
            copies(i - depth, "wait")

        copies(i, "start")

    jax.lax.fori_loop(0, total, issue, None)
    jax.lax.fori_loop(total - depth, total,
                      lambda i, _: copies(i, "wait"), None)


@functools.lru_cache(maxsize=None)
def _pages_write_fn(n, name, interpret):
    """The call for ``n`` pools under the kernel name ``name``, jitted:
    a model's layers make it with one shape, so the kernel is traced and
    lowered once a program, not once a layer (``_flash_mha_packed_fn``
    says why ``interpret`` is in the key)."""
    def write(pages, *rows_and_pools):
        pools = rows_and_pools[n:]
        blocks = pages.shape[1]
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[hbm] * (2 * n), out_specs=[hbm] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))])
        return pl.pallas_call(
            functools.partial(
                _pages_kernel, n=n, kvb=pools[0].shape[1], blocks=blocks,
                depth=min(_KV_WRITE_DEPTH, pages.shape[0] * blocks)),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in pools],
            input_output_aliases={1 + n + i: i for i in range(n)},
            compiler_params=_compiler_params("arbitrary"),
            interpret=interpret,
            name=name,
        )(pages, *rows_and_pools)

    return jax.jit(write)


def _pages_write(name, rows, pools, pages):
    """``rows[i]`` (B, T, W) into ``pools[i]`` (P, KVB, W) a page a
    copy, every pool in ONE call named ``name``."""
    B, T, _ = rows[0].shape
    kvb = pools[0].shape[1]
    if T % kvb or pages.shape != (B, T // kvb):
        raise MXNetError(
            f"{name}: rows {tuple(rows[0].shape)} want whole pages of "
            f"{kvb} and a (B, T / {kvb}) table; got pages "
            f"{tuple(pages.shape)}")
    return _pages_write_fn(len(pools), name, _interpret())(
        pages.astype(jnp.int32),
        *(r.astype(p.dtype) for r, p in zip(rows, pools)), *pools)


def kv_pages_write(k, v, k_pool, v_pool, pages):
    """k, v (B, T, W) rows; pools (P, KVB, W), T a multiple of KVB;
    pages (B, T / KVB) int32 -> the pools with block j of row b (rows
    ``j·KVB .. (j+1)·KVB - 1``, one whole page) written to page
    ``pages[b, j]``, K's and V's in ONE call.  A block whose page is 0,
    the scratch page, is skipped.  The pools are aliased to the
    outputs: donated under jit, the write is in place.

    Each page is one copy of a whole (KVB, W) tile row from the rows to
    the pool, a few in flight: as an XLA scatter the same write is a
    loop of B·T row updates (0.147 ms for 1,024 rows of 2.5 KB on the
    chip, 18 GB/s: PERF.md section 6, PR 37).
    ``pallas_hybrid.slot_rows_write`` is the sibling that writes one
    row a STREAM into a slot pool.

    The name holds no ``paged_attention``: the benchmark's readers book
    every kernel so named to the decode step's attention."""
    return tuple(_pages_write("kv_pages_write", (k, v), (k_pool, v_pool),
                              pages))


# ---------------------------------------------------------------------------
# Multi-head latent attention (ops/hybrid.py MLAPrefillAttention /
# MLAPagedDecode): a prefill kernel with two head widths and one shared
# positional key that walks the grouped-query prompt kernel's band
# schedule in tiles of its own (`_mla_tiles`), a paged decode kernel
# over ONE pool of latent rows in which the value is a lane span of the
# key, and the one-pool page write.
#
# The names hold none of ``paged_attention``, ``paged_window``,
# ``flash_fwd_mha``, ``flash_fwd_window``, ``kv_pages_write``: the
# benchmark's accepted readers book a kernel to a metric by SUBSTRING.
# ---------------------------------------------------------------------------

def _mla_heads_per_step(heads, rope_dim):
    """Heads a grid step of the prefill kernel takes: their rotary query
    lanes (``hb·rope_dim``) must be whole lane tiles when compiled — 4 at
    the published 64 — with a head's rotary lanes a whole share of a
    lane tile or whole tiles, and the ONE rotary key tile is then
    fetched once for the group, not once a head."""
    if rope_dim and 128 % rope_dim and rope_dim % 128:
        return 0
    for hb in (4, 2, 8, 16):
        if heads % hb == 0 and (hb * rope_dim) % 128 == 0:
            return hb
    return 0


def mla_flash_enabled(heads, nope_dim, rope_dim, v_dim) -> bool:
    """Use ``mla_flash``?  Compiled, a head's spans must be whole lane
    tiles (nope_dim, v_dim multiples of 128; the rotary lanes of a
    step's heads: ``_mla_heads_per_step``); interpreted, any width."""
    if not enabled():
        return False
    return _interpret() or (
        nope_dim % 128 == 0 and v_dim % 128 == 0
        and _mla_heads_per_step(heads, rope_dim) > 0)


def _mla_tiles(t, heads, nope, rope, v):
    """(block_q, block_k, sub, inner, heads_per_step) of ``mla_flash``
    for a prompt bucket of ``t`` rows: the tile a grid step holds, the
    sub-block the diagonal's tile is walked in, the rows of a tile that
    are updated at a time (and left out where they hold only the last
    live tile's padding), and the heads a grid step takes.  From the
    shapes alone, each choice a row of the kernel-alone sweep in PERF.md
    section 6, PR 47 (``tools/verify_kernels.py --mla-tiles`` prints it
    again, patching this chooser).  The walk's four follow from the
    bucket (``_prompt_schedule`` asks with no widths) and are
    ``_mha_window_tiles``': the key tile's width is what counts (512 ->
    1,024 -> 2,048 keys: -37%, -12% a call at 8,192 rows), a query tile
    of 2,048 rows reads 1-9% faster still and compiles twice as long,
    sub-blocks of 512 read the same as 256.  The heads from their count
    and rotary width (``_mla_heads_per_step``: 2, 4 and 8 read within
    1%; interpreted, any width: what divides)."""
    return _mha_window_tiles(t, 0) + (
        _mla_heads_per_step(heads, rope) or math.gcd(heads, 4),)


def _mla_flash_kernel(len_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, hb, per, n, r, dv, block_q,
                      block_k, sub, inner, scale, edges, interior):
    qi = pl.program_id(2)
    kj = pl.program_id(3)           # the walk starts at key tile 0
    length = len_ref[pl.program_id(0)]
    live = qi * block_q < length
    # a live tile's walk ends on the diagonal's tile, never past the
    # tile that holds row length - 1
    last = _diagonal_tile(qi, block_q, block_k)
    walked = live & (kj <= last)
    off = kj * block_k - qi * block_q   # first column less first row

    @pl.when(jnp.logical_not(live) & (kj == 0))
    def _dead():
        # only the bucket's padding: zeros, never what the buffer held
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(live & (kj == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def heads(updates):
        """``_band_update`` of each of the step's heads (a rolled loop:
        the bodies are traced and compiled once, not once a head) for
        ``updates`` = [(rows, pieces)] — but the blocks of ``inner`` rows
        that hold only the last live tile's padding, which are left out:
        their state stays empty and their rows come out 0."""
        @functools.partial(jax.lax.fori_loop, 0, hb, init_val=None)
        def _head(i, _):
            def lanes(j, width):
                return pl.ds(pl.multiple_of(j * width, width), width)

            # the rotary lanes of `per` heads share a lane tile, which
            # no dynamic slice may start inside: the tile is taken whole
            # against the rotary key beside zeros (`_mla_flash`) — one
            # product of n + per·r lanes gives q_n . k_n + q_r . k_r
            tile = lanes(_tile_of(i, per), per * r)
            spot = lanes(i & (per - 1), per * r)
            for blk, pieces in updates:
                def _rows(blk=blk, pieces=pieces):
                    q = jnp.concatenate(
                        [qn_ref[0, blk, lanes(i, n)], qr_ref[0, blk, tile]],
                        axis=1)
                    _band_update(
                        lambda cols: _dot(q, jnp.concatenate(
                            [kn_ref[0, cols, lanes(i, n)],
                             kr_ref[0, cols, spot]], axis=1), 1, 1)
                        * (scale * _LOG2E),
                        lambda cols: v_ref[0, cols, lanes(i, dv)],
                        acc_ref.at[i], m_ref.at[i], l_ref.at[i], blk, pieces)

                pl.when(qi * block_q + blk.start // inner * inner
                        < length)(_rows)

    if interior:    # wholly below the diagonal of a live tile, so below
        # the length too: no mask, no guard, every key seen
        @pl.when(walked & (off <= -block_k))
        def _interior():
            heads([(slice(r0, r0 + inner), [(slice(0, block_k), None)])
                   for r0 in range(0, block_q, inner)])

    # the diagonal crosses the tile: walked in sub-blocks, only what a
    # row can see computed, only the blocks the diagonal cuts masked (a
    # key a prompt's row sees is below the length; the rows past it are
    # zeroed at the end).  One body an offset this grid can reach
    for o in edges:
        def _edge(o=o):
            walk = _band_walk(o, block_q, block_k, sub)
            masks = {cut: _band_mask(sub, *cut) for _, pieces in walk
                     for _, cut in pieces if cut is not None}
            heads([(blk, [(cols, cut and masks[cut]) for cols, cut in pieces])
                   for blk, pieces in walk])

        pl.when(walked & (off == o))(_edge)

    whole = (qi + 1) * block_q <= length  # no row of the tile is padding

    def finalize(keep):
        for i in range(hb):
            out = acc_ref[i] / jnp.maximum(l_ref[i, :, :1], 1e-30)
            o_ref[0, :, i * dv:(i + 1) * dv] = (
                out if keep is None else jnp.where(keep, out, 0.0)
            ).astype(o_ref.dtype)

    @pl.when(whole & (kj == last))
    def _finalize():
        finalize(None)

    @pl.when(live & jnp.logical_not(whole) & (kj == last))
    def _finalize_last():
        # the prompt ends inside this tile: its padding rows as zeros
        finalize(qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, dv), 0) < length)


def mla_flash(q, q_r, kv, k_r, heads, nope_dim, v_dim, scale, lengths=None):
    """Causal attention of ``heads`` heads with qk width n + r and v
    width dv: q (B, T, H·n + ...), its first H·n lanes every head's
    q_n (what follows is not read: the projection's unrotated q_r);
    q_r (B, T, H·r), rotated; kv (B, T, H·n + H·dv) = [every head's k_n
    | every head's v], the latent's up-projection as it leaves its
    matmul; k_r (B, T, r), rotated — ONE key for all heads, never
    repeated a head -> (B, T, H·dv).  Scores (q_n . k_n + q_r . k_r) x
    ``scale``.  Token-major rows as the projections leave them: a head
    is a lane span (k_n and v are two windows on ONE array, no slice is
    copied out) and a grid step takes a group of heads over one (query
    tile, key tile), the rotary key tile fetched once for the group.

    The schedule is ``flash_mha_window``'s without a window, its tiles
    this kernel's own choice from its shapes (``_mla_tiles``,
    ``_band_schedule``): the grid holds no step above the diagonal; a
    key tile wholly below it runs without a mask, ``inner`` rows at a
    time over the whole tile; the diagonal's tile is walked in
    sub-blocks (``_band_walk``), of which only those a row can see are
    computed and only those the diagonal cuts are masked.  A head's
    scores are ONE product: its rotary query lanes share a lane tile
    with the step's other heads', so the tile is multiplied whole with
    the rotary key beside zeros ([k_r | 0] or [0 | k_r], built here) —
    the 128-deep pass the MXU pays for 64 lanes anyway.
    :func:`prompt_tile_work` (``latent=True``) counts the walk; the
    gauges ``mla_flash.tile_q`` / ``.tile_k`` / ``.subtile`` /
    ``.heads_per_step`` say what was chosen.

    ``lengths`` (B,): each prompt's rows in its bucket of T, as
    :func:`flash_mha_window` takes them — rows at and past a length
    come out 0, a query tile of them alone walks nothing and fetches
    nothing, and of the last live tile the blocks of ``inner`` rows
    past the prompt's are left out."""
    from .. import profiler

    B, T, _ = q.shape
    H = int(heads)
    tiles = _mla_tiles(T, H, int(nope_dim), q_r.shape[2] // H, int(v_dim))
    profiler.set_gauge("mla_flash.tile_q", tiles[0])
    profiler.set_gauge("mla_flash.tile_k", tiles[1])
    profiler.set_gauge("mla_flash.subtile", tiles[2])
    profiler.set_gauge("mla_flash.heads_per_step", tiles[4])
    return _mla_flash(q, q_r, kv, k_r, _prompt_lengths(lengths, B, T),
                      heads=H, nope_dim=int(nope_dim), v_dim=int(v_dim),
                      scale=float(scale), tiles=tiles)


# jitted, as `_flash_mha_window` is: a program's layers share ONE trace
# of the kernel and one lowered function
@functools.partial(jax.jit, static_argnames=("heads", "nope_dim", "v_dim",
                                             "scale", "tiles"))
def _mla_flash(q, q_r, kv, k_r, lens, *, heads, nope_dim, v_dim, scale,
               tiles):
    B, T, _ = q.shape
    H, n, dv = heads, nope_dim, v_dim
    r = q_r.shape[2] // H
    bq, bk, sub, inner, hb = tiles
    v_in = kv
    if (H * n) % (hb * dv):       # v's lanes start inside a block of it
        v_in = kv[..., H * n:]
    v_first = 0 if v_in is not kv else H * n // (hb * dv)
    # `per` heads' rotary lanes a lane tile (a power of two, as `hb`
    # is): the rotary key once a place in the tile, zeros beside it
    per = math.gcd(hb, max(1, 128 // r))
    k_w = (jnp.eye(per, dtype=k_r.dtype)[:, :, None]
           * k_r[:, :, None, None, :]).reshape(B, T, per * per * r)
    qf, qr, kf, kr, vf = (_pad_to(x, 1, max(bq, bk))
                          for x in (q, q_r, kv, k_w, v_in))
    rows = qf.shape[1]
    plan = _band_schedule(rows, bq, bk, sub, 0)
    steps = int((plan.last + 1).max())
    last = functools.partial(_diagonal_tile, block_q=bq, block_k=bk)

    def q_map(b, g, qi, kj, len_ref):
        return (b, jnp.minimum(qi, _last_live_tile(len_ref[b], bq)), g)

    def seen(b, qi, kj, len_ref):
        # past the diagonal's tile, and through a dead query tile, the
        # index stands still: no tile is fetched
        return _walked_key_tile(qi, kj, _last_live_tile(len_ref[b], bq),
                                lambda qi: 0, steps, last)

    kern = functools.partial(
        _mla_flash_kernel, hb=hb, per=per, n=n, r=r, dv=dv, block_q=bq,
        block_k=bk, sub=sub, inner=inner, scale=scale, edges=plan.edges,
        interior=plan.interior)
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb, rows // bq, steps),
            in_specs=[
                _vmem_spec((1, bq, hb * n), q_map),
                _vmem_spec((1, bq, hb * r), q_map),
                _vmem_spec((1, bk, hb * n), lambda b, g, qi, kj, len_ref:
                           (b, seen(b, qi, kj, len_ref), g)),
                _vmem_spec((1, bk, per * per * r),
                           lambda b, g, qi, kj, len_ref:
                           (b, seen(b, qi, kj, len_ref), 0)),
                _vmem_spec((1, bk, hb * dv), lambda b, g, qi, kj, len_ref:
                           (b, seen(b, qi, kj, len_ref), v_first + g)),
            ],
            out_specs=_vmem_spec(
                (1, bq, hb * dv),
                lambda b, g, qi, kj, len_ref: (b, qi, g)),
            scratch_shapes=[pltpu.VMEM((hb, bq, dv), jnp.float32),
                            pltpu.VMEM((hb, bq, 128), jnp.float32),
                            pltpu.VMEM((hb, bq, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, rows, H * dv), kv.dtype),
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel", "arbitrary",
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="mla_flash_fwd",
    )(lens, qf, qr, kf, kr, vf)
    return o[:, :T]


# Keys a chunk of the latent paged kernel.  VMEM at the published widths
# (128 heads, rows of 640 lanes, bfloat16): two buffers of 512 rows, 1.3
# MB; the (128, 512) float32 scores and probabilities, 0.5 MB; q, the
# (128, 512) float32 accumulator and the output block, 0.7 MB: 2.5 MB of
# the 16 a kernel is given.  A chunk is two matmuls, (128 x 640) x
# (640 x 512) and (128 x 512) x (512 x 512): 242 FLOP a byte of cache.
_MLA_PAGED_CHUNK_KEYS = 512


def mla_paged_enabled(heads, lanes, rank) -> bool:
    """Use ``mla_paged_decode`` over a latent pool of ``lanes``-wide
    rows?  The rule of :func:`paged_enabled` for the rows, and —
    compiled — the value span (``rank``) in whole lane tiles and the
    heads in whole sublane tiles."""
    return paged_enabled(lanes) and (
        _interpret() or (rank % 128 == 0 and heads % 16 == 0))


def _mla_paged_kernel(table_ref, start_ref, q_ref, pool_hbm, o_ref, buf, sem,
                      turn_scr, acc_scr, m_scr, l_scr, *, scale, kvb, pages,
                      mb, rank):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    keys = pages * kvb
    lanes = buf.shape[-1]

    def live(row):
        return jnp.clip(start_ref[row] + 1, 0, mb * kvb)

    def copies(row, chunk, slot, op):
        # the chunk's live pages, page i into span i of the slot's
        # buffer; "start" or "wait", the same pages by the same count
        first = chunk * pages

        def page(i, _):
            getattr(pltpu.make_async_copy(
                pool_hbm.at[table_ref[row, first + i]], buf.at[slot, i],
                sem.at[slot]), op)()

        jax.lax.fori_loop(
            0, jnp.clip(pl.cdiv(live(row), kvb) - first, 0, pages), page,
            None)

    @pl.when(b == 0)
    def _first_row():
        turn_scr[0] = 0
        buf[...] = jnp.zeros_like(buf)

    # the walk of ``_paged_kernel``: chunk c of the row is in buffer
    # (turn + c) % 2; a row starts its chunk c + 1 under chunk c and,
    # under its last chunk, the NEXT row's first — which a row with no
    # chunk of its own starts at once, and row 0 starts for itself
    turn = turn_scr[0]
    n = pl.cdiv(live(b), keys)
    after = jnp.minimum(b + 1, nb - 1)

    @pl.when((b == 0) | ((n == 0) & (b + 1 < nb)))
    def _first_chunk():
        copies(jnp.where(n == 0, after, b), 0, turn % 2, "start")

    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)

    def chunk_step(c, _):
        slot = (turn + c) % 2
        more = c + 1 < n

        @pl.when(more | (b + 1 < nb))
        def _ahead():
            copies(jnp.where(more, b, after), jnp.where(more, c + 1, 0),
                   1 - slot, "start")

        copies(b, c, slot, "wait")
        q = q_ref[0]                                  # (H, lanes)
        k = buf[slot].reshape(keys, lanes)
        # s[h, t] = [q~_h | q_r,h | 0] . [c_t | k_r,t | 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = c * keys + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < start_ref[b] + 1
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        # the value is the row's first ``rank`` lanes: the latent itself
        pv = jax.lax.dot_general(
            p.astype(k.dtype), k[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    jax.lax.fori_loop(0, n, chunk_step, None)
    turn_scr[0] = turn + n
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                ).astype(o_ref.dtype)


def mla_paged_decode(q, pool, block_table, start, rank, scale):
    """One decode step over the latent pool: q (B, H, lanes), a head's
    whole query [q_n W_uk | q_r rotated | zeros] at position
    ``start[b]``; pool (P, KVB, lanes), a token's row [latent | rotated
    positional key | zeros]; block_table (B, MB) page ids (page 0 =
    scratch) -> (B, H, rank): softmax(q . row x scale) over the keys
    ``0 .. start[b]``, times the rows' first ``rank`` lanes.

    The H heads of a stream share every cached row, so they are the
    ROWS of the chunk's two matmuls and the cache is read once a stream,
    not once a head; the value is a lane span of the key buffer, not a
    second pool.  The walk over a stream's pages (two buffers, a row
    starting the next row's first copies) is ``_paged_kernel``'s."""
    B, H, lanes = q.shape
    KVB = pool.shape[1]
    MB = block_table.shape[1]
    if pool.shape[2] != lanes or not 0 < rank <= lanes:
        raise MXNetError(
            f"mla_paged_decode: queries of {lanes} lanes over pool rows "
            f"{tuple(pool.shape)} with a value span of {rank}")
    pages = max(1, min(_MLA_PAGED_CHUNK_KEYS // KVB, MB))
    kern = functools.partial(_mla_paged_kernel, scale=float(scale), kvb=KVB,
                             pages=pages, mb=MB, rank=int(rank))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[_vmem_spec((1, H, lanes), lambda b, tr, sr: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=_vmem_spec((1, H, rank), lambda b, tr, sr: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages) + pool.shape[1:], pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, rank), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32)],
    )
    # rows in order ("arbitrary"): a row starts the next row's copies
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, int(rank)), q.dtype),
        compiler_params=_compiler_params("arbitrary"),
        interpret=_interpret(),
        name="mla_paged_decode",
    )(block_table.astype(jnp.int32), start.astype(jnp.int32), q, pool)


def latent_pages_write(rows, pool, pages):
    """:func:`kv_pages_write` for a layer with ONE pool: rows (B, T, W),
    pool (P, KVB, W), T a multiple of KVB; pages (B, T / KVB) int32 ->
    the pool with block j of row b written to page ``pages[b, j]``, a
    page a copy, a few in flight; a block whose page is 0 is skipped."""
    return _pages_write("mla_latent_write", (rows,), (pool,), pages)[0]
