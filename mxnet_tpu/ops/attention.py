"""Attention ops.

The reference predates attention (SURVEY §5.7), but the framework's
long-context story needs it as a first-class op: this registers a
fused multi-head scaled-dot-product attention usable from symbols and
imperatively, with a blockwise (FlashAttention-style) formulation that
never materializes the full (T, T) score matrix — the building block
``mxnet_tpu.sequence`` distributes over the mesh (ring / Ulysses).

Mesh contract (serving_mesh.MeshPrograms runs these INSIDE shard_map):
every paged op here is head-wise independent — scores, softmax and
the weighted sum never mix heads — so calling it on a tp shard's
LOCAL head slice (num_heads = H/tp, pools sliced on their head dim)
computes exactly the rows a single-device call computes for those
heads; page gathers/scatters through the block table are pure data
movement, bit-exact under sharding.  The one subtlety is the scratch
page: padding rows all scatter to (page 0, slot 0) and the winning
duplicate is implementation-defined, but it is CONSISTENT between two
jitted programs built from the same ops, which is what the engine's
bit-replay contract needs (page 0 is never read unmasked).
"""

from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, attr_bool, attr_int
from .registry import register


def blockwise_attention_partial(q, k, v, causal=False, block_size=512,
                                kv_offset=0):
    """Online-softmax attention over K/V blocks — UN-normalized state.

    q: (B, Tq, H, D); k, v: (B, Tk, H, D) → (o (B,H,Tq,D), m, l) with
    ``out = o / l`` after all partial states are merged.
    ``kv_offset`` is the absolute position of k[0] minus the absolute
    position of q[0] (the ring rotation uses it for causal masking
    across shards).  Memory: O(Tq · block) instead of O(Tq·Tk).

    On TPU the forward runs as the hand-written Pallas flash kernel
    (pallas_kernels.flash_attention_partial: MXU score tiles, VMEM-
    resident online-softmax state); backward rematerializes through
    this lax.scan formulation.  MXNET_PALLAS=0 disables.
    """
    from . import pallas_kernels as pk

    if pk.enabled() and q.ndim == 4:
        koff = jnp.asarray(kv_offset, jnp.int32)
        return _flash_partial_fn(bool(causal), int(block_size))(
            q, k, v, koff)
    return _blockwise_attention_partial_lax(q, k, v, causal, block_size,
                                            kv_offset)


def _blockwise_attention_partial_lax(q, k, v, causal, block_size,
                                     kv_offset, lengths=None,
                                     init_state=None, diagonal=False,
                                     window=0):
    """The pure lax.scan formulation — reference semantics and the
    remat backward for the Pallas forward.

    ``lengths`` (B,) int32, when given, replaces the positional causal
    mask with a per-stream key-visibility mask ``k_pos < lengths[b]``
    — the incremental-decode contract where the (single) query sits at
    absolute position ``lengths[b] - 1`` of a cache padded to Tk.  The
    block-local arithmetic is UNCHANGED, so with the same ``block_size``
    a decode step over a padded cache is bit-identical to the matching
    row of the full-sequence causal forward: shared blocks see the same
    values and the same effective mask, and a fully-masked trailing
    block is an exact no-op of the online-softmax merge (alpha == 1,
    p == 0 contributions).

    ``init_state``: an (o, m, l) carry to CONTINUE from instead of the
    empty state — chaining two calls scans their blocks as one
    sequence, so splitting a key range across calls (cached prefix
    pages, then raw suffix K/V — the prefix-cache suffix prefill) is
    bit-identical to a single scan over the concatenation.

    ``diagonal`` (with ``lengths``): per-QUERY visibility — query row
    ``i`` sees ``k_pos < lengths[b] + i`` instead of one limit per
    stream.  This is the speculative-verify mask: W queries at
    absolute positions ``start[b] + i`` each reproduce, row for row,
    the mask (and therefore the exact online-softmax block chain) of
    the single-query decode step at length ``lengths[b] + i`` — rows
    of the blockwise body are arithmetically independent, so one
    diagonal-masked scan is bit-identical to W sequential decode
    steps over the same cache bytes.

    ``window`` > 0 (causal, or one limit per stream): a query sees
    itself and the ``window - 1`` keys before it, no others — a lower
    bound on the keys beside the upper one; a block wholly below it is
    the same exact no-op of the merge as one wholly above."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    block = min(block_size, Tk)
    nblocks = (Tk + block - 1) // block
    pad = nblocks * block - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblocks, block, H, D)
    vb = v.reshape(B, nblocks, block, H, D)
    q_pos = jnp.arange(Tq)

    def body(carry, blk):
        o, m, l = carry
        k_j, v_j, j = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_j) * scale
        k_pos = j * block + jnp.arange(block) + kv_offset
        valid = (j * block + jnp.arange(block)) < Tk  # padding mask
        mask = valid[None, None, None, :]
        if lengths is not None and diagonal:
            limit = lengths[:, None] + q_pos[None, :]     # (B, Tq)
            mask = mask & (k_pos[None, None, None, :]
                           < limit[:, None, :, None])
        elif lengths is not None:
            mask = mask & (k_pos[None, None, None, :]
                           < lengths[:, None, None, None])
            if window:
                mask = mask & (k_pos[None, None, None, :]
                               >= lengths[:, None, None, None] - window)
        elif causal:
            mask = mask & (k_pos[None, None, None, :]
                           <= q_pos[None, None, :, None])
            if window:
                mask = mask & (k_pos[None, None, None, :]
                               > q_pos[None, None, :, None] - window)
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_j)
        return (o_new, m_new, l_new), None

    o0, m0, l0 = attention_state_init(q) if init_state is None \
        else init_state
    (o, m, l), _ = lax.scan(
        body, (o0, m0, l0),
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nblocks)))
    return o, m, l


@_functools.lru_cache(maxsize=None)
def _flash_partial_fn(causal, block_size):
    """custom_vjp wrapper per (causal, block_size): Pallas forward,
    lax.scan-remat backward (the LSTM kernel's differentiation
    pattern).  kv_offset rides along as a non-differentiable int32
    scalar (it is traced inside the ring's scan)."""
    import numpy as _np

    from . import pallas_kernels as pk

    @jax.custom_vjp
    def f(q, k, v, koff):
        return pk.flash_attention_partial(q, k, v, causal, block_size,
                                          koff)

    def fwd(q, k, v, koff):
        o, m, l = f(q, k, v, koff)
        return (o, m, l), (q, k, v, koff, m)

    def bwd(res, cots):
        q, k, v, koff, m = res
        do, dm, dl = cots
        # Pallas backward (pallas_kernels.flash_attention_bwd): the dm
        # cotangent is absorbed exactly — every consumer of the partial
        # state is invariant under (o,m,l) -> (o e^-c, m+c, l e^-c),
        # which cancels the argmax-subgradient terms (see the kernel's
        # derivation comment).  Equality with the lax.scan vjp is
        # asserted in tests/test_pallas.py.
        dq, dk, dv = pk.flash_attention_bwd(q, k, v, m, do, dl, causal,
                                            block_size, koff)
        return dq, dk, dv, _np.zeros(_np.shape(koff), jax.dtypes.float0)

    f.defvjp(fwd, bwd)
    return f


def normalize_attention_state(o, m, l, dtype):
    """(o, m, l) partial state → (B, Tq, H, D) attention output."""
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 2).astype(dtype)


def blockwise_attention(q, k, v, causal=False, block_size=0,
                        layout="BTHD"):
    """Normalized blockwise attention.

    layout='BTHD': (B, T, H, D) in/out (the reference-style layout).
    layout='BHTD': (B, H, T, D) in/out — the TPU-native layout (T in
    the sublane slot): on the kernel path this runs with ZERO
    transposes and no head-dim padding in HBM (the transformer model
    emits this layout).
    """
    from . import pallas_kernels as pk

    if pk.enabled() and q.ndim == 4:
        # normalized kernel: in-VMEM online-softmax state, in-kernel
        # normalization, single lse residual — ~6x less attention HBM
        # I/O than partial+normalize for d_head=64 (PERF.md)
        if layout == "BHTD":
            B, H, Tq, D = q.shape
            qf, kf, vf = (jnp.reshape(x, (B * H, x.shape[2], D))
                          for x in (q, k, v))
        else:
            B, Tq, H, D = q.shape
            qf, kf, vf = (jnp.reshape(jnp.transpose(x, (0, 2, 1, 3)),
                                      (B * H, x.shape[1], D))
                          for x in (q, k, v))
        o = pk.flash_mha(qf, kf, vf, causal=causal, block_size=block_size)
        o4 = jnp.reshape(o, (B, H, o.shape[1], D))
        if layout == "BHTD":
            return o4
        return jnp.transpose(o4, (0, 2, 1, 3))
    if layout == "BHTD":
        q, k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    o, m, l = blockwise_attention_partial(q, k, v, causal=causal,
                                          block_size=block_size or 512)
    out = normalize_attention_state(o, m, l, q.dtype)
    if layout == "BHTD":
        return jnp.transpose(out, (0, 2, 1, 3))
    return out


def attention_state_init(q):
    """Empty online-softmax state for q (B, Tq, H, D) → (o, m, l).

    Derived from q rather than fresh constants so that under shard_map
    the carries have the same varying-axis type as the loop body's
    outputs (fresh constants are 'unvarying' and fail the scan check).
    """
    o0 = q.swapaxes(1, 2).astype(jnp.float32) * 0.0  # (B, H, Tq, D)
    l0 = o0[..., 0]
    m0 = l0 - jnp.inf
    return o0, m0, l0


def attention_state_merge(o, m, l, o2, m2, l2):
    """Combine two partial online-softmax states (ring accumulation)."""
    m_new = jnp.maximum(m, m2)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    a1 = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_safe), 0.0)
    return (o * a1[..., None] + o2 * a2[..., None],
            m_new, l * a1 + l2 * a2)


def _attention_infer(attrs, in_shapes):
    q, k, v = in_shapes
    if q is None:
        return in_shapes, None, None
    return in_shapes, [tuple(q)], []


def _check_qkv_packing(last_dim, num_heads, shape):
    """Reject a qkv last dim that is not a positive multiple of
    3*num_heads — shared by shape inference and the runtime op, so the
    diagnosis is the same whichever path a bad graph reaches first
    (and not an opaque Pallas reshape failure later).  last_dim <
    3*num_heads also rejects d_head = 0, which a bare % 3 check would
    wave through."""
    if last_dim % (3 * num_heads) or last_dim < 3 * num_heads:
        raise MXNetError(
            f"QKVSelfAttention: qkv last dim {last_dim} does not pack "
            f"3*num_heads*d_head with num_heads={num_heads} (needs a "
            f"positive multiple of 3*{num_heads} = {3 * num_heads}); "
            f"expected packing is (B, T, 3*num_heads*d_head) laid out "
            f"as contiguous thirds [q | k | v], each third holding all "
            f"heads' d_head lanes (got shape {tuple(shape)})")


def _flash_mha_packed_on_plan(qkv, H, causal):
    """The packed-heads kernel, on one device or across the mesh the
    graph is traced for.

    A Mosaic kernel cannot be partitioned by the compiler (on the chip
    a mesh program holding a bare one fails to lower), so under a
    MeshPlan the call is a shard_map: the batch splits over the mesh
    axis the rules give 'batch', the heads over the one they give
    'heads' (when it divides them), every other axis replicates.
    qkv arrives whole along its packed dim — [q | k | v], each H·D
    wide, so a contiguous split of that dim is NOT a split by heads —
    and each device cuts its own heads' q, k and v spans out of it.
    The output leaves sharded by heads on its last dim: contiguous
    H·D/tp spans, the layout the row-parallel output projection
    takes."""
    from jax.sharding import PartitionSpec as P

    from . import pallas_kernels as pk
    from ..parallel import traced_plan

    plan = traced_plan()
    if plan is None or plan.num_devices == 1:
        return pk.flash_mha_packed(qkv, H, causal=causal)
    sizes = dict(plan.mesh.shape)
    B, _T, HD3 = qkv.shape
    HD = HD3 // 3
    b_ax = plan.rules.axis_or_none("batch")
    if b_ax is not None and B % sizes[b_ax]:
        b_ax = None
    h_ax = plan.rules.axis_or_none("heads")
    if h_ax is not None and (h_ax == b_ax or H % sizes[h_ax]):
        h_ax = None
    n_h = sizes[h_ax] if h_ax is not None else 1
    span = HD // n_h  # this device's heads, as lanes of q (or k, or v)

    def local(x):
        if n_h > 1:
            lo = jax.lax.axis_index(h_ax) * span
            x = jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(x, part * HD + lo, span,
                                              axis=2)
                 for part in range(3)], axis=2)
        return pk.flash_mha_packed(x, H // n_h, causal=causal)

    return jax.shard_map(local, mesh=plan.mesh,
                         in_specs=P(b_ax, None, None),
                         out_specs=P(b_ax, None, h_ax),
                         check_vma=False)(qkv)


def _qkv_infer(attrs, in_shapes):
    (s,) = in_shapes
    if s is None:
        return in_shapes, None, None
    H = attr_int(attrs.get("num_heads", 1), 1)
    if len(s) != 3:
        raise MXNetError(
            f"QKVSelfAttention wants a 3-D qkv (B, T, 3*num_heads*d_head); "
            f"got {s}")
    _check_qkv_packing(s[2], H, s)
    return in_shapes, [(s[0], s[1], s[2] // 3)], []


@register("QKVSelfAttention", arg_names=("qkv",), infer_shape=_qkv_infer,
          doc="Self-attention straight off the fused QKV projection: "
              "qkv (B, T, 3*H*D) packed [q|k|v] per head -> (B, T, H*D)."
              " On TPU this is the packed-heads Pallas kernel with zero "
              "layout changes anywhere (PERF.md), on tiles of its own "
              "choice; block_size is the lax body's; attrs: num_heads, "
              "causal, block_size")
def _qkv_attention(op_ctx, attrs, inputs, aux):
    (qkv,) = inputs
    if qkv.ndim != 3:
        raise MXNetError("QKVSelfAttention expects (B, T, 3*H*D)")
    H = attr_int(attrs.get("num_heads", 1), 1)
    causal = attr_bool(attrs.get("causal", False), False)
    block = attr_int(attrs.get("block_size", 0), 0)
    from . import pallas_kernels as pk

    B, T, HD3 = qkv.shape
    _check_qkv_packing(HD3, H, qkv.shape)
    D = HD3 // (3 * H)
    if pk.enabled():
        return [_flash_mha_packed_on_plan(qkv, H, causal)]
    # lax fallback: unpack → blockwise attention → repack
    q, k, v = (jnp.reshape(x, (B, T, H, D))
               for x in jnp.split(qkv, 3, axis=-1))
    o, m, l = _blockwise_attention_partial_lax(q, k, v, causal,
                                               block or 512, 0)
    out = normalize_attention_state(o, m, l, qkv.dtype)
    return [jnp.reshape(out, (B, T, H * D))]


# ---------------------------------------------------------------------------
# Incremental decode: prefill K/V exposure, cached single-token decode,
# and the paged (block-table) cache variant.  Design contract: the KV
# page size IS the attention block size, so the decode step's online-
# softmax block partition lines up with the full forward's — shared
# blocks compute identical floats and trailing fully-masked blocks are
# exact no-ops, making prefill + N decode steps bit-identical (lax
# path) to the full-sequence causal forward.  See tests/test_decode.py.
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, lengths, block_size, window=0):
    """One-query-position attention over a padded KV cache.

    q: (B, 1, H, D) — the current token's query, sitting at absolute
    position ``lengths[b] - 1``; k_cache/v_cache: (B, C, H, D) with
    positions >= lengths[b] ignored (masked exactly); lengths: (B,)
    int32 INCLUDING the current token; ``window`` > 0: positions below
    ``lengths[b] - window`` ignored too.  Returns (B, 1, H, D).
    """
    o, m, l = _blockwise_attention_partial_lax(
        q, k_cache, v_cache, True, block_size or 512, 0, lengths=lengths,
        window=window)
    return normalize_attention_state(o, m, l, q.dtype)


def _unpack_qkv(qkv, H):
    B, S, HD3 = qkv.shape
    _check_qkv_packing(HD3, H, qkv.shape)
    D = HD3 // (3 * H)
    q, k, v = (jnp.reshape(x, (B, S, H, D))
               for x in jnp.split(qkv, 3, axis=-1))
    return q, k, v, D


def _split_qkv(qkv, H):
    """The q, k and v lane spans of a fused projection, each
    (B, S, H·D) — the rows the lane-dense K/V pools hold as they are
    (``kv_cache.value_pool_shape``), never reshaped to (H, D)."""
    _check_qkv_packing(qkv.shape[2], H, qkv.shape)
    return jnp.split(qkv, 3, axis=-1)


def _heads(x, H):
    """(..., H·D) rows -> (..., H, D): for GATHERED rows and queries
    on the lax fallbacks, never for a pool."""
    return jnp.reshape(x, x.shape[:-1] + (H, x.shape[-1] // H))


def _rows(x):
    """(..., H, D) -> (..., H·D) rows."""
    return jnp.reshape(x, x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def quantize_kv(x, qdtype):
    """Quantize K or V state (..., H, D) to ``qdtype`` (int8 or an fp8
    type) with one float32 scale per (..., H) — per token slot, per
    head.  The scale maps each head's max-|value| to the dtype's
    representable max, so pages written once keep their bytes forever
    (a shared full page is immutable; no page-wide re-scaling drift).
    Returns (q, scale)."""
    from ..kv_cache import KV_QMAX

    qdtype = jnp.dtype(qdtype)
    qmax = KV_QMAX["int8"] if qdtype == jnp.int8 else KV_QMAX["fp8"]
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    y = x32 / scale[..., None]
    if qdtype == jnp.int8:
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = y.astype(qdtype)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv`: float32 values."""
    return q.astype(jnp.float32) * scale[..., None]


def cache_update(cache_k, cache_v, k_t, v_t, lengths):
    """Scatter the current token's K/V into a contiguous (B, C, H, D)
    cache at position ``lengths - 1``.  Streams with lengths == 0
    (padded batch slots) write to slot 0 — their cache is dead weight
    and every read of it is masked."""
    B = cache_k.shape[0]
    pos = jnp.maximum(lengths - 1, 0)
    rows = jnp.arange(B)
    return (cache_k.at[rows, pos].set(k_t[:, 0].astype(cache_k.dtype)),
            cache_v.at[rows, pos].set(v_t[:, 0].astype(cache_v.dtype)))


def paged_cache_update(k_pool, v_pool, k_t, v_t, block_table, lengths):
    """Scatter the current token's K/V rows into the paged pools.

    k_pool/v_pool: (P, KVB, H·D) — lane-dense, see
    ``kv_cache.value_pool_shape``; k_t/v_t: (B, 1, H·D), the k and v
    lane spans of the fused projection; block_table: (B, MB) int32
    page ids; lengths: (B,) including the current token.  Page 0 is
    the reserved scratch page: inactive streams (lengths == 0) land
    there, so the scatter needs no masking and never corrupts a live
    page."""
    page, slot = _step_write_coords(block_table, lengths, k_pool.shape[1])
    return (k_pool.at[page, slot].set(k_t[:, 0].astype(k_pool.dtype)),
            v_pool.at[page, slot].set(v_t[:, 0].astype(v_pool.dtype)))


def _step_write_coords(block_table, lengths, KVB):
    """(page, slot) of the current token's row, position ``lengths - 1``
    of each stream; a stream with lengths == 0 lands on the scratch
    page's slot 0."""
    pos = jnp.maximum(lengths - 1, 0)
    B = block_table.shape[0]
    rows = jnp.arange(B)
    page = jnp.where(lengths > 0,
                     block_table[rows, pos // KVB], 0)
    slot = jnp.where(lengths > 0, pos % KVB, 0)
    return page, slot


def _paged_write_coords(block_table, lengths, T, KVB, start=None):
    """(page, slot, live) scatter coordinates for a (B, T, ...) run of
    tokens whose first row sits at absolute position ``start[b]``
    (default 0 — the classic whole-prompt prefill).  Rows at or past
    ``lengths[b]`` (padding) route to the scratch page 0."""
    pos = jnp.broadcast_to(jnp.arange(T)[None, :],
                           (block_table.shape[0], T))
    if start is not None:
        pos = pos + start[:, None]
    live = pos < lengths[:, None]                              # (B, T)
    page = jnp.where(live,
                     jnp.take_along_axis(block_table,
                                         pos // KVB, axis=1), 0)
    slot = jnp.where(live, pos % KVB, 0)
    return page, slot, live


def _live_pages(block_table, lengths, blocks, KVB):
    """(B, blocks) page ids of a prompt's blocks: the table's where the
    block holds a position below ``lengths[b]``, else 0 (nowhere)."""
    live = jnp.arange(blocks)[None, :] * KVB < lengths[:, None]
    return jnp.where(live, block_table[:, :blocks], 0)


def _writes_whole_pages(k, k_pool, start):
    """May a (B, T, W) run of rows reach the (P, KVB, W) pools a page a
    copy (``pallas_kernels.kv_pages_write``)?  Decided from what the
    call can see: the run starts at position 0 (``start`` is traced and
    need not be page-aligned), T is whole pages, a page is whole
    sublane tiles of the pool's type (16 rows of bfloat16, 8 of
    float32: a copy moves whole tiles) and the paged kernels run over
    rows this wide at all (``pallas_kernels.paged_enabled``: lanes in
    whole tiles when compiled).  Counted on ``/metrics`` once a traced
    op: ``kv_write.page_kernel_calls`` / ``kv_write.row_scatter_calls``,
    and the pages of the last such call, ``kv_write.pages_per_call``."""
    from .. import profiler
    from . import pallas_kernels as pk

    B, T = k.shape[:2]
    KVB, W = k_pool.shape[1:]
    whole = (start is None and T % KVB == 0
             and KVB % (32 // jnp.dtype(k_pool.dtype).itemsize) == 0
             and pk.paged_enabled(W))
    profiler.inc_counter("kv_write.page_kernel_calls", whole)
    profiler.inc_counter("kv_write.row_scatter_calls", not whole)
    if whole:
        profiler.set_gauge("kv_write.pages_per_call", B * (T // KVB))
    return whole


def paged_prefill_write(k, v, k_pool, v_pool, block_table, lengths,
                        start=None):
    """Write a prompt's (or — with ``start`` — a prompt suffix's) K/V
    rows (B, T, H·D) into the (P, KVB, H·D) paged pools.

    A whole prompt from position 0 in whole pages goes page by page
    (:func:`_writes_whole_pages`; one Mosaic kernel for K and V): block
    j of row b lands on page ``block_table[b, j]`` where
    ``j·KVB < lengths[b]`` and nowhere otherwise (the scratch page 0 is
    not written; a windowed table's blocks behind the window hold 0
    too).  THE LAST LIVE PAGE then holds the prompt's padding rows at
    its slots >= ``lengths[b]`` — where the page's previous owner's
    bytes were before: no reader may depend on a slot at or past the
    length, and none does (attention masks by length, the decode step
    writes slot ``length`` before anything reads it, the prefix index
    registers FULL pages only, an exported frame's tail is masked by
    its importer the same way).

    Every other run (``start`` given: a suffix, a chunk, a verify
    window; T or KVB off the tiles) is a row-wise scatter: positions
    >= lengths[b] are routed to the scratch page 0 instead of being
    masked out of the scatter."""
    KVB = k_pool.shape[1]
    T = k.shape[1]
    if _writes_whole_pages(k, k_pool, start):
        from . import pallas_kernels as pk

        return tuple(pk.kv_pages_write(
            k, v, k_pool, v_pool,
            _live_pages(block_table, lengths, T // KVB, KVB)))
    page, slot, _ = _paged_write_coords(block_table, lengths, T, KVB,
                                        start)
    return (k_pool.at[page, slot].set(k.astype(k_pool.dtype)),
            v_pool.at[page, slot].set(v.astype(v_pool.dtype)))


def latent_cache_update(pool, row, block_table, lengths):
    """:func:`paged_cache_update` for a layer that keeps ONE row a token
    (a compressed latent: ``kv_cache.latent_pool_shape``): row (B, 1, W)
    into pool (P, KVB, W) at position ``lengths - 1``; rows with
    lengths == 0 land on the scratch page."""
    page, slot = _step_write_coords(block_table, lengths, pool.shape[1])
    return pool.at[page, slot].set(row[:, 0].astype(pool.dtype))


def latent_prefill_write(rows, pool, block_table, lengths):
    """:func:`paged_prefill_write` for ONE pool: a whole prompt's rows
    (B, T, W) into (P, KVB, W), a page a copy under the same rule
    (:func:`_writes_whole_pages`; ``pallas_kernels.latent_pages_write``)
    — the last live page then holds the padding's rows past the length,
    which no reader depends on — and a row-wise scatter otherwise."""
    KVB = pool.shape[1]
    T = rows.shape[1]
    if _writes_whole_pages(rows, pool, None):
        from . import pallas_kernels as pk

        return pk.latent_pages_write(
            rows, pool, _live_pages(block_table, lengths, T // KVB, KVB))
    page, slot, _ = _paged_write_coords(block_table, lengths, T, KVB)
    return pool.at[page, slot].set(rows.astype(pool.dtype))


def _quantize_rows(x, H, qdtype):
    """:func:`quantize_kv` of (..., H·D) rows, per head: the quantized
    rows (..., H·D) and their (..., H) float32 scales."""
    q, scale = quantize_kv(_heads(x, H), qdtype)
    return _rows(q), scale


def paged_prefill_write_q(k, v, k_pool, v_pool, k_scale, v_scale,
                          block_table, lengths, start=None):
    """Quantize-on-write prefill scatter: K/V rows (B, T, H·D) land in
    the int8/fp8 (P, KVB, H·D) pools, their per-slot-per-head float32
    scales in the (P, KVB, H) scale pools."""
    KVB = k_pool.shape[1]
    T = k.shape[1]
    H = k_scale.shape[2]
    page, slot, _ = _paged_write_coords(block_table, lengths, T, KVB,
                                        start)
    kq, ks = _quantize_rows(k, H, k_pool.dtype)
    vq, vs = _quantize_rows(v, H, v_pool.dtype)
    return (k_pool.at[page, slot].set(kq),
            v_pool.at[page, slot].set(vq),
            k_scale.at[page, slot].set(ks),
            v_scale.at[page, slot].set(vs))


def paged_cache_update_q(k_pool, v_pool, k_scale, v_scale, k_t, v_t,
                         block_table, lengths):
    """Quantize-on-write single-token scatter (the decode step): the
    new token's K/V rows (B, 1, H·D) quantize against their own
    per-head scales and land in the narrow pools; the scales land in
    the (P, KVB, H) scale pools.  Previously-written slots are
    untouched — no page-wide re-scaling, so shared full pages keep
    their bytes."""
    KVB = k_pool.shape[1]
    H = k_scale.shape[2]
    pos = jnp.maximum(lengths - 1, 0)
    B = block_table.shape[0]
    rows = jnp.arange(B)
    page = jnp.where(lengths > 0,
                     block_table[rows, pos // KVB], 0)
    slot = jnp.where(lengths > 0, pos % KVB, 0)
    kq, ks = _quantize_rows(k_t[:, 0], H, k_pool.dtype)  # (B, H·D), (B, H)
    vq, vs = _quantize_rows(v_t[:, 0], H, v_pool.dtype)
    return (k_pool.at[page, slot].set(kq),
            v_pool.at[page, slot].set(vq),
            k_scale.at[page, slot].set(ks),
            v_scale.at[page, slot].set(vs))


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, H):
    """Gather-by-block-table decode attention: q (B, 1, H·D) ->
    (B, 1, H·D).

    The lax fallback materializes the gathered cache and reshapes IT —
    the gathered rows, never the pool — to (B, MB*KVB, H, D), then
    runs the same blockwise body with block == KVB, so the result is
    bit-identical to the contiguous-cache decode (pages hold the same
    values; page boundaries ARE block boundaries).  The Pallas kernel
    (pallas_kernels.paged_attention_decode) gathers a chunk of pages
    at a time in VMEM instead and never materializes the full cache.
    """
    from . import pallas_kernels as pk

    KVB = k_pool.shape[1]
    if pk.paged_enabled(k_pool.shape[2]):
        out = pk.paged_attention_decode(q[:, 0], k_pool, v_pool,
                                        block_table, lengths, H)
        return out[:, None]
    B, MB = block_table.shape
    D = k_pool.shape[2] // H
    kg = k_pool[block_table].reshape(B, MB * KVB, H, D)
    vg = v_pool[block_table].reshape(B, MB * KVB, H, D)
    return _rows(decode_attention(_heads(q, H), kg, vg, lengths, KVB))


def paged_decode_attention_q(q, k_pool, v_pool, k_scale, v_scale,
                             block_table, lengths):
    """Quantized-cache decode attention: the Pallas kernel dequantizes
    each page in VMEM after its DMA; the lax fallback dequantizes the
    gathered cache to float32 and runs the reference blockwise body
    (fp32 softmax accumulation on both paths)."""
    from . import pallas_kernels as pk

    KVB = k_pool.shape[1]
    H = k_scale.shape[2]
    if pk.paged_enabled(k_pool.shape[2]):
        out = pk.paged_attention_decode_quant(
            q[:, 0], k_pool, v_pool, k_scale, v_scale, block_table,
            lengths, H)
        return out[:, None]
    B, MB = block_table.shape
    D = k_pool.shape[2] // H
    kg = dequantize_kv(k_pool[block_table].reshape(B, MB * KVB, H, D),
                       k_scale[block_table].reshape(B, MB * KVB, H))
    vg = dequantize_kv(v_pool[block_table].reshape(B, MB * KVB, H, D),
                       v_scale[block_table].reshape(B, MB * KVB, H))
    return _rows(decode_attention(_heads(q, H), kg, vg, lengths, KVB))


def prefix_suffix_attention(q, k_suf, v_suf, kg, vg, start, block):
    """Attention for a suffix prefill over a prefix-shared cache.

    q/k_suf/v_suf (B, Ts, H, D) are the UNCACHED suffix (absolute
    positions ``start[b] + i``); kg/vg (B, C, H, D) is the gathered
    (and, if quantized, dequantized) paged cache whose first
    ``start[b]`` slots hold the shared prefix.  Two chained scans over
    the SAME online-softmax body — prefix blocks (key-visibility mask
    ``k_pos < start``), then causal suffix blocks continuing the carry
    — reproduce the full forward's block merge sequence exactly:
    ``start`` is block-aligned, so every block either matches a full
    forward block bit-for-bit or is a fully-masked exact no-op.  The
    suffix attends its OWN K/V raw (pre-quantization), like the full
    forward would."""
    o, m, l = _blockwise_attention_partial_lax(
        q, kg, vg, False, block, 0, lengths=start)
    o, m, l = _blockwise_attention_partial_lax(
        q, k_suf, v_suf, True, block, 0, init_state=(o, m, l))
    return normalize_attention_state(o, m, l, q.dtype)


def _qkv_prefill_infer(attrs, in_shapes):
    (s,) = in_shapes
    if s is None:
        return in_shapes, None, None
    H = attr_int(attrs.get("num_heads", 1), 1)
    if len(s) != 3:
        raise MXNetError(
            f"QKVSelfAttentionPrefill wants a 3-D qkv "
            f"(B, T, 3*num_heads*d_head); got {s}")
    _check_qkv_packing(s[2], H, s)
    D = s[2] // (3 * H)
    return in_shapes, [(s[0], s[1], s[2] // 3),
                       (s[0], s[1], H, D), (s[0], s[1], H, D)], []


@register("QKVSelfAttentionPrefill", arg_names=("qkv",),
          out_names=("output", "key", "value"),
          infer_shape=_qkv_prefill_infer,
          doc="Causal self-attention off the fused QKV projection that "
              "ALSO returns the (B, T, H, D) key/value state for a KV "
              "cache — the prefill half of incremental decode.  Output "
              "is bit-identical to QKVSelfAttention at the same "
              "block_size.  block_size governs the lax body only (the "
              "CPU contract: prefill + decode equal the full forward at "
              "one block size); the Mosaic kernel chooses its tiles "
              "from the shape (pallas_kernels._mhap_tiles), so a page "
              "size never sets them; attrs: num_heads, block_size")
def _qkv_attention_prefill(op_ctx, attrs, inputs, aux):
    (qkv,) = inputs
    if qkv.ndim != 3:
        raise MXNetError("QKVSelfAttentionPrefill expects (B, T, 3*H*D)")
    H = attr_int(attrs.get("num_heads", 1), 1)
    block = attr_int(attrs.get("block_size", 0), 0)
    q, k, v, D = _unpack_qkv(qkv, H)
    B, T = qkv.shape[0], qkv.shape[1]
    from . import pallas_kernels as pk

    if pk.enabled():
        out = pk.flash_mha_packed(qkv, H, causal=True)
        return [out, k, v]
    o, m, l = _blockwise_attention_partial_lax(q, k, v, True, block or 512,
                                               0)
    out = normalize_attention_state(o, m, l, qkv.dtype)
    return [jnp.reshape(out, (B, T, H * D)), k, v]


def _qkv_decode_infer(attrs, in_shapes):
    qkv, ck, cv, ln = in_shapes
    if qkv is None or ck is None:
        return in_shapes, None, None
    H = attr_int(attrs.get("num_heads", 1), 1)
    _check_qkv_packing(qkv[2], H, qkv)
    _check_decode_step_shape("QKVSelfAttentionDecode", qkv)
    return in_shapes, [(qkv[0], 1, qkv[2] // 3), tuple(ck),
                       tuple(cv if cv is not None else ck)], []


@register("QKVSelfAttentionDecode",
          arg_names=("qkv", "cache_k", "cache_v", "lengths"),
          out_names=("output", "new_cache_k", "new_cache_v"),
          infer_shape=_qkv_decode_infer,
          doc="One incremental-decode step over a contiguous KV cache: "
              "qkv (B, 1, 3*H*D) of the current token at position "
              "lengths-1, cache_k/v (B, C, H, D), lengths (B,) int32 "
              "counting the current token -> output (B, 1, H*D) plus "
              "the in-place-updated caches (donate them under jit).  "
              "block_size must equal the prefill/full-forward block "
              "size for bit-identical decode — of the lax bodies, which "
              "alone read it (this op has no kernel; the prefill's and "
              "the full forward's Mosaic kernels choose their own "
              "tiles); attrs: num_heads, block_size")
def _qkv_attention_decode(op_ctx, attrs, inputs, aux):
    qkv, cache_k, cache_v, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    _check_decode_step_shape("QKVSelfAttentionDecode", qkv.shape)
    block = attr_int(attrs.get("block_size", 0), 0)
    q, k_t, v_t, D = _unpack_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    new_k, new_v = cache_update(cache_k, cache_v, k_t, v_t, lengths)
    out = decode_attention(q, new_k, new_v, lengths, block)
    B = qkv.shape[0]
    return [jnp.reshape(out, (B, 1, H * D)), new_k, new_v]


def _check_decode_step_shape(op_name, qkv_shape):
    if qkv_shape[1] != 1:
        raise MXNetError(
            f"{op_name} feeds ONE query position per step; got qkv "
            f"{tuple(qkv_shape)} (S = {qkv_shape[1]}) — tokens past "
            f"the first would be silently dropped, not attended")


def _pools_out(op_name, pools, H, D):
    """Output shapes of the pools a paged op hands back — its inputs'
    (a missing v pool/scale takes its k twin's) — with every value pool
    held to ``kv_cache.value_pool_shape``: a (P, KVB, H, D) pool from
    before the pools went lane-dense is refused here, by name."""
    from ..kv_cache import value_pool_shape

    out = []
    for i, p in enumerate(pools):
        p = p if p is not None else pools[i - i % 2]
        if p is not None and i < 2 \
                and tuple(p) != value_pool_shape(p[0], p[1], H, D):
            raise MXNetError(
                f"{op_name}: value pool {tuple(p)} is not (pages, "
                f"kv_block, num_heads*d_head = {H}*{D}) — "
                f"kv_cache.value_pool_shape")
        out.append(tuple(p) if p is not None else None)
    return out


def _paged_qkv_infer(op_name, n_pools, one_position=False):
    """infer_shape of a paged op fed the fused qkv: inputs (qkv,
    *pools, ...), outputs (attention, *pools)."""
    def infer(attrs, in_shapes):
        qkv, pools = in_shapes[0], in_shapes[1:1 + n_pools]
        if qkv is None or pools[0] is None:
            return in_shapes, None, None
        H = attr_int(attrs.get("num_heads", 1), 1)
        _check_qkv_packing(qkv[2], H, qkv)
        if one_position:
            _check_decode_step_shape(op_name, qkv)
        return in_shapes, [(qkv[0], qkv[1], qkv[2] // 3)] + _pools_out(
            op_name, pools, H, qkv[2] // (3 * H)), []
    return infer


def _paged_write_infer(op_name, n_pools):
    """infer_shape of PagedCacheWrite[Q]: inputs (key, value, *pools,
    ...) with key/value (B, T, H, D), outputs the pools."""
    def infer(attrs, in_shapes):
        k, pools = in_shapes[0], in_shapes[2:2 + n_pools]
        if k is None or pools[0] is None:
            return in_shapes, None, None
        return in_shapes, _pools_out(op_name, pools, k[2], k[3]), []
    return infer


@register("QKVPagedAttentionDecode",
          arg_names=("qkv", "k_pool", "v_pool", "block_table", "lengths"),
          out_names=("output", "new_k_pool", "new_v_pool"),
          infer_shape=_paged_qkv_infer("QKVPagedAttentionDecode", 2, True),
          doc="One incremental-decode step over the PAGED KV cache: "
              "qkv (B, 1, 3*H*D), k_pool/v_pool (P, KVB, H*D) shared "
              "page pools — lane-dense, a head is a D-lane span of a "
              "row as in qkv (D = 64 is half a lane tile: a "
              "(..., H, D) pool has no unpadded tiled layout and is "
              "re-laid-out whole by every program that touches it) — "
              "block_table (B, MB) int32 page ids (page 0 "
              "reserved scratch), lengths (B,) int32 -> output "
              "(B, 1, H*D) + updated pools (donate under jit).  The "
              "page size KVB is the attention block size; memory "
              "scales with pages actually held, not max_len x streams."
              "  Pallas gather-by-block-table kernel on TPU, lax "
              "gather fallback elsewhere; attrs: num_heads")
def _qkv_paged_attention_decode(op_ctx, attrs, inputs, aux):
    qkv, k_pool, v_pool, block_table, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    _check_decode_step_shape("QKVPagedAttentionDecode", qkv.shape)
    q, k_t, v_t = _split_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    new_kp, new_vp = paged_cache_update(k_pool, v_pool, k_t, v_t,
                                        block_table, lengths)
    out = paged_decode_attention(q, new_kp, new_vp, block_table, lengths,
                                 H)
    return [out, new_kp, new_vp]


@register("PagedCacheWrite",
          arg_names=("key", "value", "k_pool", "v_pool", "block_table",
                     "lengths"),
          out_names=("new_k_pool", "new_v_pool"),
          infer_shape=_paged_write_infer("PagedCacheWrite", 2),
          doc="Scatter a prefilled prompt's (B, T, H, D) key/value "
              "state, as (B, T, H*D) rows, into the (P, KVB, H*D) "
              "paged pools through each stream's block "
              "table.  The prefill half of paged incremental decode.  "
              "Whole pages of a whole prompt go a page a copy (one "
              "Mosaic kernel for K and V) where the shapes allow "
              "(ops.attention.paged_prefill_write): the LAST live page "
              "then holds the prompt's padding rows at its slots >= "
              "lengths[b], and no reader may depend on a slot at or "
              "past the length; blocks past the length are not "
              "written.  Otherwise row by row: positions >= lengths[b] "
              "land on the scratch page 0.")
def _paged_cache_write(op_ctx, attrs, inputs, aux):
    k, v, k_pool, v_pool, block_table, lengths = inputs
    new_kp, new_vp = paged_prefill_write(
        _rows(k), _rows(v), k_pool, v_pool,
        block_table.astype(jnp.int32), lengths.astype(jnp.int32))
    return [new_kp, new_vp]


# ---------------------------------------------------------------------------
# Prefix-shared + quantized cache ops.  The *Q variants carry the
# (P, KVB, H) float32 scale pools alongside the int8/fp8 (P, KVB, H·D)
# value pools
# (quantize-on-write, dequantize-on-read, fp32 softmax accumulation);
# the PrefillAttend pair is the suffix-only prefill of a prefix-cache
# hit: the uncached suffix's K/V is written at offset ``start`` and
# its queries attend cached-prefix pages + raw suffix causally.
# ---------------------------------------------------------------------------


@register("PagedCacheWriteQ",
          arg_names=("key", "value", "k_pool", "v_pool", "k_scale",
                     "v_scale", "block_table", "lengths"),
          out_names=("new_k_pool", "new_v_pool", "new_k_scale",
                     "new_v_scale"),
          infer_shape=_paged_write_infer("PagedCacheWriteQ", 4),
          doc="PagedCacheWrite for QUANTIZED pools: the (B, T, H, D) "
              "key/value state quantizes on write into int8/fp8 "
              "(P, KVB, H*D) pools with per-slot-per-head float32 "
              "scales in the (P, KVB, H) scale pools.  Positions >= "
              "lengths[b] land on the scratch page 0.")
def _paged_cache_write_q(op_ctx, attrs, inputs, aux):
    k, v, k_pool, v_pool, k_scale, v_scale, block_table, lengths = inputs
    return list(paged_prefill_write_q(
        _rows(k), _rows(v), k_pool, v_pool, k_scale, v_scale,
        block_table.astype(jnp.int32), lengths.astype(jnp.int32)))


@register("QKVPagedAttentionDecodeQ",
          arg_names=("qkv", "k_pool", "v_pool", "k_scale", "v_scale",
                     "block_table", "lengths"),
          out_names=("output", "new_k_pool", "new_v_pool",
                     "new_k_scale", "new_v_scale"),
          infer_shape=_paged_qkv_infer("QKVPagedAttentionDecodeQ", 4, True),
          doc="QKVPagedAttentionDecode over QUANTIZED pools: the "
              "current token's K/V quantizes on write (per-slot-per-"
              "head scales); attention dequantizes inside the Pallas "
              "page-gather kernel (lax fallback dequantizes the "
              "gathered cache) with fp32 softmax accumulation; "
              "attrs: num_heads")
def _qkv_paged_attention_decode_q(op_ctx, attrs, inputs, aux):
    qkv, k_pool, v_pool, k_scale, v_scale, block_table, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    _check_decode_step_shape("QKVPagedAttentionDecodeQ", qkv.shape)
    q, k_t, v_t = _split_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    new_kp, new_vp, new_ks, new_vs = paged_cache_update_q(
        k_pool, v_pool, k_scale, v_scale, k_t, v_t, block_table,
        lengths)
    out = paged_decode_attention_q(q, new_kp, new_vp, new_ks, new_vs,
                                   block_table, lengths)
    return [out, new_kp, new_vp, new_ks, new_vs]


@register("QKVPagedPrefillAttend",
          arg_names=("qkv", "k_pool", "v_pool", "block_table", "start",
                     "lengths"),
          out_names=("output", "new_k_pool", "new_v_pool"),
          infer_shape=_paged_qkv_infer("QKVPagedPrefillAttend", 2),
          doc="Suffix prefill over a prefix-shared paged cache: qkv "
              "(B, Ts, 3*H*D) holds the UNCACHED suffix (absolute "
              "positions start[b]+i, start block-aligned); its K/V is "
              "written through the block table at that offset and its "
              "queries attend the cached prefix pages plus the raw "
              "suffix causally — bit-identical (lax path) to the full "
              "causal forward's suffix rows.  start (B,) int32 cached "
              "tokens, lengths (B,) int32 TOTAL tokens; attrs: "
              "num_heads")
def _qkv_paged_prefill_attend(op_ctx, attrs, inputs, aux):
    qkv, k_pool, v_pool, block_table, start, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    q, k, v = _split_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    start = start.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    new_kp, new_vp = paged_prefill_write(
        k, v, k_pool, v_pool, block_table, lengths, start=start)
    KVB = k_pool.shape[1]
    B, MB = block_table.shape
    D = k_pool.shape[2] // H
    kg = new_kp[block_table].reshape(B, MB * KVB, H, D)
    vg = new_vp[block_table].reshape(B, MB * KVB, H, D)
    out = prefix_suffix_attention(_heads(q, H), _heads(k, H),
                                  _heads(v, H), kg, vg, start, KVB)
    return [_rows(out), new_kp, new_vp]


@register("QKVPagedPrefillAttendQ",
          arg_names=("qkv", "k_pool", "v_pool", "k_scale", "v_scale",
                     "block_table", "start", "lengths"),
          out_names=("output", "new_k_pool", "new_v_pool",
                     "new_k_scale", "new_v_scale"),
          infer_shape=_paged_qkv_infer("QKVPagedPrefillAttendQ", 4),
          doc="QKVPagedPrefillAttend over QUANTIZED pools: the suffix "
              "quantizes on write; the cached prefix dequantizes on "
              "gather; the suffix attends its own K/V raw (pre-"
              "quantization), fp32 softmax accumulation; attrs: "
              "num_heads")
def _qkv_paged_prefill_attend_q(op_ctx, attrs, inputs, aux):
    (qkv, k_pool, v_pool, k_scale, v_scale, block_table, start,
     lengths) = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    q, k, v = _split_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    start = start.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    new_kp, new_vp, new_ks, new_vs = paged_prefill_write_q(
        k, v, k_pool, v_pool, k_scale, v_scale, block_table, lengths,
        start=start)
    KVB = k_pool.shape[1]
    B, MB = block_table.shape
    D = k_pool.shape[2] // H
    kg = dequantize_kv(new_kp[block_table].reshape(B, MB * KVB, H, D),
                       new_ks[block_table].reshape(B, MB * KVB, H))
    vg = dequantize_kv(new_vp[block_table].reshape(B, MB * KVB, H, D),
                       new_vs[block_table].reshape(B, MB * KVB, H))
    out = prefix_suffix_attention(_heads(q, H), _heads(k, H),
                                  _heads(v, H), kg, vg, start, KVB)
    return [_rows(out), new_kp, new_vp, new_ks, new_vs]


# ---------------------------------------------------------------------------
# Speculative verify: the k-token multi-query decode step.  W = 1 + k
# queries at absolute positions start[b]..start[b]+W-1 are scored in
# ONE program — K/V for the whole window is written through the block
# table first (rows >= lengths[b] route to the scratch page like any
# padded prefill row), then every query attends the GATHERED cache
# under the diagonal mask k_pos < start + 1 + row.  Reading the
# window's own keys back through the pools (quantized pools included)
# — rather than chaining a raw-suffix scan — is what makes each row
# bit-identical to the sequential single-query decode step it
# replaces: the decode path, too, quantizes-then-reads its own token.
# Rejected tokens' writes are garbage past the accepted length; every
# later read masks them and every later write overwrites them, the
# same contract stale page bytes already live under.
# ---------------------------------------------------------------------------


def paged_verify_attention(q, k_pool, v_pool, block_table, start, H):
    """Multi-query decode attention for a verify window.

    q (B, W, H·D) at absolute positions ``start[b] + i`` (window K/V
    already written); returns (B, W, H·D), each row bit-identical
    (lax path) to the single-query paged decode at length
    ``start[b] + i + 1`` over the same pool bytes."""
    from . import pallas_kernels as pk

    KVB = k_pool.shape[1]
    if pk.paged_enabled(k_pool.shape[2]):
        return pk.paged_attention_verify(q, k_pool, v_pool, block_table,
                                         start, H)
    B, MB = block_table.shape
    D = k_pool.shape[2] // H
    kg = k_pool[block_table].reshape(B, MB * KVB, H, D)
    vg = v_pool[block_table].reshape(B, MB * KVB, H, D)
    o, m, l = _blockwise_attention_partial_lax(
        _heads(q, H), kg, vg, False, KVB, 0, lengths=start + 1,
        diagonal=True)
    return _rows(normalize_attention_state(o, m, l, q.dtype))


def paged_verify_attention_q(q, k_pool, v_pool, k_scale, v_scale,
                             block_table, start):
    """Quantized verify window: dequantize the gathered cache to fp32
    (window keys included — matching the quantized decode step, which
    also reads its own token back through the pools), then run the
    diagonal-masked blockwise body with fp32 softmax accumulation."""
    KVB = k_pool.shape[1]
    B, MB = block_table.shape
    H = k_scale.shape[2]
    D = k_pool.shape[2] // H
    kg = dequantize_kv(k_pool[block_table].reshape(B, MB * KVB, H, D),
                       k_scale[block_table].reshape(B, MB * KVB, H))
    vg = dequantize_kv(v_pool[block_table].reshape(B, MB * KVB, H, D),
                       v_scale[block_table].reshape(B, MB * KVB, H))
    o, m, l = _blockwise_attention_partial_lax(
        _heads(q, H), kg, vg, False, KVB, 0, lengths=start + 1,
        diagonal=True)
    return _rows(normalize_attention_state(o, m, l, q.dtype))


@register("QKVPagedVerifyAttend",
          arg_names=("qkv", "k_pool", "v_pool", "block_table", "start",
                     "lengths"),
          out_names=("output", "new_k_pool", "new_v_pool"),
          infer_shape=_paged_qkv_infer("QKVPagedVerifyAttend", 2),
          doc="Speculative-verify decode step over the paged cache: "
              "qkv (B, W, 3*H*D) holds the pending token plus k draft "
              "tokens at absolute positions start[b]+i; their K/V is "
              "written through the block table at that offset (rows "
              ">= lengths[b] land on the scratch page) and each query "
              "attends the gathered cache under the diagonal mask "
              "k_pos < start+1+row — row i bit-identical (lax path) "
              "to the single-query decode at length start+1+i.  start "
              "(B,) int32 tokens already cached, lengths (B,) int32 "
              "start + live window rows; attrs: num_heads")
def _qkv_paged_verify_attend(op_ctx, attrs, inputs, aux):
    qkv, k_pool, v_pool, block_table, start, lengths = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    q, k, v = _split_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    start = start.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    new_kp, new_vp = paged_prefill_write(
        k, v, k_pool, v_pool, block_table, lengths, start=start)
    out = paged_verify_attention(q, new_kp, new_vp, block_table, start,
                                 H)
    return [out, new_kp, new_vp]


@register("QKVPagedVerifyAttendQ",
          arg_names=("qkv", "k_pool", "v_pool", "k_scale", "v_scale",
                     "block_table", "start", "lengths"),
          out_names=("output", "new_k_pool", "new_v_pool",
                     "new_k_scale", "new_v_scale"),
          infer_shape=_paged_qkv_infer("QKVPagedVerifyAttendQ", 4),
          doc="QKVPagedVerifyAttend over QUANTIZED pools: the window "
              "quantizes on write and every query reads the gathered, "
              "dequantized cache (its own window keys included — the "
              "quantized decode step's read path), fp32 softmax "
              "accumulation; attrs: num_heads")
def _qkv_paged_verify_attend_q(op_ctx, attrs, inputs, aux):
    (qkv, k_pool, v_pool, k_scale, v_scale, block_table, start,
     lengths) = inputs
    H = attr_int(attrs.get("num_heads", 1), 1)
    q, k, v = _split_qkv(qkv, H)
    lengths = lengths.astype(jnp.int32)
    start = start.astype(jnp.int32)
    block_table = block_table.astype(jnp.int32)
    new_pools = paged_prefill_write_q(
        k, v, k_pool, v_pool, k_scale, v_scale, block_table, lengths,
        start=start)
    out = paged_verify_attention_q(q, *new_pools, block_table, start)
    return [out, *new_pools]


@register("DotProductAttention", arg_names=("query", "key", "value"),
          infer_shape=_attention_infer,
          aliases=("MultiHeadAttention",),
          doc="Fused blockwise multi-head attention: (B, T, H, D) "
              "q/k/v -> (B, T, H, D); attrs: causal, block_size, "
              "layout ('BTHD' default | 'BHTD' — the TPU-native "
              "transpose-free layout)")
def _attention(op_ctx, attrs, inputs, aux):
    q, k, v = inputs
    if q.ndim != 4:
        raise MXNetError("DotProductAttention expects 4-D inputs")
    causal = attr_bool(attrs.get("causal", False), False)
    block = attr_int(attrs.get("block_size", 0), 0)
    layout = str(attrs.get("layout", "BTHD"))
    if layout not in ("BTHD", "BHTD"):
        raise MXNetError(f"unknown attention layout {layout!r}")
    return [blockwise_attention(q, k, v, causal=causal, block_size=block,
                                layout=layout)]
