"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference's long-sequence story is bucketing + recompute (SURVEY
§5.7); this module supplies the scale dimension the reference never
had: shard the SEQUENCE axis over a mesh axis so context length grows
linearly with chips.

* ``ring_attention``: each shard keeps its Q block resident and
  rotates K/V blocks around the ring with ``lax.ppermute`` (ICI
  neighbor exchanges), merging per-hop online-softmax partial states —
  compute overlaps the rotation, full (T, T) scores never exist, and
  per-chip memory is O(T/sp).
* ``ulysses_attention``: ``lax.all_to_all`` re-shards sequence ↔ heads
  so each chip runs full-sequence attention for H/sp heads, then
  a2a's back.  Cheaper collectives when heads ≥ sp; ring wins when a
  single head's full sequence no longer fits.

Both run inside ``shard_map`` over a ``Mesh`` built by
``sequence_mesh`` and are validated against single-device blockwise
attention on the virtual CPU mesh (tests/test_sequence.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .base import MXNetError
from .ops import pallas_kernels as _pk
from .ops.attention import (attention_state_init, attention_state_merge,
                            blockwise_attention,
                            blockwise_attention_partial,
                            normalize_attention_state)

__all__ = ["sequence_mesh", "ring_attention", "ulysses_attention"]


def _shard_map(f, mesh, in_specs, out_specs, check: bool):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def sequence_mesh(sp: Optional[int] = None, devices=None,
                  axis_name: str = "sp") -> Mesh:
    """A 1-D mesh over the sequence-parallel axis."""
    devices = list(devices if devices is not None else jax.devices())
    sp = sp or len(devices)
    if sp > len(devices):
        raise MXNetError(f"sp={sp} exceeds {len(devices)} devices")
    return Mesh(np.asarray(devices[:sp]), (axis_name,))


def _ring_attention_local(q, k, v, axis_name, causal, block_size,
                          q_offset):
    """shard_map body: q is the local (B, Tq/sp, H, D) shard, k/v the
    local (B, Tkv/sp, H, D) shards.  ``q_offset`` is the absolute K/V
    position of the GLOBAL q[0] — 0 for the classic self-attention
    layout (Tq == Tkv), the chunk start for the decode-time layout
    where q is one prefill chunk and k/v are the K/V gathered from the
    cache over everything written so far."""
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t_q = q.shape[1]
    t_kv = k.shape[1]
    q_start = q_offset + idx * t_q  # absolute position of local q[0]
    perm = [(i, (i + 1) % sp) for i in range(sp)]  # ring: send right

    def partial_for(k_cur, v_cur, src):
        kv_off = src * t_kv - q_start  # k_abs_start - q_abs_start
        return blockwise_attention_partial(
            q, k_cur, v_cur, causal=causal, block_size=block_size,
            kv_offset=kv_off)

    def merge_hop(state, k_cur, v_cur, src):
        o, m, l = state
        o2, m2, l2 = partial_for(k_cur, v_cur, src)
        return attention_state_merge(o, m, l, o2, m2, l2)

    def hop(carry, j):
        o, m, l, k_cur, v_cur = carry
        # rotate first: K/V for this hop come from shard (idx - j) mod sp
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        src = (idx - j) % sp
        if causal:
            # a shard whose first key is past this shard's LAST query
            # contributes nothing under the causal mask — skip its
            # whole attention compute (the q_offset shift keeps the
            # skip exact for the chunked decode-time layout too)
            o, m, l = lax.cond(
                src * t_kv > q_start + t_q - 1,
                lambda s, kc, vc, sr: s,
                lambda s, kc, vc, sr: merge_hop(s, kc, vc, sr),
                (o, m, l), k_cur, v_cur, src)
        else:
            o, m, l = merge_hop((o, m, l), k_cur, v_cur, src)
        return (o, m, l, k_cur, v_cur), None

    # hop 0 (the local shard) needs no rotation; hops 1..sp-1 rotate
    # then compute, so no collective's result is ever discarded
    state = merge_hop(attention_state_init(q), k, v, idx)
    (o, m, l, _, _), _ = lax.scan(hop, (*state, k, v),
                                  jnp.arange(1, sp))
    return normalize_attention_state(o, m, l, q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = False, block_size: int = 512,
                   q_offset=0):
    """Sequence-parallel attention: (B, T, H, D) global arrays with T
    sharded over ``axis_name``; returns same-sharded output.

    ``q_offset`` unlocks the decode-time K/V-gathered layout: q may be
    SHORTER than k/v (one chunk of a long prompt, Tq != Tkv) with its
    rows sitting at absolute K/V positions ``[q_offset, q_offset+Tq)``
    — the shape the chunked-prefill state machine feeds when a prompt
    outgrows one chip's prefill ladder (suffix chunk attends the whole
    gathered history).  Both T axes shard over ``axis_name``; causal
    masking and the future-shard skip shift by ``q_offset`` so the
    result is bit-identical to the same chunk's rows of a full causal
    forward."""
    spec = P(None, axis_name, None, None)
    fn = _shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, block_size=block_size,
                          q_offset=q_offset),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # the Pallas flash kernel's interpret-mode lowering (CPU tests)
        # mixes sp-varying operands with unvarying grid indices in its
        # block dynamic_slices; vma checking rejects that pairing, so
        # follow JAX's prescribed workaround — scoped to interpret mode
        # only, so native TPU runs and the lax path keep full checking
        check=not (_pk.enabled() and _pk._interpret()))
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name, causal, block_size, q_offset):
    """a2a: (B, T/sp, H, D) → (B, T, H/sp, D), attend, a2a back."""
    sp = lax.psum(1, axis_name)
    H = q.shape[2]
    if H % sp != 0:
        raise MXNetError(f"ulysses needs heads ({H}) divisible by sp ({sp})")

    def seq_to_heads(x):
        # split heads across the axis, gather the full sequence
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    plain = isinstance(q_offset, int) and q_offset == 0 \
        and q.shape[1] == k.shape[1]
    if plain:
        # full (non-ring) attention after the a2a: the normalized flash
        # kernel (in-kernel normalization + Pallas backward) — faster
        # than partial+normalize with the lax-remat backward
        out = blockwise_attention(qf, kf, vf, causal=causal,
                                  block_size=block_size)
    else:
        # decode-time layout (q is a chunk at q_offset into the K/V
        # timeline): kv_offset = k_abs_start - q_abs_start = -q_offset
        o, m, l = blockwise_attention_partial(
            qf, kf, vf, causal=causal, block_size=block_size or 512,
            kv_offset=-q_offset)
        out = normalize_attention_state(o, m, l, qf.dtype)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                      causal: bool = False, block_size: int = 512,
                      q_offset=0):
    """All-to-all sequence parallelism (Ulysses): T sharded in/out,
    heads sharded during the attention itself.  ``q_offset`` as in
    :func:`ring_attention` — the decode-time K/V-gathered layout with
    a chunked q (Tq != Tkv) at absolute offset ``q_offset``."""
    spec = P(None, axis_name, None, None)
    fn = _shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name,
                          causal=causal, block_size=block_size,
                          q_offset=q_offset),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check=not (_pk.enabled() and _pk._interpret()))
    return fn(q, k, v)
