"""Paged KV-cache bookkeeping for autoregressive serving.

The device side of the paged cache is two pool arrays per layer —
``k_pool``/``v_pool`` of shape ``(num_blocks, block_tokens, H·D)``
(:func:`value_pool_shape`: lane-dense, a head is a D-lane span of a
row) — updated functionally inside the decode program
(``ops/attention.py`` ``QKVPagedAttentionDecode`` /
``PagedCacheWrite``, donated under jit).  This module is the HOST
side: which pages belong to which stream.

Design (PagedAttention, Kwon et al. SOSP '23):

* device memory is carved into fixed-size **token blocks** (pages);
  a stream holds ``ceil(tokens / block_tokens)`` of them, so memory
  scales with tokens actually cached, not ``max_len x max_streams``;
* the **block table** maps a stream's logical block index to a page
  id; pages are handed out from a free list in any order, so
  interleaved alloc/free (churning streams) fragments the *table*,
  never the memory;
* **page 0 is reserved scratch**: padded batch slots and padded
  prompt positions write there, which keeps every scatter in the
  decode program mask-free — reads of scratch are always masked by
  the per-stream length;
* a page id addresses the same page in the pools of every layer of one
  KIND.  Layers that see only the last W keys (a sliding window) keep
  theirs in pools of a second kind, ``window_pages``: a second
  :class:`BlockAllocator` with page ids of its own and a table of its
  own, in which a stream holds the pages its window still reaches and
  frees each page that falls wholly behind it (``DecodeEngine``; the
  entries of given-back blocks are the scratch page).

Prefix sharing (RadixAttention, Zheng et al. '23) adds **reference
counting**: a page holding a fully-written block of a common prompt
prefix may back several streams at once.  ``share``/``release`` move
a page's refcount; a page whose count reaches zero while the prefix
index still maps its content is **parked** (``release(...,
park=True)``) — it keeps its bytes and can be revived on the next
prefix hit, or reclaimed (``reclaim``) when the pool runs dry.  A
page referenced by N streams occupies ONE slot and is counted once
everywhere (``used_blocks`` / ``cache_util``); parked pages count as
free capacity because they are reclaimable on demand.

The allocator is intentionally dumb and exact: a LIFO free list and
integer arithmetic, no heuristics.  Admission control, preemption and
the eviction *policy* live in :class:`mxnet_tpu.serving.DecodeEngine`
and :class:`mxnet_tpu.prefix_cache.PrefixCache`; the
``serving.cache_util`` gauge is maintained here so every alloc/free
updates it.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from . import profiler
from .base import MXNetError

__all__ = ["BlockAllocator", "SlotAllocator", "blocks_for_tokens",
           "bucket_ladder", "trim_blocks", "kv_storage_dtype",
           "kv_quantized", "pool_device_bytes", "value_pool_shape",
           "latent_pool_shape", "state_pool_shape", "conv_tail_shape", "KV_DTYPES", "KV_QMAX"]

SCRATCH_PAGE = 0

# MXNET_SERVING_KV_DTYPE vocabulary.  fp32 is the bit-exact reference;
# bf16 is a plain narrow-float cast (no scales); int8/fp8 store
# quantized values plus per-slot-per-head float32 scales, dequantized
# inside the decode attention (fp32 softmax accumulation throughout —
# the PR-3 bf16-gradient-wire precedent: lossy storage, exact math).
KV_DTYPES = ("fp32", "bf16", "int8", "fp8")
KV_QMAX = {"int8": 127.0, "fp8": 448.0}  # e4m3 finite max


def kv_quantized(name: str) -> bool:
    """Does this KV storage dtype carry per-slot scale pools?"""
    return name in KV_QMAX


def kv_storage_dtype(name: str) -> np.dtype:
    """Numpy dtype backing the device K/V pools for a
    ``MXNET_SERVING_KV_DTYPE`` name; unknown names raise loudly at
    engine construction."""
    if name == "fp32":
        return np.dtype(np.float32)
    if name == "bf16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if name == "int8":
        return np.dtype(np.int8)
    if name == "fp8":
        try:
            import ml_dtypes
            return np.dtype(ml_dtypes.float8_e4m3fn)
        except (ImportError, AttributeError):
            raise MXNetError(
                "MXNET_SERVING_KV_DTYPE=fp8 needs ml_dtypes with "
                "float8_e4m3fn; use int8 or bf16 on this toolchain")
    raise MXNetError(
        f"unknown KV cache dtype {name!r} (MXNET_SERVING_KV_DTYPE "
        f"wants one of {KV_DTYPES})")


def value_pool_shape(pages: int, kv_block: int, num_heads: int,
                     d_head: int) -> tuple:
    """THE shape of a K or V value pool: ``(pages, kv_block, H·D)``.

    Lane-dense — a token's row holds its heads side by side, head h on
    lanes ``[h·D, (h+1)·D)``, exactly as the fused QKV projection the
    row was cut from.  Not ``(…, H, D)``: D = 64 is half a 128-lane
    tile, so the TPU backend has no tiled row-major layout for such a
    pool without padding (20 x 64 pads to 32 x 128, 3.2 x the bytes);
    it holds it pages-minor instead, and every program that scatters
    into it or hands it to the paged kernel re-lays-out the WHOLE pool
    on the way in and again on the way out (4 pool-sized copies a
    layer a program, PERF.md §6 PR 26).  At H·D a multiple of 128 a
    page is whole tiles; elsewhere the code is the same and the
    backend picks the layout.  The shape fits any head count; the
    paged KERNEL over it does not — its all-heads form holds
    W·H²·D-sized state in VMEM and refuses, by name, what exceeds it
    (``ops/pallas_kernels._paged_attention``: every GPT-2 size fits,
    64 heads x 128 with a 5-row window does not).  Everything that
    builds, checks or ships a pool takes its shape from here; the
    quantized engines' float32 scale pools stay
    ``(pages, kv_block, H)``."""
    return (int(pages), int(kv_block), int(num_heads) * int(d_head))


LANE_TILE = 128


def latent_pool_shape(pages: int, kv_block: int, kv_rank: int,
                      rope_dim: int) -> tuple:
    """The shape of a latent-attention layer's ONE pool: ``(pages,
    kv_block, lanes)``, a token's row = [the compressed latent
    (``kv_rank``) | its rotated positional key (``rope_dim``) | zeros]
    with ``lanes`` the next whole number of 128-lane tiles: 512 + 64 =
    576 values are 4.5 tiles and are held as 640 (11% padding), because
    the paged kernels copy a page's rows in whole lane tiles
    (``ops/pallas_kernels.paged_enabled``) and a row that is one span of
    one array is one copy a page, one matmul a chunk — the zero lanes
    add nothing to a score and are never read as a value."""
    need = int(kv_rank) + int(rope_dim)
    return (int(pages), int(kv_block), -(-need // LANE_TILE) * LANE_TILE)


def state_pool_shape(slots: int, head_state) -> tuple:
    """Shape of a recurrent layer's STATE pool: per slot the mixer's
    ``head_state`` = (heads, rows, lanes) float32 stack of matrices —
    the second kind of per-stream state, beside the K/V pages.  What a
    head's matrix is, is the mixer's to say (``models/hybrid_lm.py
    mixer_state``): a kda head's (d_v, d_k), held transposed; a mamba2
    head's (head_dim, d_state), not square; a retention KV head's packed
    symmetric square, ((head_dim / 2 + 1) * head_dim, head_dim): block
    ``delta`` of head_dim rows holds the pairs of key lanes ``delta``
    apart, transposed (value lane by key lane) — 8,320 rows at 128,
    shared by the KV head's query heads (``ops/pallas_hybrid.py``).  The
    same shape serves a mixer's AUXILIARY array where that is no
    convolution's tail (:func:`conv_tail_shape`) but another stack of
    matrices: the retention mixer's normaliser, (kv_heads, head_dim,
    head_dim).  A stream holds ONE slot from admission to retirement,
    whatever its length; slot 0 is scratch (padded batch rows land
    there), so ``slots`` = live streams + 1."""
    heads, rows, lanes = (int(n) for n in head_state)
    return (int(slots), heads, rows, lanes)


def conv_tail_shape(slots: int, kernel: int, channels: int) -> tuple:
    """Shape of a short convolution's carried inputs: per slot (slot 0
    scratch) the last ``kernel - 1`` rows of ``channels``, back to back
    in ONE run of (kernel - 1) * channels numbers held as whole
    (8, 128)-tiles: (slots, 8, W), W the run's eighth rounded up to
    whole lanes.  As (slots, kernel - 1, channels) the 3-row middle
    axis has no unpadded tiled layout and every decode step copied the
    pool twice round its scatter; a slot of whole tiles is written in
    place, one DMA (the value pools' lesson, :func:`value_pool_shape`)."""
    run = (int(kernel) - 1) * int(channels)
    return (int(slots), 8, -(-run // 1024) * 128)


def pool_device_bytes(cache_blocks: int, kv_block: int,
                      num_layers: int, num_heads: int, d_model: int,
                      kv_dtype: str = "fp32", tp: int = 1,
                      pp: int = 1, latent_row=None) -> int:
    """Bytes of K/V pool (values + quantization scales) EACH device
    holds for a serving engine meshed ``tp x pp``: the stacked layer
    dim shards over 'pp' (stage-resident slabs) and the head dim over
    'tp', so per-device bytes fall as 1/(tp*pp).  ``tp=pp=1`` is the
    single-device total — capacity planners compare the two to
    prove that a model's pool doesn't fit one chip before they
    shard it.  ``latent_row`` = (kv_rank, rope_dim): the layers keep one
    latent row a token (:func:`latent_pool_shape`: ONE pool a layer,
    shared by all heads — nothing for ``tp`` to cut) in place of K and V
    rows of ``d_model``."""
    if latent_row is not None:
        pool = latent_pool_shape(cache_blocks, kv_block, *latent_row)
        return int(num_layers) * int(np.prod(pool)) \
            * kv_storage_dtype(kv_dtype).itemsize // int(pp)
    pool = value_pool_shape(cache_blocks, kv_block, num_heads,
                            int(d_model) // int(num_heads))
    per_layer = int(np.prod(pool)) * kv_storage_dtype(kv_dtype).itemsize
    if kv_quantized(kv_dtype):
        # per-slot-per-head float32 scales, (pages, kv_block, H)
        per_layer += int(np.prod(pool[:2])) * int(num_heads) * 4
    return 2 * int(num_layers) * per_layer // (int(tp) * int(pp))


def blocks_for_tokens(tokens: int, block_tokens: int) -> int:
    """Pages needed to hold ``tokens`` cache entries.

    Edge contract: ``blocks_for_tokens(0, b) == 0`` — an empty suffix
    (a fully prefix-cached prompt) needs no new pages, and
    ``alloc(0)`` returns an empty page list rather than failing.
    Negative token counts are a caller bug and raise."""
    tokens = int(tokens)
    if tokens < 0:
        raise MXNetError(f"blocks_for_tokens({tokens}): negative")
    return -(-tokens // int(block_tokens))


def trim_blocks(blocks: List[int], tokens: int, block_tokens: int):
    """Tail-length accounting after a speculative-verify rollback:
    split a stream's page list into (keep, surplus) where ``keep``
    covers ``tokens`` cache slots and ``surplus`` is everything past
    it — pages the verify step allocated for draft tokens that were
    then rejected.  The surplus pages hold only garbage window writes
    (every read of them is length-masked, every future write
    overwrites before any read), so returning them to the pool is
    safe; callers release them so shared-pool accounting stays
    truthful mid-generation instead of only at retire.  Page order is
    positional (page j holds slots [j*B, (j+1)*B)), so the split is a
    plain prefix split."""
    keep = blocks_for_tokens(tokens, block_tokens)
    if keep >= len(blocks):
        return blocks, []
    return blocks[:keep], blocks[keep:]


def bucket_ladder(max_value: int, base: int = 1) -> List[int]:
    """Doubling ladder ``base, 2*base, ...`` capped at (and always
    including) ``max_value`` — the executable-cache bucketing shape
    used for batch sizes, cache blocks and prefill lengths.

    Edge contract: ``max_value < 1`` raises loudly — a ladder must
    contain at least one positive bucket (downstream validation
    rejects ``[0]`` anyway, but the diagnosis belongs here, at the
    sizing bug, not at engine construction)."""
    if int(max_value) < 1:
        raise MXNetError(
            f"bucket_ladder({max_value}): a bucket ladder needs a "
            f"positive top — zero-token work is the 0-page path "
            f"(blocks_for_tokens(0) == 0), not a bucket")
    out = []
    v = max(1, int(base))
    while v < max_value:
        out.append(v)
        v *= 2
    out.append(int(max_value))
    return out


class SlotAllocator:
    """Which state slot belongs to which stream: slots 1..n handed out
    from a LIFO free list, slot 0 reserved scratch.  A slot has one
    owner at a time; freeing one that is not held is an error."""

    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise MXNetError(f"state slots {num_slots} must be >= 1")
        self.num_slots = int(num_slots)
        self._free = list(range(self.num_slots, 0, -1))
        self._owner: Dict[int, object] = {}

    @property
    def live(self) -> int:
        return len(self._owner)

    def owner(self, slot: int):
        return self._owner.get(slot)

    def alloc(self, owner=None) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._owner[slot] = owner
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._owner:
            raise MXNetError(f"state slot {slot} is not held")
        del self._owner[slot]
        self._free.append(slot)


class BlockAllocator:
    """Ref-counted free-list allocator over ``num_blocks`` fixed-size
    token pages.

    Page 0 is reserved as the shared scratch page and never handed
    out.  ``alloc`` is all-or-nothing: a request that cannot be fully
    satisfied takes nothing (the caller decides whether to preempt,
    queue, or shrink).  Pages come back at refcount 1; ``share``
    attaches another holder, ``release`` detaches one.  A released
    page either returns to the free list or — ``park=True`` — keeps
    its bytes as reclaimable cache.

    ``gauge_prefix`` names the profiler gauge family this allocator
    maintains (default: the KV pool's ``serving.cache*``).  A second
    allocator in the same process — the LoRA adapter-slot pool reuses
    this exact machinery with "pages" = adapter slots — must pass its
    own prefix or the two would silently clobber each other's gauges."""

    def __init__(self, num_blocks: int, block_tokens: int,
                 gauge_prefix: str = "serving"):
        if num_blocks < 2:
            raise MXNetError(
                f"BlockAllocator needs >= 2 blocks (1 scratch + 1 "
                f"usable); got {num_blocks}")
        if block_tokens < 1:
            raise MXNetError(f"bad block_tokens {block_tokens}")
        self.num_blocks = int(num_blocks)
        self.block_tokens = int(block_tokens)
        self._gauge_prefix = str(gauge_prefix)
        # LIFO free list: recently-freed (likely still cache-warm)
        # pages are reused first
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._owner: Dict[int, object] = {}  # page -> stream tag
        self._refs: Dict[int, int] = {}      # page -> holder count
        self._parked: set = set()            # refcount-0 cached pages
        self._update_gauges()

    # ------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the scratch page)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        """Pages available to a new allocation: truly free ones plus
        parked (refcount-0 cached) ones, which are reclaimable on
        demand.  A page shared by N streams is ABSENT from this count
        exactly once — sharing never inflates apparent capacity."""
        return len(self._free) + len(self._parked)

    @property
    def used_blocks(self) -> int:
        """Pages some stream actively references (refcount >= 1).
        N streams on one page count it ONCE."""
        return self.capacity - self.free_blocks

    @property
    def free_list_blocks(self) -> int:
        """Pages immediately allocatable without an eviction."""
        return len(self._free)

    @property
    def parked_blocks(self) -> int:
        """Refcount-0 cached pages awaiting revival or reclaim."""
        return len(self._parked)

    @property
    def shared_blocks(self) -> int:
        """Pages currently referenced by MORE than one stream."""
        return sum(1 for r in self._refs.values() if r > 1)

    def utilization(self) -> float:
        return self.used_blocks / self.capacity if self.capacity else 0.0

    def can_fit(self, tokens: int) -> bool:
        return blocks_for_tokens(tokens, self.block_tokens) \
            <= self.free_blocks

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def is_parked(self, page: int) -> bool:
        return page in self._parked

    # ------------------------------------------------------------------
    def alloc(self, n: int, owner=None) -> Optional[List[int]]:
        """Take ``n`` pages at refcount 1, or None (and take nothing)
        if they are not all available from the free list.  Parked
        pages are NOT taken implicitly — the caller (the prefix
        cache's eviction policy) must ``reclaim`` them first, so an
        eviction is always an explicit, countable decision."""
        if n < 0:
            raise MXNetError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._owner[p] = owner
            self._refs[p] = 1
        self._update_gauges()
        return pages

    def share(self, page: int) -> int:
        """Attach one more holder to a live page; returns the new
        refcount."""
        if page not in self._refs:
            raise MXNetError(f"share of non-live page {page}")
        self._refs[page] += 1
        self._update_gauges()
        return self._refs[page]

    def revive(self, page: int, owner=None) -> None:
        """Re-activate a parked page at refcount 1 (a prefix hit on a
        cached page no stream currently holds)."""
        if page not in self._parked:
            raise MXNetError(f"revive of non-parked page {page} "
                             f"(parked: {sorted(self._parked)})")
        self._parked.discard(page)
        self._owner[page] = owner
        self._refs[page] = 1
        self._update_gauges()

    def release(self, page: int, park: bool = False) -> int:
        """Detach one holder; returns the remaining refcount.  At zero
        the page returns to the free list, or — ``park=True`` — keeps
        its bytes as reclaimable cache (the prefix index still maps
        its content)."""
        if page not in self._refs:
            raise MXNetError(f"release of non-live page {page}")
        self._refs[page] -= 1
        left = self._refs[page]
        if left == 0:
            del self._refs[page]
            del self._owner[page]
            if park:
                self._parked.add(page)
            else:
                self._free.append(page)
        self._update_gauges()
        return left

    def reclaim(self, page: int) -> None:
        """Move a parked page to the free list (the prefix index has
        dropped its entry — an eviction)."""
        if page not in self._parked:
            raise MXNetError(f"reclaim of non-parked page {page}")
        self._parked.discard(page)
        self._free.append(page)
        self._update_gauges()

    def export_pages(self, pages: List[int]) -> int:
        """Detach EXCLUSIVELY-held pages whose bytes have been shipped
        to another pool (live KV migration, see ``fleet.Router``
        roles).  The slots return to the free list — the data now
        lives on the importing replica — but the operation is audited
        separately from :meth:`free`: the ``pages_exported`` counter is
        what reconciles a disaggregated fleet's page movement.

        A shared or parked page refuses loudly: migration ships a
        stream's PRIVATE tail, and a page the prefix index (or another
        stream) still maps must be detached from the index first
        (``PrefixCache.detach``) or merely released, never exported.
        Returns the number of pages exported."""
        for p in pages:
            if p == SCRATCH_PAGE:
                raise MXNetError("attempt to export the scratch page")
            if p in self._parked:
                raise MXNetError(
                    f"export of parked page {p} — reclaim/revive it "
                    f"first; a parked page has no owning stream")
            if p not in self._owner:
                raise MXNetError(
                    f"export of non-live page {p} (owned pages: "
                    f"{sorted(self._owner)})")
            if self._refs.get(p, 0) > 1:
                raise MXNetError(
                    f"export of page {p} with {self._refs[p]} live "
                    f"references — another stream still reads it; "
                    f"detach it from the prefix index or release() "
                    f"this stream's reference instead")
        for p in pages:
            del self._owner[p]
            self._refs.pop(p, None)
            self._free.append(p)
        profiler.inc_counter("serving.kv_pages_exported", len(pages))
        self._update_gauges()
        return len(pages)

    def import_pages(self, n: int, owner=None) -> Optional[List[int]]:
        """Allocate ``n`` fresh pages to receive migrated KV bytes — a
        block-table splice target on the importing replica.  Same
        all-or-nothing contract as :meth:`alloc` (None = pool cannot
        take the stream right now; the caller preempts or refuses the
        migration), plus the ``pages_imported`` audit counter that
        mirrors the exporter's ``pages_exported``."""
        pages = self.alloc(n, owner=owner)
        if pages is not None:
            profiler.inc_counter("serving.kv_pages_imported",
                                 len(pages))
        return pages

    def free(self, pages: List[int]) -> None:
        """Terminal free of EXCLUSIVELY-held pages.  A page another
        stream still references raises loudly — returning it to the
        free list would hand the same page to a new stream while the
        sharer still reads it (silent cross-stream corruption).
        Shared pages go through :meth:`release` instead."""
        for p in pages:
            if p == SCRATCH_PAGE:
                raise MXNetError("attempt to free the scratch page")
            if p in self._parked:
                # cached, no holders: freeing it is a plain reclaim
                self._parked.discard(p)
                self._free.append(p)
                continue
            if p not in self._owner:
                raise MXNetError(
                    f"double free / foreign page {p} (owned pages: "
                    f"{sorted(self._owner)})")
            if self._refs.get(p, 0) > 1:
                raise MXNetError(
                    f"free of page {p} with {self._refs[p]} live "
                    f"references — another stream still reads it; "
                    f"release() the caller's reference instead")
            del self._owner[p]
            self._refs.pop(p, None)
            self._free.append(p)
        self._update_gauges()

    # ------------------------------------------------------------------
    def _update_gauges(self):
        pre = self._gauge_prefix
        profiler.set_gauge(f"{pre}.cache_blocks_used", self.used_blocks)
        profiler.set_gauge(f"{pre}.cache_blocks_free", self.free_blocks)
        profiler.set_gauge(f"{pre}.cache_blocks_cached",
                           self.parked_blocks)
        profiler.set_gauge(f"{pre}.shared_blocks", self.shared_blocks)
        profiler.set_gauge(f"{pre}.cache_util", self.utilization())
