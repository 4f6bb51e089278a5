"""Prefix-shared, quantized KV cache tests: ref-counted allocator
semantics (share/park/revive, loud free-of-shared), the radix
PrefixIndex, LRU eviction determinism, engine-level hit→attach→
diverge→evict behavior (bit-identity preserved under sharing — shared
pages are the same bytes), copy-on-write isolation, preemption of
shared pages, and the int8/fp8 quantized storage paths.

Tier-1 keeps one fast engine smoke per contract; the wide
quantization matrix and long shared-prefix sweeps are ``slow``.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kv_cache import (BlockAllocator, blocks_for_tokens,
                                bucket_ladder, kv_storage_dtype,
                                value_pool_shape)
from mxnet_tpu.prefix_cache import PrefixCache, PrefixIndex

from _engines import (KVB, WAIT, V,  # noqa: E402
                      dense_engine as _engine, tiny_lm_params,
                      tiny_lm_reference)


# ---------------------------------------------------------------------------
# edge contracts: the 0-token path
# ---------------------------------------------------------------------------


def test_zero_token_edge_contracts():
    """A fully prefix-cached prompt has an EMPTY uncached suffix:
    blocks_for_tokens(0) is 0 new pages (and alloc(0) == []), while a
    zero-topped bucket ladder is a sizing bug and raises loudly."""
    assert blocks_for_tokens(0, 4) == 0
    assert blocks_for_tokens(0, 1) == 0
    with pytest.raises(mx.MXNetError, match="negative"):
        blocks_for_tokens(-1, 4)
    a = BlockAllocator(5, 4)
    assert a.alloc(0, owner="s") == []
    assert a.free_blocks == 4
    with pytest.raises(mx.MXNetError, match="positive"):
        bucket_ladder(0)
    with pytest.raises(mx.MXNetError, match="positive"):
        bucket_ladder(-3)


# ---------------------------------------------------------------------------
# ref-counted allocator
# ---------------------------------------------------------------------------


def test_allocator_share_release_park_revive():
    a = BlockAllocator(6, 4)  # 5 usable
    (p,) = a.alloc(1, owner="A")
    assert a.refcount(p) == 1 and a.used_blocks == 1
    assert a.share(p) == 2
    assert a.shared_blocks == 1
    # a page referenced by two streams counts ONCE
    assert a.used_blocks == 1 and a.free_blocks == 4
    assert a.release(p) == 1
    assert a.shared_blocks == 0
    # last holder parks it (the index still maps its bytes)
    assert a.release(p, park=True) == 0
    assert a.is_parked(p) and a.parked_blocks == 1
    # parked pages count as reclaimable capacity, not as used
    assert a.free_blocks == 5 and a.used_blocks == 0
    # a prefix hit revives it at refcount 1
    a.revive(p, owner="B")
    assert a.refcount(p) == 1 and not a.is_parked(p)
    # reclaim only applies to parked pages
    with pytest.raises(mx.MXNetError, match="non-parked"):
        a.reclaim(p)
    a.release(p, park=True)
    a.reclaim(p)
    assert a.free_blocks == 5 and a.parked_blocks == 0


def test_allocator_free_of_shared_page_raises():
    """The satellite contract: free() of a page another stream still
    references raises loudly instead of corrupting the free list."""
    a = BlockAllocator(6, 4)
    (p,) = a.alloc(1, owner="A")
    a.share(p)
    with pytest.raises(mx.MXNetError, match="live references"):
        a.free([p])
    assert a.refcount(p) == 2  # nothing changed
    a.release(p)
    a.free([p])  # exclusive again: terminal free works
    assert a.free_blocks == 5
    with pytest.raises(mx.MXNetError, match="double free|foreign"):
        a.free([p])
    # freeing a parked page is a plain reclaim
    (q,) = a.alloc(1, owner="B")
    a.release(q, park=True)
    a.free([q])
    assert a.free_blocks == 5


# ---------------------------------------------------------------------------
# radix index
# ---------------------------------------------------------------------------


def _toks(*vals):
    return np.asarray(vals, np.int32)


def test_prefix_index_match_insert_remove():
    ix = PrefixIndex(4)
    t = _toks(*range(1, 13))  # 3 full blocks
    assert ix.match(t) == []
    created = ix.insert(t, [5, 6, 7], 3)
    assert len(created) == 3 and len(ix) == 3
    # longest-prefix match: full chain, then a diverging suffix
    chain = ix.match(t)
    assert [n.page for n in chain] == [5, 6, 7]
    t2 = np.concatenate([t[:8], _toks(99, 98, 97, 96)])
    chain = ix.match(t2)
    assert [n.page for n in chain] == [5, 6]
    # a 7-token prompt only has one FULL block
    assert [n.page for n in ix.match(t[:7])] == [5]
    # duplicate insert keeps the incumbent pages
    assert ix.insert(t, [50, 60, 70], 3) == []
    assert [n.page for n in ix.match(t)] == [5, 6, 7]
    # interior removal refuses; leaf removal unlinks
    with pytest.raises(mx.MXNetError, match="interior"):
        ix.remove(chain[0])
    leaf = ix.match(t)[-1]
    ix.remove(leaf)
    assert [n.page for n in ix.match(t)] == [5, 6]


def test_prefix_cache_attach_register_release_evict_lru():
    a = BlockAllocator(8, 4)  # 7 usable
    pc = PrefixCache(a, policy="lru")
    t = _toks(*range(1, 11))  # 10 tokens: 2 full blocks + tail
    pages = pc.alloc(3, owner="A")
    pc.register(t, pages)  # only the 2 FULL blocks index
    assert pc.stats()["indexed_blocks"] == 2
    # B attaches the cached prefix: refcounts bump, ONE hit counted
    cached, got = pc.attach(t, owner="B")
    assert cached == 8 and got == pages[:2]
    assert a.refcount(pages[0]) == 2
    assert pc.hits == 1 and pc.hit_tokens == 8
    # "preemption frees only its private refs": B releases — A's refs
    # survive, nothing parks, nothing frees
    pc.release(got)
    assert a.refcount(pages[0]) == 1
    # A retires: indexed pages park, the private tail frees
    pc.release(pages)
    assert a.parked_blocks == 2 and a.free_blocks == 7
    # a fresh attach revives parked pages
    cached, got = pc.attach(t, owner="C")
    assert cached == 8 and a.refcount(pages[0]) == 1
    pc.release(got)
    # pressure: 7 usable, 2 parked — asking for 6 must evict LRU
    out = pc.alloc(6, owner="D")
    assert out is not None and len(out) == 6
    assert pc.evictions >= 1
    assert pc.stats()["indexed_blocks"] < 2


def test_prefix_cache_eviction_lru_order_deterministic():
    a = BlockAllocator(10, 4)  # 9 usable
    pc = PrefixCache(a, policy="lru")
    t1 = _toks(*range(1, 9))     # chain A: 2 blocks
    t2 = _toks(*range(21, 29))   # chain B: 2 blocks
    pa = pc.alloc(2, "A")
    pc.register(t1, pa)
    pb = pc.alloc(2, "B")
    pc.register(t2, pb)
    pc.release(pa)
    pc.release(pb)
    # touch chain A (a peek does NOT touch; an attach does)
    cached, got = pc.attach(t1, "C")
    pc.release(got)
    # eviction must take chain B first (least recently used), leaf
    # before parent — deepest page of B goes first
    assert pc.evict(1) == 1
    assert [n.page for n in pc.index.match(t2, touch=False)] == [pb[0]]
    assert pc.evict(1) == 1
    assert pc.index.match(t2, touch=False) == []
    # chain A survived both evictions
    assert [n.page for n in pc.index.match(t1, touch=False)] == pa
    assert pc.evictions == 2


def test_prefix_cache_policy_off_frees_immediately():
    a = BlockAllocator(6, 4)
    pc = PrefixCache(a, policy="off")
    t = _toks(*range(1, 9))
    pages = pc.alloc(2, "A")
    pc.register(t, pages)
    assert pc.needs_cow(pages[0])  # indexed while live
    pc.release(pages)
    # no retention: pages free, index entries dropped
    assert a.parked_blocks == 0 and a.free_blocks == 5
    assert pc.stats()["indexed_blocks"] == 0
    with pytest.raises(mx.MXNetError):
        PrefixCache(a, policy="banana")


def test_needs_cow_semantics():
    a = BlockAllocator(6, 4)
    pc = PrefixCache(a, policy="lru")
    (private,) = pc.alloc(1, "A")
    assert not pc.needs_cow(private)       # exclusive, unindexed
    (shared,) = pc.alloc(1, "A")
    a.share(shared)
    assert pc.needs_cow(shared)            # two holders
    t = _toks(1, 2, 3, 4)
    (indexed,) = pc.alloc(1, "B")
    pc.register(t, [indexed])
    assert pc.needs_cow(indexed)           # ref 1 but index-mapped


# ---------------------------------------------------------------------------
# engine integration: the tiny-LM fixture (test_decode's pattern)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    """-> (params, naive_generate).  Every engine below is its test's
    own: what the prefix cache holds from one request to the next is the
    state under test."""
    params = tiny_lm_params()
    return params, tiny_lm_reference(params)[1]


def test_engine_smoke_hit_attach_diverge_evict(lm):
    """The tier-1 smoke (<5s): miss → suffix-only hit → full hit
    (COW) → diverge → evict under pressure → repeat the first prompt
    and get the SAME tokens back — engine-only, no full-forward
    recompiles (the naive bit-identity lives in its own test)."""
    params, _ = lm
    shared = np.arange(1, 9, dtype=np.int32)        # 2 full blocks
    pa = np.concatenate([shared, [11, 12, 13]])     # 11 tokens
    pb = np.concatenate([shared, [21, 22]])         # diverges after 8
    with _engine(params, cache_blocks=7) as eng:    # 6 usable pages
        a1 = eng.generate(pa, 4)                    # miss
        st = eng.stats()
        assert st["prefix_hits"] == 0
        assert st["prefill_tokens"] == 11
        assert st["cache_blocks_cached"] == 2       # parked, bytes kept
        b1 = eng.generate(pb, 4)                    # suffix-only hit
        st = eng.stats()
        assert st["prefix_hits"] == 1
        assert st["prefix_hit_tokens"] == 8
        assert st["prefill_tokens"] == 11 + 2       # suffix only
        assert st["ttft_hit_p50_ms"] is not None
        assert st["ttft_miss_p50_ms"] is not None
        assert b1.shape == (4,)  # diverged suffix decoded fine
        # full hit: block-aligned prompt == the cached chain → prefill
        # SKIPPED entirely; the replayed tail write triggers ONE COW
        eng.generate(shared, 4)
        st = eng.stats()
        assert st["prefix_full_hits"] == 1
        assert st["prefills"] == 2            # unchanged by the hit
        assert st["prefill_tokens"] == 13     # no new prefill tokens
        assert st["cow_copies"] == 1
        # pressure: a disjoint prompt needing the whole pool — its
        # decode growth drains the free list and evicts the parked
        # chain LRU
        big = np.arange(40, 56, dtype=np.int32)  # 16 tokens, 4 pages
        eng.generate(big, 4)
        st = eng.stats()
        assert st["evictions"] >= 1
        assert st["cache_util"] == 0.0        # truthful: all retired
    assert st["generations"] == 4
    assert a1.shape == (4,)


def test_engine_prefix_hit_bitwise_vs_full_forward(lm):
    """Bit-identity PRESERVED with the prefix cache on: a suffix-only
    hit's generation equals the naive full-causal-forward chain to
    the last bit (shared pages are the same bytes)."""
    params, naive = lm
    shared = np.arange(1, 9, dtype=np.int32)
    pa = np.concatenate([shared, [11, 12, 13]])
    pb = np.concatenate([shared, [21, 22]])
    with _engine(params) as eng:
        a = eng.generate(pa, 4)                # miss
        b = eng.generate(pb, 4)                # suffix-only hit
        st = eng.stats()
    assert st["prefix_hits"] == 1
    np.testing.assert_array_equal(a, naive(pa, 4))
    np.testing.assert_array_equal(b, naive(pb, 4))


def test_engine_cow_isolation_diverging_streams(lm):
    """Two streams sharing a full-hit prefix then sampling with
    different seeds never see each other's tokens: each bit-matches
    its own solo run."""
    params, _ = lm
    shared = np.arange(2, 10, dtype=np.int32)  # block-aligned 8
    solo = {}
    for sd in (7, 8):
        with _engine(params, seed=3) as eng:
            solo[sd] = eng.generate(shared, 6, temperature=0.8,
                                    seed=sd)
    with _engine(params, seed=3) as eng:
        eng.generate(shared, 2)  # seed the cache (greedy, retires)
        f1 = eng.submit(shared, 6, temperature=0.8, seed=7)
        f2 = eng.submit(shared, 6, temperature=0.8, seed=8)
        g1, g2 = f1.result(WAIT), f2.result(WAIT)
        st = eng.stats()
    np.testing.assert_array_equal(g1, solo[7])
    np.testing.assert_array_equal(g2, solo[8])
    assert st["prefix_hits"] >= 2
    assert st["cow_copies"] >= 2  # each full hit COWed its tail page


def test_engine_preemption_frees_only_private_refs(lm):
    """A preempted stream holding shared pages releases only its OWN
    references — the sharer keeps decoding on the same pages, and
    every output still bit-matches the naive chain."""
    params, naive = lm
    shared = np.arange(3, 11, dtype=np.int32)
    pa = np.concatenate([shared, [31, 32, 33]])
    pb = np.concatenate([shared, [41, 42, 43]])
    # 8 usable pages: two 11-token prompts (3 pages each) only coexist
    # through sharing; growth under decode forces preemption
    with _engine(params, cache_blocks=9, max_streams=2) as eng:
        f1 = eng.submit(pa, 10)
        f2 = eng.submit(pb, 10)
        g1, g2 = f1.result(WAIT), f2.result(WAIT)
        st = eng.stats()
    np.testing.assert_array_equal(g1, naive(pa, 10))
    np.testing.assert_array_equal(g2, naive(pb, 10))
    assert st["prefix_hits"] >= 1
    assert st["generations"] == 2


def test_engine_prefix_cache_off_matches_legacy(lm):
    """MXNET_SERVING_PREFIX_CACHE=0: exclusive-owner behavior — no
    sharing machinery in the stats, repeated prompts re-prefill, and
    output is bit-identical to the naive chain (the acceptance gate's
    baseline path)."""
    params, naive = lm
    p = np.arange(1, 9, dtype=np.int32)
    with _engine(params, prefix_cache=0) as eng:
        np.testing.assert_array_equal(eng.generate(p, 4), naive(p, 4))
        np.testing.assert_array_equal(eng.generate(p, 4), naive(p, 4))
        st = eng.stats()
    assert st["prefix_cache"] == 0
    assert "prefix_hits" not in st
    assert st["prefill_tokens"] == 16  # both prompts fully prefilled
    assert st["cache_blocks_cached"] == 0


def test_engine_env_validation(lm, monkeypatch):
    params, _ = lm
    monkeypatch.setenv("MXNET_SERVING_KV_DTYPE", "banana")
    with pytest.raises(mx.MXNetError, match="banana"):
        _engine(params)
    monkeypatch.delenv("MXNET_SERVING_KV_DTYPE")
    monkeypatch.setenv("MXNET_SERVING_EVICT", "mru")
    with pytest.raises(mx.MXNetError, match="mru"):
        _engine(params)
    monkeypatch.delenv("MXNET_SERVING_EVICT")
    monkeypatch.setenv("MXNET_SERVING_PREFIX_CACHE", "2")
    with pytest.raises(mx.MXNetError, match="0 or 1"):
        _engine(params)
    monkeypatch.setenv("MXNET_SERVING_PREFIX_CACHE", "banana")
    with pytest.raises(mx.MXNetError, match="integer"):
        _engine(params)


# ---------------------------------------------------------------------------
# quantized KV storage
# ---------------------------------------------------------------------------


def test_kv_storage_dtype_catalog():
    assert kv_storage_dtype("fp32") == np.float32
    assert kv_storage_dtype("int8") == np.int8
    assert kv_storage_dtype("bf16").itemsize == 2
    with pytest.raises(mx.MXNetError, match="unknown"):
        kv_storage_dtype("fp4")


def test_quantized_paged_ops_tolerance():
    """Op-level: int8/fp8 paged decode matches the fp32 reference
    within the documented tolerance on the lax path, and the
    interpret-mode Pallas kernel matches the lax dequant to float32's
    last bit."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import (paged_decode_attention,
                                         paged_decode_attention_q,
                                         paged_prefill_write,
                                         paged_prefill_write_q)

    rng = np.random.RandomState(0)
    B, Hh, D, NB, MB = 2, 2, 16, 8, 3
    k = rng.randn(B, 10, Hh * D).astype(np.float32)
    v = rng.randn(B, 10, Hh * D).astype(np.float32)
    q = rng.randn(B, 1, Hh * D).astype(np.float32)
    lengths = np.asarray([10, 7], np.int32)
    table = np.asarray([[1, 2, 3], [4, 5, 0]], np.int32)

    pool = value_pool_shape(NB, KVB, Hh, D)
    kp = jnp.zeros(pool)
    vp = jnp.zeros(pool)
    kp, vp = paged_prefill_write(jnp.asarray(k), jnp.asarray(v), kp, vp,
                                 jnp.asarray(table),
                                 jnp.asarray(lengths))
    ref = paged_decode_attention(jnp.asarray(q), kp, vp,
                                 jnp.asarray(table),
                                 jnp.asarray(lengths), Hh)
    for name, tol in (("int8", 0.02), ("fp8", 0.06)):
        dt = jnp.dtype(kv_storage_dtype(name))
        kq = jnp.zeros(pool, dt)
        vq = jnp.zeros(pool, dt)
        ks = jnp.ones((NB, KVB, Hh))
        vs = jnp.ones((NB, KVB, Hh))
        kq, vq, ks, vs = paged_prefill_write_q(
            jnp.asarray(k), jnp.asarray(v), kq, vq, ks, vs,
            jnp.asarray(table), jnp.asarray(lengths))
        out = paged_decode_attention_q(jnp.asarray(q), kq, vq, ks, vs,
                                       jnp.asarray(table),
                                       jnp.asarray(lengths))
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < tol, (name, err)
        # interpret-mode Pallas kernel == lax dequant to the last bit
        # of float32 (1e-6, what the unquantized kernel is held to):
        # both contract the SAME float32 dequantized values, but the
        # lane-dense kernel sums a head's D products along a row of
        # H·D lanes (the other heads' are exact zeros), in another
        # order than the fallback's (..., D) contraction
        import os
        os.environ["MXNET_PALLAS"] = "1"
        try:
            out_pk = paged_decode_attention_q(
                jnp.asarray(q), kq, vq, ks, vs, jnp.asarray(table),
                jnp.asarray(lengths))
        finally:
            del os.environ["MXNET_PALLAS"]
        np.testing.assert_allclose(np.asarray(out_pk), np.asarray(out),
                                   rtol=1e-6, atol=1e-6)


def test_engine_int8_kv_greedy_decode(lm):
    """End-to-end: the int8-KV engine's greedy chain matches the fp32
    naive chain on a short horizon (the documented tolerance is
    logit-level; at this scale the argmax chain is stable), and
    sharing still works on top of the quantized pools.  NOTE a
    prefix-cache HIT reads the whole prompt through quantized pages
    while a miss's prefill attends raw K/V, so hit-vs-miss token
    equality is only a bit-exact guarantee for fp32 storage — for
    int8 the hit chain is checked for shape/stats, not identity."""
    params, naive = lm
    p = np.arange(1, 9, dtype=np.int32)
    with _engine(params, kv_dtype="int8") as eng:
        got = eng.generate(p, 4)
        again = eng.generate(p, 4)  # full hit over quantized pages
        st = eng.stats()
    assert st["kv_dtype"] == "int8"
    assert st["prefix_full_hits"] == 1
    assert st["cow_copies"] == 1
    np.testing.assert_array_equal(got, naive(p, 4))
    assert again.shape == (4,) and np.all(again >= 0) \
        and np.all(again < V)
    # fp32 storage: the SAME hit path IS bit-exact (shared pages are
    # the same bytes) — the contract the quantized path trades away
    with _engine(params, kv_dtype="fp32") as eng:
        a = eng.generate(p, 4)
        b = eng.generate(p, 4)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, naive(p, 4))


@pytest.mark.slow
def test_engine_quantized_matrix_vs_fp32(lm):
    """The kv_dtype matrix (bf16/int8/fp8) x (lax, interpret Pallas):
    greedy chains at this scale match fp32 exactly; quantized pools
    shrink the reported pool bytes."""
    import os

    params, naive = lm
    p = np.concatenate([np.arange(1, 9), [17, 23, 5]]).astype(np.int32)
    want = naive(p, 6)
    for kv in ("bf16", "int8", "fp8"):
        for pallas in ("0", "1"):
            os.environ["MXNET_PALLAS"] = pallas
            try:
                with _engine(params, kv_dtype=kv) as eng:
                    got = eng.generate(p, 6)
                    bytes_kv = eng._pool_bytes
            finally:
                del os.environ["MXNET_PALLAS"]
            np.testing.assert_array_equal(got, want, err_msg=f"{kv}")
        with _engine(params, kv_dtype="fp32") as eng:
            assert bytes_kv < eng._pool_bytes


@pytest.mark.slow
def test_engine_long_shared_prefix_sweep(lm):
    """Many clients over an 80%-shared-prefix workload: everything
    retires, accounting stays truthful (shared pages once), outputs
    all bit-match naive."""
    params, naive = lm
    rng = np.random.RandomState(11)
    shared = np.arange(5, 17, dtype=np.int32)  # 12 tokens
    reqs = []
    for i in range(12):
        if rng.rand() < 0.8:
            suffix = rng.randint(1, V, size=rng.randint(1, 5))
            reqs.append(np.concatenate([shared, suffix])
                        .astype(np.int32))
        else:
            reqs.append(rng.randint(
                1, V, size=rng.randint(6, 14)).astype(np.int32))
    with _engine(params, cache_blocks=25) as eng:
        futs = [(p, eng.submit(p, 5)) for p in reqs]
        outs = [(p, f.result(WAIT)) for p, f in futs]
        st = eng.stats()
    for p, got in outs:
        np.testing.assert_array_equal(got, naive(p, 5))
    assert st["prefix_hits"] >= 6
    assert st["generations"] == 12
    assert st["cache_util"] == 0.0
