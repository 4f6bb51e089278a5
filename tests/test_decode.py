"""Autoregressive serving tests: KV-cache decode correctness (the
bit-identity contract), paged block tables under fragmentation, the
Pallas gather kernel vs the lax fallback, and the continuous-batching
DecodeEngine (join/retire, preemption, admission, close-drain).

Fast variants run in tier-1; the long decode loops and wide
multi-stream sweeps are marked ``slow``.
"""

import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.executor import build_graph_fn
from mxnet_tpu.kv_cache import (BlockAllocator, blocks_for_tokens,
                                bucket_ladder, value_pool_shape)
from mxnet_tpu.models.transformer import (transformer_lm_decode,
                                          transformer_lm_prefill)

from _engines import (KVB, MAXLEN, WAIT, DM, H, L, V,  # noqa: E402
                      build, dense_engine as _engine, tiny_lm_params,
                      tiny_lm_reference)


@pytest.fixture(scope="module")
def lm():
    """Tiny trained-shape transformer: params + a greedy full-forward
    reference that goes through the TRAINING symbol (SoftmaxOutput
    head), so decode is checked against the genuine serving target.
    The tests that name no argument share one engine (``engines``)."""
    params = tiny_lm_params()
    return (params,) + tiny_lm_reference(params)


# ---------------------------------------------------------------------------
# prefill + incremental decode vs the full forward
# ---------------------------------------------------------------------------
#
# What is bitwise and what is not: a PREFILL row (M = prompt length)
# is bit-identical to the full forward's row (M = T) — same dot
# kernel, same accumulation order.  A DECODE step feeds every
# FullyConnected a ONE-row left operand, and the installed XLA:CPU
# picks a different dot kernel for M = 1 than for M > 1 (gemv vs
# gemm: `dot_general(x[5:6], W)` != `dot_general(x, W)[5:6]` in the
# last bit, measured 2026-09 on jaxlib 0.9.0), so a decode step's
# logits differ from the full forward's row by accumulation order,
# ~1e-6 at these sizes.  That is XLA:CPU's kernel choice, not the
# cache's: the contract (ROADMAP D3) is the greedy token equal and the
# logits within LOGIT_TOL.

LOGIT_TOL = dict(rtol=1e-5, atol=1e-5)


def _assert_decode_row(got, want, msg):
    np.testing.assert_allclose(got, want, err_msg=msg, **LOGIT_TOL)
    assert int(np.argmax(got)) == int(np.argmax(want)), msg



def test_prefill_decode_logits_bitwise_contiguous(lm):
    """Op-level contract: prefill rows are BIT-IDENTICAL to the
    full-sequence causal forward, and N contiguous decode steps give
    its greedy token with logits within LOGIT_TOL at every step,
    across a cache-length bucket boundary (the cache here is padded
    to C > T like a bucketed executable would)."""
    import jax
    import jax.numpy as jnp

    params, full_logits, _ = lm
    ds = transformer_lm_decode(V, num_layers=L, num_heads=H,
                               d_model=DM, kv_block=KVB, paged=False)
    gfn = build_graph_fn(ds)
    base = {n: jnp.asarray(params[n].asnumpy())
            for n in ds.list_arguments() if n in params}
    key = jax.random.PRNGKey(0)

    rng = np.random.RandomState(0)
    seq = rng.randint(1, V, size=18).astype(np.int32)
    p0 = 5
    full = full_logits(seq)

    # prefill via the prefill symbol (contiguous: caches come back as
    # (B, T, H, D)); re-home them into a C=24-slot cache (crosses the
    # 8->16->24 block boundaries as decode proceeds)
    ps = transformer_lm_prefill(V, num_layers=L, num_heads=H,
                                d_model=DM, kv_block=KVB, paged=False)
    pgfn = build_graph_fn(ps)
    a = dict(base)
    a.update(data=jnp.asarray(seq[None, :p0]),
             positions=jnp.asarray(np.arange(p0, dtype=np.int32)[None]),
             lengths=jnp.asarray(np.asarray([p0], np.int32)))
    pouts, _ = pgfn(a, {}, key, False)
    np.testing.assert_array_equal(np.asarray(pouts[0][0]), full[:p0])

    C = 24
    caches = []
    for kv in pouts[1:]:
        c = np.zeros((1, C, H, DM // H), np.float32)
        c[:, :p0] = np.asarray(kv)
        caches.append(jnp.asarray(c))
    for t in range(p0, len(seq)):
        a = dict(base)
        a.update(data=jnp.asarray(seq[None, t:t + 1]),
                 positions=jnp.asarray(
                     np.asarray([[t]], np.int32)),
                 lengths=jnp.asarray(np.asarray([t + 1], np.int32)))
        for i in range(L):
            a[f"layer{i}_kcache"] = caches[2 * i]
            a[f"layer{i}_vcache"] = caches[2 * i + 1]
        outs, _ = gfn(a, {}, key, False)
        _assert_decode_row(np.asarray(outs[0][0, 0]), full[t],
                           f"decode step t={t} off the full forward")
        caches = [jnp.asarray(x) for x in outs[1:]]


def test_paged_decode_bitwise_under_fragmentation(lm):
    """The paged path with a DELIBERATELY fragmented block table
    (pages interleaved/allocated out of order, stale data in freed
    pages): prefill rows bit-identical to the full forward, decode
    steps its greedy token with logits within LOGIT_TOL."""
    import jax
    import jax.numpy as jnp

    params, full_logits, _ = lm
    ds = transformer_lm_decode(V, num_layers=L, num_heads=H,
                               d_model=DM, kv_block=KVB, paged=True)
    ps = transformer_lm_prefill(V, num_layers=L, num_heads=H,
                                d_model=DM, kv_block=KVB, paged=True)
    dfn, pfn = build_graph_fn(ds), build_graph_fn(ps)
    base = {n: jnp.asarray(params[n].asnumpy())
            for n in ds.list_arguments() if n in params}
    key = jax.random.PRNGKey(0)

    rng = np.random.RandomState(1)
    seq = rng.randint(1, V, size=15).astype(np.int32)
    p0 = 6
    full = full_logits(seq)

    P = 12
    # stale garbage in the pool: a previous tenant's values must not
    # leak through the masks (finite garbage — K/V are activations)
    pools = [jnp.asarray(rng.randn(*value_pool_shape(P, KVB, H, DM // H))
                         .astype(np.float32)) for _ in range(2 * L)]
    # fragmented page order from interleaved alloc/free
    table = np.zeros((1, 4), np.int32)
    table[0] = [7, 2, 11, 5]
    a = dict(base)
    a.update(data=jnp.asarray(seq[None, :p0]),
             positions=jnp.asarray(np.arange(8, dtype=np.int32)[None]),
             lengths=jnp.asarray(np.asarray([p0], np.int32)),
             block_table=jnp.asarray(table[:, :2]))
    a["data"] = jnp.asarray(
        np.pad(seq[:p0], (0, 2))[None])  # prompt padded to bucket 8
    for i in range(L):
        a[f"layer{i}_kpool"] = pools[2 * i]
        a[f"layer{i}_vpool"] = pools[2 * i + 1]
    pouts, _ = pfn(a, {}, key, False)
    np.testing.assert_array_equal(np.asarray(pouts[0][0, :p0]),
                                  full[:p0])
    pools = [jnp.asarray(x) for x in pouts[1:]]
    for t in range(p0, len(seq)):
        a = dict(base)
        a.update(data=jnp.asarray(seq[None, t:t + 1]),
                 positions=jnp.asarray(np.asarray([[t]], np.int32)),
                 lengths=jnp.asarray(np.asarray([t + 1], np.int32)),
                 block_table=jnp.asarray(table))
        for i in range(L):
            a[f"layer{i}_kpool"] = pools[2 * i]
            a[f"layer{i}_vpool"] = pools[2 * i + 1]
        outs, _ = dfn(a, {}, key, False)
        _assert_decode_row(np.asarray(outs[0][0, 0]), full[t],
                           f"paged decode t={t} off the full forward")
        pools = [jnp.asarray(x) for x in outs[1:]]


@pytest.mark.parametrize("kind", ["w1", "w5", "int8"])
@pytest.mark.parametrize("nH,D", [(2, 8), (4, 32), (20, 64)])
def test_paged_pallas_kernel_matches_lax(monkeypatch, nH, D, kind):
    """The gather-by-block-table Pallas kernel (interpret mode on CPU
    — the same kernel code path as TPU) over lane-dense (P, KVB, H·D)
    pools matches the blockwise lax body over a cache gathered (and,
    for int8 pools, dequantized) HERE, in numpy, from the pool viewed
    as (P, KVB, H, D) — at dtype tolerance: the decode step (W = 1), a
    verify window (W = 5) and int8 pools, at the benchmark's 20 heads
    x 64, at a width where a head is a quarter of a lane tile, and at
    a toy one."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att, pallas_kernels as pk

    rng = np.random.RandomState(3)
    B, P, MB = 3, 10, 3
    W = 5 if kind == "w5" else 1
    q = rng.randn(B, W, nH * D).astype(np.float32)
    shape = value_pool_shape(P, KVB, nH, D)
    kp = rng.randn(*shape).astype(np.float32)
    vp = rng.randn(*shape).astype(np.float32)
    table = np.array([[5, 2, 9], [1, 7, 3], [0, 0, 0]], np.int32)
    # tokens cached before the window; row 2 is an inactive slot
    start = np.array([8, 4, -1], np.int32)
    scales = ()
    if kind == "int8":
        (kp, ks), (vp, vs) = (
            [np.asarray(a) for a in
             att._quantize_rows(jnp.asarray(x), nH, jnp.int8)]
            for x in (kp, vp))
        scales = (ks, vs)

    monkeypatch.setenv("MXNET_PALLAS", "1")
    assert pk.enabled()
    args = [jnp.asarray(x) for x in (kp, vp, *scales, table)]
    if kind == "int8":
        out = pk.paged_attention_decode_quant(
            jnp.asarray(q[:, 0]), *args, jnp.asarray(start + 1),
            nH)[:, None]
    elif kind == "w5":
        out = pk.paged_attention_verify(jnp.asarray(q), *args,
                                        jnp.asarray(start), nH)
    else:
        out = pk.paged_attention_decode(
            jnp.asarray(q[:, 0]), *args, jnp.asarray(start + 1),
            nH)[:, None]
    out = np.asarray(out)
    assert out.shape == (B, W, nH * D)

    def gathered(pool, scale=None):
        g = pool[table].reshape(B, MB * KVB, nH, D).astype(np.float32)
        if scale is not None:
            g = g * scale[table].reshape(B, MB * KVB, nH)[..., None]
        return jnp.asarray(g)

    o, m, l = att._blockwise_attention_partial_lax(
        jnp.asarray(q.reshape(B, W, nH, D)), gathered(kp, *scales[:1]),
        gathered(vp, *scales[1:]), False, KVB, 0,
        lengths=jnp.asarray(start + 1), diagonal=True)
    ref = np.asarray(att.normalize_attention_state(
        o, m, l, jnp.float32)).reshape(B, W, nH * D)
    live = slice(0, 2)
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-6,
                               atol=1e-6)
    # a fully-masked (inactive) stream produces zeros, not NaN
    assert np.all(np.isfinite(out))
    if W == 1:
        np.testing.assert_array_equal(out[2], np.zeros_like(out[2]))


@pytest.mark.parametrize("nH,D", [(4, 32), (20, 64)])
def test_paged_quant_kernel_float32_operands_under_bf16_query(
        monkeypatch, nH, D):
    """A bf16 engine over int8 pools: the kernel dequantizes a page to
    float32 and keeps q, the values AND the probabilities float32
    through both matmuls, as the (P, KVB, H, D) kernel did.  Its bf16
    output then equals, element for element, the float32 body over the
    numpy-dequantized cache rounded to bf16 (but for a rare tie at a
    rounding boundary).  Probabilities cast to the query's bf16 before
    P·V — 8 mantissa bits for 24 — move a third of the elements by a
    bf16 ulp, and fail here."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att, pallas_kernels as pk

    rng = np.random.RandomState(3)
    B, P, MB = 2, 10, 3
    q = jnp.asarray(rng.randn(B, 1, nH * D).astype(np.float32)
                    ).astype(jnp.bfloat16)
    shape = value_pool_shape(P, KVB, nH, D)
    table = np.array([[5, 2, 9], [1, 7, 3]], np.int32)
    lengths = np.array([9, 5], np.int32)
    (kq, ks), (vq, vs) = (
        att._quantize_rows(jnp.asarray(rng.randn(*shape)
                                       .astype(np.float32)), nH, jnp.int8)
        for _ in range(2))

    monkeypatch.setenv("MXNET_PALLAS", "1")
    out = pk.paged_attention_decode_quant(
        q[:, 0], kq, vq, ks, vs, jnp.asarray(table), jnp.asarray(lengths),
        nH)
    assert out.dtype == jnp.bfloat16

    def gathered(pool, scale):
        g = np.asarray(pool)[table].reshape(B, MB * KVB, nH, D)
        return jnp.asarray(
            g.astype(np.float32)
            * np.asarray(scale)[table].reshape(B, MB * KVB, nH)[..., None])

    ref = att.decode_attention(
        q.astype(jnp.float32).reshape(B, 1, nH, D), gathered(kq, ks),
        gathered(vq, vs), jnp.asarray(lengths), KVB)
    ref = ref.astype(jnp.bfloat16).reshape(B, nH * D)
    differ = np.asarray(out.astype(jnp.float32)) \
        != np.asarray(ref.astype(jnp.float32))
    assert differ.mean() < 0.02, differ.mean()


def _chunk_case(shape, kind, lengths_of, rng, nan_past_live=False):
    """Pools, table, queries and the float32 gather-and-softmax answer
    for rows whose live lengths ``lengths_of(C, bucket)`` names, C the
    kernel's own chunk at this shape.  ``nan_past_live``: every page no
    row position can see — the scratch page too — holds NaN."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att, pallas_kernels as pk

    Hq, Hkv, D = shape
    W = {"w1": 1, "w5": 5, "int8": 1}[kind]
    page, MB = 16, 40                       # a 640-token bucket
    C = page * pk._paged_pages_per_chunk(
        W, Hq, Hkv, D, page, MB, 4, 1 if kind == "int8" else 4,
        kind == "int8")[1]
    assert C < MB * page, "the bucket must hold more than one chunk"
    live = np.asarray(lengths_of(C, MB * page), np.int32)
    B = len(live)
    start = live - W                        # cached before the window
    P = B * MB + 1
    kp, vp = (rng.randn(P, page, Hkv * D).astype(np.float32)
              for _ in range(2))
    table = (1 + rng.permutation(P - 1)).reshape(B, MB).astype(np.int32)
    q = rng.randn(B, W, Hq * D).astype(np.float32)
    scales = ()
    if kind == "int8":
        (kp, ks), (vp, vs) = ([np.array(a) for a in att._quantize_rows(
            jnp.asarray(x), Hkv, jnp.int8)] for x in (kp, vp))
        scales = (ks, vs)

    def cache(pool, scale=None):
        g = pool[table].reshape(B, MB * page, Hkv, D).astype(np.float64)
        if scale is not None:
            g = g * scale[table].reshape(B, MB * page, Hkv)[..., None]
        return np.repeat(g, Hq // Hkv, axis=2)

    kc, vc = cache(kp, *scales[:1]), cache(vp, *scales[1:])
    want = np.zeros((B, W, Hq, D))
    for b in range(B):
        for w in range(W):
            n = start[b] + 1 + w            # keys row w sees
            if n <= 0:
                continue
            qh = q[b, w].reshape(Hq, D).astype(np.float64)
            s = np.einsum("hd,thd->ht", qh, kc[b, :n]) / np.sqrt(D)
            e = np.exp(s - s.max(axis=1, keepdims=True))
            want[b, w] = np.einsum("ht,thd->hd",
                                   e / e.sum(axis=1, keepdims=True),
                                   vc[b, :n])
    if nan_past_live:
        dead = np.ones(P, bool)
        for b in range(B):
            dead[table[b, :-(-int(live[b]) // page)]] = False
        table = np.where(np.arange(MB)[None] * page < live[:, None],
                         table, 0).astype(np.int32)
        for pool in (kp, vp) + scales:
            if pool.dtype != np.int8:
                pool[dead] = np.nan
    args = [jnp.asarray(x) for x in (q, kp, vp)]
    run = lambda: np.asarray(pk._paged_attention(          # noqa: E731
        *args, tuple(jnp.asarray(x) for x in scales), jnp.asarray(table),
        jnp.asarray(start), Hq, kv_heads=Hkv))
    return run, want.reshape(B, W, Hq * D), live, C


_CHUNK_LENGTHS = {
    # the ends of a chunk: one key, one short of a chunk, a whole
    # chunk, one over
    "chunk-ends": lambda C, bucket: [1, C - 1, C, C + 1],
    # a whole bucket, and an idle row between two full ones
    "bucket": lambda C, bucket: [bucket, 0, bucket],
}


@pytest.mark.parametrize("lengths", sorted(_CHUNK_LENGTHS))
@pytest.mark.parametrize("shape,kind", [
    ((20, 20, 64), "w1"), ((20, 20, 64), "w5"), ((20, 20, 64), "int8"),
    ((64, 8, 128), "w1"), ((64, 8, 128), "w5"), ((4, 4, 32), "w5")])
def test_paged_kernel_walks_live_chunks(monkeypatch, shape, kind, lengths):
    """The kernel takes a row's pages a chunk of C keys at a time and
    walks only the chunks its live length reaches: rows that end one
    key into a chunk, one short of it, on it and one past it, a whole
    bucket, and an idle row beside full ones — the decode step, a
    5-row window and int8 pools, at the doc cell's 20 x 64, the
    grouped 64 / 8 x 128 and a width a quarter of a lane tile — each
    against a float64 gather-and-softmax over the gathered pages."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    run, want, live, C = _chunk_case(shape, kind, _CHUNK_LENGTHS[lengths],
                                     np.random.RandomState(5))
    out = run()
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    assert not out[live == 0].any()


@pytest.mark.parametrize("shape,kind", [
    ((20, 20, 64), "w1"), ((20, 20, 64), "int8"), ((64, 8, 128), "w5")])
def test_paged_kernel_reads_no_page_past_the_live_length(monkeypatch, shape,
                                                         kind):
    """Every page past a row's live length, and the scratch page the
    table pads with, filled with NaN (the int8 pools' scales, there):
    the walk is bounded and the mask right, so the output is finite and
    equal to the clean pools' — a NaN that reached a buffer would come
    through P·V however exactly its probability is 0."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    mixed = lambda C, bucket: [C + 1, 0, 3, bucket, C - 16]  # noqa: E731
    clean, want, _, _ = _chunk_case(shape, kind, mixed,
                                    np.random.RandomState(7))
    dirty, _, _, _ = _chunk_case(shape, kind, mixed,
                                 np.random.RandomState(7),
                                 nan_past_live=True)
    out = dirty()
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean())
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)


def test_paged_op_pallas_vs_lax_path(monkeypatch, lm):
    """QKVPagedAttentionDecode end to end: the kernel path equals the
    lax path at tolerance on identical pools/tables."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.registry import invoke

    rng = np.random.RandomState(4)
    B, nH, D, P = 2, 2, 8, 8
    qkv = rng.randn(B, 1, 3 * nH * D).astype(np.float32)
    kp = rng.randn(*value_pool_shape(P, KVB, nH, D)).astype(np.float32)
    vp = rng.randn(*value_pool_shape(P, KVB, nH, D)).astype(np.float32)
    table = np.array([[3, 6], [1, 4]], np.int32)
    lengths = np.array([6, 3], np.int32)
    ins = [jnp.asarray(x) for x in (qkv, kp, vp, table, lengths)]

    monkeypatch.setenv("MXNET_PALLAS", "0")
    (o_lax, k_lax, v_lax), _ = invoke("QKVPagedAttentionDecode", ins,
                                      {"num_heads": nH})
    monkeypatch.setenv("MXNET_PALLAS", "1")
    (o_pal, k_pal, v_pal), _ = invoke("QKVPagedAttentionDecode", ins,
                                      {"num_heads": nH})
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_lax),
                               rtol=1e-6, atol=1e-6)
    # the cache write is the same scatter on both paths
    np.testing.assert_array_equal(np.asarray(k_pal), np.asarray(k_lax))
    np.testing.assert_array_equal(np.asarray(v_pal), np.asarray(v_lax))


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------


def test_block_allocator_alloc_free_fragmentation():
    a = BlockAllocator(9, 4)  # 1 scratch + 8 usable
    assert a.capacity == 8 and a.free_blocks == 8
    x = a.alloc(3, owner="x")
    y = a.alloc(2, owner="y")
    assert len(set(x) | set(y)) == 5 and 0 not in x + y
    assert a.used_blocks == 5
    a.free(x)  # interleaved free fragments the id space
    with pytest.raises(mx.MXNetError, match="double free|foreign"):
        a.free([x[0]])
    z = a.alloc(4, owner="z")
    assert z is not None and 0 not in z
    assert set(z).isdisjoint(y)
    # all-or-nothing: 3 left, asking 4 takes nothing
    assert a.alloc(4) is None
    assert a.free_blocks == 2
    assert a.alloc(2) is not None
    assert a.utilization() == 1.0
    with pytest.raises(mx.MXNetError, match="scratch"):
        a.free([0])
    with pytest.raises(mx.MXNetError, match=">= 2"):
        BlockAllocator(1, 4)


def test_blocks_for_tokens_and_ladder():
    assert blocks_for_tokens(1, 4) == 1
    assert blocks_for_tokens(4, 4) == 1
    assert blocks_for_tokens(5, 4) == 2
    assert bucket_ladder(8) == [1, 2, 4, 8]
    assert bucket_ladder(6) == [1, 2, 4, 6]
    assert bucket_ladder(1) == [1]


# ---------------------------------------------------------------------------
# DecodeEngine: the tier-1 smoke (4-token decode on the tiny model)
# ---------------------------------------------------------------------------


def test_the_padded_greedy_chain_is_the_natural_lengths(lm):
    """``naive_generate`` runs one program at MAXLEN rows; its chain is
    the chain of full forwards at each natural length."""
    _, full_logits, naive_generate = lm
    seq = [3, 17, 42, 5, 9]
    for _ in range(3):
        seq.append(int(np.argmax(full_logits(seq)[-1])))
    np.testing.assert_array_equal(naive_generate(seq[:5], 3), seq[5:])


def test_engine_smoke_greedy_decode(engines, lm):
    """4-token greedy decode on a tiny model equals the full-forward
    argmax chain — the tier-1-visible variant of the slow loops."""
    params, _, naive_generate = lm
    prompt = np.array([3, 17, 42, 5, 9], np.int32)
    eng = engines(_engine, params)
    got = eng.generate(prompt, 4)
    st = eng.stats()
    np.testing.assert_array_equal(got, naive_generate(prompt, 4))
    assert st["generations"] == 1 and st["tokens"] == 4
    assert st["prefill_tokens"] == 5


def test_engine_admission_and_cache_accounting(lm):
    params, _, _ = lm
    with _engine(params, cache_blocks=33) as eng:
        f = eng.submit(np.arange(1, 6, dtype=np.int32), 3)
        f.result(timeout=WAIT)
        st = eng.stats()
        # everything retired: all pages back in the pool
        assert st["cache_util"] == 0.0
        assert st["cache_blocks_free"] == 32
        assert st["preempted"] == 0


def test_engine_submit_validation(lm):
    params, _, _ = lm
    with _engine(params) as eng:
        with pytest.raises(mx.MXNetError, match="non-empty 1-D"):
            eng.submit(np.zeros((2, 3), np.int32), 4)
        with pytest.raises(mx.MXNetError, match="max_len"):
            eng.submit(np.arange(30, dtype=np.int32), 10)
        with pytest.raises(mx.MXNetError, match="max_new_tokens"):
            eng.submit(np.arange(3, dtype=np.int32), 0)
    with pytest.raises(mx.EngineClosedError):
        eng.submit(np.arange(3, dtype=np.int32), 2)


def test_engine_eos_stops_early(engines, lm):
    """Greedy chains revisit tokens; use the first generated token as
    eos so generation must stop right after producing it again."""
    params, _, naive_generate = lm
    prompt = np.array([3, 17, 42, 5, 9], np.int32)
    ref = naive_generate(prompt, 6)
    eos = int(ref[2])
    got = engines(_engine, params).generate(prompt, 6, eos_id=eos)
    stop = int(np.argmax(ref == eos)) + 1
    np.testing.assert_array_equal(got, ref[:stop])
    assert got[-1] == eos


def test_engine_close_fails_inflight_with_named_error(lm):
    """The drain test: close() during an in-flight decode fails the
    outstanding futures with EngineClosedError at wait — never a
    hang."""
    params, _, _ = lm
    eng = _engine(params)
    futs = [eng.submit(np.arange(1, 5, dtype=np.int32), 25)
            for _ in range(3)]
    time.sleep(0.05)  # let the scheduler pick them up
    t0 = time.perf_counter()
    eng.close(timeout=60)
    assert time.perf_counter() - t0 < 60
    for f in futs:
        with pytest.raises(mx.EngineClosedError, match="closed"):
            f.result(timeout=10)


def test_inference_engine_batch_loop_death_poisons_futures():
    """InferenceEngine: a dying batch loop fails queued futures with
    the named error instead of stranding them (failure poisoning
    raises at wait instead of hanging)."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    mod = mx.mod.Module(net, context=mx.cpu(), label_names=[])
    mod.bind(data_shapes=[("data", (2, 6))], for_training=False)
    mod.init_params(mx.initializer.Xavier())
    arg, aux = mod.get_params()
    pred = mx.Predictor(net, {**arg, **aux}, {"data": (1, 6)})
    eng = mx.InferenceEngine(pred, buckets=(4,), batch_timeout_ms=1.0)
    try:
        # sabotage the coalescing loop itself (outside _dispatch's
        # per-batch try/except): `t_first + None` raises TypeError
        eng._timeout_s = eng._idle_timeout_s = None
        fut = eng.submit(np.zeros((1, 6), np.float32))
        with pytest.raises(mx.EngineClosedError, match="died"):
            fut.result(timeout=30)
    finally:
        eng._queue.put(None)  # loop is dead; unblock close's join
        eng.close(timeout=5)


# ---------------------------------------------------------------------------
# env-var validation (MXNET_CKPT_* convention: garbage raises loudly)
# ---------------------------------------------------------------------------


def test_env_validation_garbage_raises(monkeypatch, lm):
    params, _, _ = lm
    monkeypatch.setenv("MXNET_SERVING_KV_BLOCK", "banana")
    with pytest.raises(mx.MXNetError, match="MXNET_SERVING_KV_BLOCK"):
        build(params, vocab_size=V, num_layers=L, num_heads=H,
                  d_model=DM, max_len=MAXLEN)
    monkeypatch.setenv("MXNET_SERVING_KV_BLOCK", "-4")
    with pytest.raises(mx.MXNetError, match="MXNET_SERVING_KV_BLOCK"):
        build(params, vocab_size=V, num_layers=L, num_heads=H,
                  d_model=DM, max_len=MAXLEN)
    monkeypatch.delenv("MXNET_SERVING_KV_BLOCK")
    monkeypatch.setenv("MXNET_SERVING_MAX_STREAMS", "0")
    with pytest.raises(mx.MXNetError,
                       match="MXNET_SERVING_MAX_STREAMS"):
        build(params, vocab_size=V, num_layers=L, num_heads=H,
                  d_model=DM, max_len=MAXLEN)
    monkeypatch.delenv("MXNET_SERVING_MAX_STREAMS")
    monkeypatch.setenv("MXNET_SERVING_DECODE_BUCKETS", "4,2,1")
    with pytest.raises(mx.MXNetError, match="increasing"):
        build(params, vocab_size=V, num_layers=L, num_heads=H,
                  d_model=DM, max_len=MAXLEN)
    monkeypatch.setenv("MXNET_SERVING_DECODE_BUCKETS", "1,zebra")
    with pytest.raises(mx.MXNetError, match="comma-separated"):
        build(params, vocab_size=V, num_layers=L, num_heads=H,
                  d_model=DM, max_len=MAXLEN)
    monkeypatch.delenv("MXNET_SERVING_DECODE_BUCKETS")
    monkeypatch.setenv("MXNET_SERVING_PREFILL_BUCKETS", "3,7")
    with pytest.raises(mx.MXNetError, match="multiple of"):
        build(params, vocab_size=V, num_layers=L, num_heads=H,
                  d_model=DM, max_len=MAXLEN)
    # registered in the config catalog
    for name in ("MXNET_SERVING_KV_BLOCK", "MXNET_SERVING_MAX_STREAMS",
                 "MXNET_SERVING_DECODE_BUCKETS",
                 "MXNET_SERVING_CACHE_BUCKETS",
                 "MXNET_SERVING_PREFILL_BUCKETS"):
        assert mx.config.describe(name).name == name


def test_ladder_coverage_validated_at_construction(lm):
    """A ladder that doesn't cover the configured maxima would kill the
    serving loop mid-flight (a _bucket miss poisons every outstanding
    future) — it must raise at construction instead.  Explicit
    prefill_buckets get the same strictly-increasing check as the
    other ladders."""
    params, _, _ = lm
    with pytest.raises(mx.MXNetError, match="does not cover"):
        _engine(params, max_streams=8, decode_buckets=[1, 2, 4])
    with pytest.raises(mx.MXNetError, match="does not cover"):
        _engine(params, cache_buckets=[1, 2])  # MAXLEN/KVB = 8 pages
    with pytest.raises(mx.MXNetError, match="bad prefill_buckets"):
        _engine(params, prefill_buckets=[16, 8])


def test_reset_stats_isolates_measurement_points(engines, lm):
    """A sweep drives one engine across load points; reset_stats
    must zero counters AND histogram reservoirs so a point's
    percentiles don't blend earlier points' samples."""
    params, _, _ = lm
    eng = engines(_engine, params)
    eng.generate(np.arange(1, 5, dtype=np.int32), 4)
    st = eng.stats()
    assert st["tokens"] >= 4 and st["p50_ms"] is not None
    eng.reset_stats()
    st = eng.stats()
    assert st["tokens"] == 0 and st["p50_ms"] is None


# ---------------------------------------------------------------------------
# continuous batching: join/retire and preemption (slow variants)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multi_stream_join_retire_outputs_unchanged(lm):
    """Streams joining and retiring mid-loop (staggered submits,
    different lengths) leave every stream's output identical to its
    single-stream generation."""
    params, _, naive_generate = lm
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (5, 3, 7, 1, 6, 4)]
    lens = [12, 5, 9, 17, 2, 8]
    with _engine(params, max_streams=4) as eng:
        futs = []
        for i, (p, n) in enumerate(zip(prompts, lens)):
            futs.append(eng.submit(p, n))
            if i == 2:
                time.sleep(0.1)  # stagger: join mid-loop
        outs = [f.result(timeout=WAIT) for f in futs]
        st = eng.stats()
    for p, n, o in zip(prompts, lens, outs):
        np.testing.assert_array_equal(o, naive_generate(p, n))
    assert st["generations"] == len(prompts)
    # continuous batching actually batched: fewer steps than tokens
    assert st["steps"] < st["tokens"]


@pytest.mark.slow
def test_preemption_recompute_outputs_unchanged(lm):
    """A pool too small for all streams forces preemption; preempted
    streams re-prefill their progress and still produce exactly their
    single-stream outputs."""
    params, _, naive_generate = lm
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(7, 12, dtype=np.int32),
               np.arange(13, 18, dtype=np.int32)]
    with _engine(params, max_streams=3, cache_blocks=10) as eng:
        futs = [eng.submit(p, 14) for p in prompts]
        outs = [f.result(timeout=WAIT) for f in futs]
        st = eng.stats()
    assert st["preempted"] > 0
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o, naive_generate(p, 14))


@pytest.mark.slow
def test_temperature_sampling_reproducible_across_batching(lm):
    """Per-stream PRNG keys are (engine seed, stream id, position):
    the same request sampled alone and sampled inside a busy batch
    yields the same tokens."""
    params, _, _ = lm
    prompt = np.array([3, 17, 42], np.int32)
    with _engine(params, seed=11) as eng:
        alone = eng.generate(prompt, 8, temperature=0.8)
    with _engine(params, seed=11) as eng:
        futs = [eng.submit(prompt, 8, temperature=0.8),
                eng.submit(np.array([9, 9], np.int32), 8,
                           temperature=0.5)]
        batched = futs[0].result(timeout=WAIT)
    np.testing.assert_array_equal(alone, batched)


@pytest.mark.slow
def test_long_decode_loop_across_cache_buckets(lm):
    """A generation long enough to cross several cache-length buckets
    (block-table growth mid-stream) stays bit-exact."""
    params, _, naive_generate = lm
    prompt = np.array([2, 4], np.int32)
    n = 28  # 30 tokens total = 8 blocks: crosses 1->2->4->8 buckets
    with _engine(params, cache_buckets=[1, 2, 4, 8]) as eng:
        got = eng.generate(prompt, n)
    np.testing.assert_array_equal(got, naive_generate(prompt, n))


def test_capacity_edge_request_admits(lm):
    """A request whose lifetime page need is EXACTLY the pool capacity
    must still be served — admission's +1 decode headroom is capped at
    the lifetime need (review finding: it used to hold the FIFO line
    forever while the scheduler spun)."""
    params, _, naive_generate = lm
    # capacity 4 pages = 16 tokens; 15-token prompt + 1 token fills it
    prompt = np.arange(1, 16, dtype=np.int32)
    with _engine(params, cache_blocks=5, max_streams=1) as eng:
        out = eng.submit(prompt, 1).result(timeout=WAIT)
    np.testing.assert_array_equal(out, naive_generate(prompt, 1))


def test_prefill_failure_fails_the_admitted_future(lm):
    """A stream popped from pending whose prefill dies must get the
    poison error like everyone else, not hang (review finding: it was
    invisible to _fail_outstanding between pop and activation)."""
    params, _, _ = lm
    eng = _engine(params)
    try:
        def boom(*key):
            raise RuntimeError("injected prefill failure")

        eng._exe = boom
        fut = eng.submit(np.arange(1, 5, dtype=np.int32), 4)
        with pytest.raises(mx.EngineClosedError, match="died"):
            fut.result(timeout=60)
        # the dead loop also shut the door: a later submit raises
        # instead of queueing work nothing will ever process
        with pytest.raises(mx.EngineClosedError):
            eng.submit(np.arange(1, 5, dtype=np.int32), 4)
    finally:
        eng.close(timeout=10)


def test_multi_token_decode_qkv_rejected():
    """Both decode ops refuse a multi-token qkv instead of silently
    attending only the first token."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.registry import invoke

    rng = np.random.RandomState(9)
    nH, D = 2, 8
    qkv2 = jnp.asarray(rng.randn(1, 2, 3 * nH * D).astype(np.float32))
    kp = jnp.zeros(value_pool_shape(4, KVB, nH, D))
    table = jnp.zeros((1, 2), jnp.int32)
    lengths = jnp.asarray([3], jnp.int32)
    with pytest.raises(mx.MXNetError, match="ONE query position"):
        invoke("QKVPagedAttentionDecode", [qkv2, kp, kp, table, lengths],
               {"num_heads": nH})
    ck = jnp.zeros((1, 8, nH, D))
    with pytest.raises(mx.MXNetError, match="ONE query position"):
        invoke("QKVSelfAttentionDecode", [qkv2, ck, ck, lengths],
               {"num_heads": nH})


def test_decode_telemetry_surfaces(lm):
    profiler.reset_metrics()
    params, _, _ = lm
    # an engine of its own: the registry was reset, and an engine writes
    # some of its gauges when it is built and when it is closed
    with _engine(params) as eng:
        eng.generate(np.arange(1, 5, dtype=np.int32), 4)
    summ = profiler.metrics_summary()
    assert summ["counters"]["serving.tokens"] >= 4
    assert summ["counters"]["serving.prefills"] >= 1
    assert "serving.time_per_token_ms" in summ["histograms"]
    assert "serving.cache_util" in summ["gauges"]
    assert "serving.active_streams" in summ["gauges"]


# ---------------------------------------------------------------------------
# fleet hooks: inflight snapshot, drain/resume, seed override, swap
# ---------------------------------------------------------------------------


def test_drain_path_inflight_matches_poisoned_count(lm):
    """The router's view of what died with an engine: inflight() BEFORE
    the close equals the number of futures poisoned with
    EngineClosedError, and the count falls to 0 once they are failed
    (no phantom ownership after the drain)."""
    params, _, _ = lm
    eng = _engine(params)
    futs = [eng.submit(np.arange(1, 5, dtype=np.int32), 25)
            for _ in range(3)]
    time.sleep(0.05)
    n_before = eng.inflight()
    assert n_before == 3
    eng.close(timeout=60)
    poisoned = 0
    for f in futs:
        with pytest.raises(mx.EngineClosedError):
            f.result(timeout=10)
        poisoned += 1
    assert poisoned == n_before
    assert eng.inflight() == 0


def test_decode_drain_resume_and_inflight(lm):
    params, _, _ = lm
    eng = _engine(params)
    try:
        assert eng.inflight() == 0
        futs = [eng.submit(np.arange(1, 5, dtype=np.int32), 6)
                for _ in range(2)]
        left = eng.drain(timeout=120)
        assert left == 0 and eng.inflight() == 0
        for f in futs:
            assert f.result(10).shape == (6,)  # drained, not dropped
        with pytest.raises(mx.EngineClosedError, match="draining"):
            eng.submit(np.arange(1, 5, dtype=np.int32), 4)
        eng.resume()
        out = eng.submit(np.arange(1, 5, dtype=np.int32), 4).result(60)
        assert out.shape == (4,)
    finally:
        eng.close(timeout=30)


def test_submit_seed_override_reproduces_across_engines(engines, lm):
    """Fleet retry determinism: the same (prompt, seed) sampled at
    temperature > 0 yields identical tokens on a DIFFERENT engine with
    different stream-id history — the property that lets a survivor
    re-generate a dead replica's request bit-exactly."""
    params, _, _ = lm
    p = np.arange(1, 5, dtype=np.int32)
    a = engines(_engine, params).submit(
        p, 6, temperature=0.7, seed=123).result(WAIT)
    e2 = _engine(params)        # a second engine is the point
    try:
        e2.submit(p, 3).result(WAIT)  # shift e2's stream-id history
        b = e2.submit(p, 6, temperature=0.7, seed=123).result(WAIT)
        c = e2.submit(p, 6, temperature=0.7, seed=124).result(WAIT)
    finally:
        e2.close(timeout=30)
    assert np.array_equal(a, b)
    assert not np.array_equal(b, c)  # the seed really keys sampling


def test_decode_swap_params_identity_and_validation(lm):
    """swap_params installs new weights without recompiling (params
    are runtime args): identical weights → identical generation;
    missing/mis-shaped params refuse loudly."""
    params, _, naive = lm
    eng = _engine(params)
    try:
        p = np.arange(1, 6, dtype=np.int32)
        before = eng.submit(p, 5).result(WAIT)
        # warm the prefix-hit path too (a repeated prompt lazily
        # compiles the suffix-prefill bucket on its first hit — that
        # compile belongs to the hit, not to the swap under test)
        assert np.array_equal(eng.submit(p, 5).result(WAIT), before)
        eng.swap_params(params)  # same weights, full round-trip
        compiles_before = dict(eng.compiles)
        after = eng.submit(p, 5).result(WAIT)
        assert np.array_equal(before, after)
        assert dict(eng.compiles) == compiles_before  # no recompile
        name = eng._param_names[0]
        with pytest.raises(mx.MXNetError, match="missing"):
            eng.swap_params({name: params[name]})
        bad = {k: v for k, v in params.items()}
        bad[name] = np.zeros((3, 3), np.float32)
        with pytest.raises(mx.MXNetError, match="shape"):
            eng.swap_params(bad)
        # the failed swaps never installed anything
        assert np.array_equal(eng.submit(p, 5).result(WAIT), before)
    finally:
        eng.close(timeout=30)
