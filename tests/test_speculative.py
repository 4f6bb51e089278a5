"""Speculative decoding + chunked prefill tests.

The contracts, in order of appearance:

* the n-gram proposer is a deterministic function of the context;
* the verify op scores W window positions bit-identically (lax path)
  to W sequential single-query decode steps over the same cache bytes
  — the whole greedy-bit-identity story rests on this;
* the Pallas k-query verify kernel (interpret mode) matches the lax
  fallback;
* speculative greedy engine chains are BIT-identical to
  non-speculative greedy ones, including across batch-composition
  changes and prefix-cache hits;
* temperature sampling with rejection matches the target distribution
  exactly (chi-square on a tiny vocab) and a no-draft row is
  bit-identical to the plain sampler;
* chunked prefill bit-matches monolithic prefill;
* the new MXNET_SERVING_* vars validate loudly.

Fast variants run in tier-1 (the ~5s propose→verify→accept/reject→
continue smoke); the wide multi-stream sweeps are marked ``slow``
(the PR 7/13 pattern).
"""

import functools
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kv_cache import trim_blocks, value_pool_shape
from mxnet_tpu.speculative import NgramProposer, make_proposer

from _engines import KVB, H, V, dense_engine, tiny_lm_params

MAXLEN = 48


@pytest.fixture(scope="module")
def lm():
    # seeded: the n-gram smoke below needs a model whose greedy output
    # repeats enough for SOME drafts to be accepted and some not — with
    # the global stream left wherever earlier tests put it, that was
    # luck of the file order
    return tiny_lm_params(MAXLEN, seed=0)


_engine = functools.partial(dense_engine, max_len=MAXLEN)


def _repetitive_prompt(rng, n=18, motif=5):
    m = rng.randint(1, V, size=motif).astype(np.int32)
    return np.tile(m, -(-n // motif))[:n]


# ---------------------------------------------------------------------------
# proposer
# ---------------------------------------------------------------------------


def test_ngram_proposer_deterministic():
    p = NgramProposer()
    ctx = np.array([1, 2, 3, 4, 1, 2, 3], np.int32)
    # trailing [1,2,3] recurs at the start -> propose its continuation
    np.testing.assert_array_equal(p.propose(ctx, 4), [4, 1, 2, 3])
    np.testing.assert_array_equal(p.propose(ctx, 2), [4, 1])
    # same context, same proposal — determinism is what fleet decode
    # retries re-propose from
    np.testing.assert_array_equal(p.propose(ctx, 4),
                                  p.propose(ctx, 4))
    # no recurrence -> nothing proposed
    assert p.propose(np.arange(1, 9, dtype=np.int32), 4).size == 0
    # most RECENT occurrence wins: ...5,9 ... 5,7 ... 5 -> continue 7
    ctx2 = np.array([5, 9, 1, 5, 7, 2, 5], np.int32)
    np.testing.assert_array_equal(p.propose(ctx2, 2), [7, 2])
    with pytest.raises(mx.MXNetError):
        make_proposer("banana")


def test_trim_blocks_accounting():
    keep, surplus = trim_blocks([7, 9, 12], 5, 4)  # 5 tokens -> 2 pages
    assert keep == [7, 9] and surplus == [12]
    keep, surplus = trim_blocks([7, 9], 8, 4)
    assert keep == [7, 9] and surplus == []
    keep, surplus = trim_blocks([7], 9, 4)  # already short: no-op
    assert keep == [7] and surplus == []


# ---------------------------------------------------------------------------
# op-level: the verify window IS W sequential decode steps
# ---------------------------------------------------------------------------


def test_verify_op_bitwise_vs_sequential_decode():
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import (paged_cache_update,
                                         paged_decode_attention,
                                         paged_prefill_write,
                                         paged_verify_attention)

    rng = np.random.RandomState(3)
    P, B, W, start0 = 9, 2, 3, np.array([6, 3], np.int32)
    pool = value_pool_shape(P, KVB, H, 8)
    kp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    vp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    table = jnp.asarray(
        np.array([[3, 1, 7, 0], [5, 2, 0, 0]], np.int32))
    q = jnp.asarray(rng.randn(B, W, H * 8).astype(np.float32))
    kw_ = jnp.asarray(rng.randn(B, W, H * 8).astype(np.float32))
    vw = jnp.asarray(rng.randn(B, W, H * 8).astype(np.float32))
    start = jnp.asarray(start0)
    lengths = start + W

    # verify path: write the whole window, one diagonal-masked pass
    kp1, vp1 = paged_prefill_write(kw_, vw, kp, vp, table, lengths,
                                   start=start)
    out_v = np.asarray(paged_verify_attention(q, kp1, vp1, table,
                                              start, H))

    # sequential path: W single-token decode steps
    kp2, vp2 = kp, vp
    for i in range(W):
        li = start + i + 1
        kp2, vp2 = paged_cache_update(
            kp2, vp2, kw_[:, i:i + 1], vw[:, i:i + 1], table, li)
        out_i = np.asarray(paged_decode_attention(
            q[:, i:i + 1], kp2, vp2, table, li, H))
        # same mask, same block chain; the W-row and the one-row score
        # contractions are different XLA:CPU dot kernels (M = W vs
        # M = 1), so the last bit may differ — see tests/test_decode.py
        np.testing.assert_allclose(out_v[:, i:i + 1], out_i,
                                   rtol=1e-6, atol=1e-6)
    # and the pools end up with the same bytes
    np.testing.assert_array_equal(np.asarray(kp1), np.asarray(kp2))
    np.testing.assert_array_equal(np.asarray(vp1), np.asarray(vp2))


def test_pallas_verify_kernel_interpret_matches_lax():
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.attention import paged_verify_attention

    rng = np.random.RandomState(5)
    P, B, W, D = 7, 2, 4, 8
    pool = value_pool_shape(P, KVB, H, D)
    kp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    vp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    table = jnp.asarray(
        np.array([[2, 5, 1, 0], [4, 3, 0, 0]], np.int32))
    q = jnp.asarray(rng.randn(B, W, H * D).astype(np.float32))
    start = jnp.asarray(np.array([5, 2], np.int32))
    want = np.asarray(paged_verify_attention(q, kp, vp, table, start, H))
    got = np.asarray(pk.paged_attention_verify(q, kp, vp, table, start,
                                               H))
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# the rejection sampler: exact target distribution, exact plain-sampler
# fallback on no-draft rows
# ---------------------------------------------------------------------------


def test_rejection_sampling_matches_target_distribution():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.speculative import verify_sample

    Vt, N = 13, 4000
    rng = np.random.RandomState(11)
    row = rng.randn(Vt).astype(np.float32) * 1.5
    base = jax.random.PRNGKey(0)
    temp = 0.7
    draft = 4  # a mid-probability token under verification

    logits = jnp.asarray(np.tile(row, (N, 2, 1)))
    fed = jnp.asarray(
        np.tile(np.array([[0, draft]], np.int32), (N, 1)))
    wlive = jnp.full((N,), 2, jnp.int32)
    temps = jnp.full((N,), temp, jnp.float32)
    seeds = jnp.arange(N, dtype=jnp.int32)
    steps0 = jnp.zeros((N,), jnp.int32)
    emit = np.asarray(jax.jit(verify_sample, static_argnums=())(
        base, logits, fed, wlive, temps, seeds, steps0))

    p = np.exp(row / temp - np.max(row / temp))
    p /= p.sum()
    # row 0 verified `draft` by rejection sampling; its marginal must
    # still be the target distribution (chi-square, df=12; the
    # statistic is deterministic — fixed seeds — so no flake margin)
    obs = np.bincount(emit[:, 0], minlength=Vt)
    chi2 = float(np.sum((obs - N * p) ** 2 / (N * p)))
    assert chi2 < 32.9, chi2  # p=0.001 critical for df=12
    # acceptance really happens (the draft is over-represented only
    # up to its own probability): both branches exercised
    assert 0 < np.sum(emit[:, 0] == draft) < N

    # row 1 has no draft: bit-identical to the plain decode sampler's
    # categorical(key, row/temp) at position steps0+1
    def plain(sd):
        key = jax.random.fold_in(jax.random.fold_in(base, sd), 1)
        return jax.random.categorical(
            key, jnp.asarray(row) / temp).astype(jnp.int32)

    want = np.asarray(jax.vmap(plain)(seeds))
    np.testing.assert_array_equal(emit[:, 1], want)

    # greedy rows emit argmax, unconditionally
    emit_g = np.asarray(verify_sample(
        base, logits, fed, wlive, jnp.zeros((N,), jnp.float32), seeds,
        steps0))
    assert (emit_g == int(np.argmax(row))).all()

    # mixed-width batch: a stream whose window is SHORTER than W must
    # get the no-draft plain-sampler path on its bonus row — a padded
    # fed column is not a draft of token 0 (regression: the emitted
    # bits must not depend on how wide the batch's window is)
    logits3 = jnp.asarray(np.tile(row, (N, 3, 1)))
    fed3 = jnp.asarray(
        np.tile(np.array([[0, draft, 0]], np.int32), (N, 1)))
    emit3 = np.asarray(verify_sample(
        base, logits3, fed3, jnp.full((N,), 2, jnp.int32), temps,
        seeds, steps0))
    np.testing.assert_array_equal(emit3[:, 0], emit[:, 0])
    np.testing.assert_array_equal(emit3[:, 1], want)  # bonus == plain


# ---------------------------------------------------------------------------
# engine: the tier-1 propose→verify→accept/reject→continue smoke
# ---------------------------------------------------------------------------


def test_spec_greedy_smoke_bit_identical(lm):
    rng = np.random.RandomState(0)
    prompt = _repetitive_prompt(rng)
    e0 = _engine(lm, spec_tokens=0)
    try:
        ref = e0.generate(prompt, 12)
        st0 = e0.stats()
    finally:
        e0.close()
    # the non-speculative loop ran ahead: all its fetches but the last
    # (nothing left to dispatch behind it) had a newer program queued
    assert st0["d2h_syncs_saved"] == st0["d2h_syncs"] - 1
    assert st0["steps_run_ahead"] == st0["steps"]
    e1 = _engine(lm, spec_tokens=3)
    try:
        out = e1.generate(prompt, 12)
        st = e1.stats()
        e1.reset_stats()
        st2 = e1.stats()
    finally:
        e1.close()
    np.testing.assert_array_equal(ref, out)
    # the step really speculated: drafts proposed, some accepted, some
    # rejected along the way, and fewer steps than tokens
    assert st["spec_steps"] > 0
    assert st["spec_proposed"] > 0
    assert 0 < st["spec_accepted"] < st["spec_proposed"]
    assert st["accepted_token_rate"] == pytest.approx(
        st["spec_accepted"] / st["spec_proposed"], abs=1e-3)
    assert st["tokens_per_step"] > 1.0
    assert st["spec_tokens"] == 3 and st["proposer"] == "ngram"
    # reset_stats zeroes the new counters too (bench sweep contract)
    for k in ("spec_steps", "spec_proposed", "spec_accepted",
              "prefill_chunks", "d2h_syncs", "d2h_syncs_saved",
              "steps_run_ahead", "run_ahead_drains", "tokens", "steps"):
        assert st2[k] == 0, k
    assert st2["accepted_token_rate"] == 0.0


@pytest.mark.slow
def test_spec_eos_mid_window(lm):
    """An accepted token that IS eos truncates the window commit."""
    rng = np.random.RandomState(0)
    prompt = _repetitive_prompt(rng)
    e0 = _engine(lm, spec_tokens=0)
    try:
        ref = e0.generate(prompt, 12)
    finally:
        e0.close()
    eos = int(ref[5])  # eos lands mid-generation (and mid-window)
    e0 = _engine(lm, spec_tokens=0)
    try:
        want = e0.generate(prompt, 12, eos_id=eos)
    finally:
        e0.close()
    e1 = _engine(lm, spec_tokens=3)
    try:
        got = e1.generate(prompt, 12, eos_id=eos)
    finally:
        e1.close()
    np.testing.assert_array_equal(want, got)
    assert got[-1] == eos and len(got) < 12


@pytest.mark.slow
def test_d2h_pipeline_counts_saved_syncs(lm):
    """The plain decode path dispatches a step before it fetches the
    one before (``d2h_syncs_saved``: fetches that gated no dispatch) —
    same output bits as an engine whose every bucket change drains."""
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, V, size=9).astype(np.int32)
    e = _engine(lm)
    try:
        out = e.generate(prompt, 16)
        st = e.stats()
    finally:
        e.close()
    assert st["d2h_syncs_saved"] == st["d2h_syncs"] - 1
    assert st["run_ahead_share"] == 1.0
    e0 = _engine(lm, max_streams=1, decode_buckets=[1])
    try:
        ref = e0.generate(prompt, 16)
    finally:
        e0.close()
    np.testing.assert_array_equal(ref, out)


class _MarkerProposer:
    """Drafts only for prompts starting with the marker token — lets a
    test pin one stream to the never-drafts path while a co-rider
    keeps the engine in verify mode."""

    def __init__(self, marker):
        self.marker = marker
        self._inner = NgramProposer()

    def propose(self, ctx, k):
        if int(ctx[0]) != self.marker:
            return np.empty(0, np.int32)
        return self._inner.propose(ctx, k)


@pytest.mark.slow
def test_temperature_no_draft_stream_bits_match_plain_engine(lm):
    """Fleet decode-retry contract under temperature: a stream that
    never drafts must emit BIT-identical tokens whether it runs on a
    plain engine or rides verify batches beside a drafting stream —
    its rows take the plain categorical(key, position) path, never a
    phantom draft from window padding."""
    rng = np.random.RandomState(12)
    marker = 1
    x_prompt = rng.randint(2, V, size=9).astype(np.int32)
    y_prompt = np.concatenate(
        [[marker], np.tile(rng.randint(2, V, size=3), 6)]) \
        .astype(np.int32)[:13]
    e0 = _engine(lm, spec_tokens=0)
    try:
        want = e0.generate(x_prompt, 10, temperature=0.9, seed=5)
    finally:
        e0.close()
    e1 = _engine(lm, spec_tokens=3,
                 proposer=_MarkerProposer(marker))
    try:
        fy = e1.submit(y_prompt, 14, temperature=0.9, seed=9)
        fx = e1.submit(x_prompt, 10, temperature=0.9, seed=5)
        got = fx.result(timeout=120)
        fy.result(timeout=120)
        st = e1.stats()
    finally:
        e1.close()
    assert st["spec_proposed"] > 0  # Y really kept verify mode on
    np.testing.assert_array_equal(want, got)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_chunked_prefill_bitmatch_monolithic(lm):
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, V, size=21).astype(np.int32)
    e0 = _engine(lm, prefill_chunk=0)
    try:
        ref = e0.generate(prompt, 8)
    finally:
        e0.close()
    e1 = _engine(lm, prefill_chunk=8)
    try:
        out = e1.generate(prompt, 8)
        st = e1.stats()
    finally:
        e1.close()
    np.testing.assert_array_equal(ref, out)
    assert st["prefill_chunks"] == 3  # 8 + 8 + 5 uncached tokens
    assert st["prefill_chunk"] == 8


@pytest.mark.slow
def test_chunked_prefill_with_prefix_hit(lm):
    """A chunked prefill registers its prompt pages; a second
    identical prompt attaches them and its chain still bit-matches."""
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, V, size=20).astype(np.int32)
    e0 = _engine(lm, prefill_chunk=0, prefix_cache=0)
    try:
        ref = e0.generate(prompt, 6)
    finally:
        e0.close()
    e1 = _engine(lm, prefill_chunk=8, prefix_cache=1)
    try:
        first = e1.generate(prompt, 6)
        st1 = e1.stats()
        again = e1.generate(prompt, 6)
        st2 = e1.stats()
    finally:
        e1.close()
    np.testing.assert_array_equal(ref, first)
    np.testing.assert_array_equal(ref, again)
    assert st1["prefill_chunks"] >= 2
    # the re-submission hit the prefix cache: its uncached suffix fits
    # one chunk, so no NEW chunked prefill ran
    assert st2["prefix_hits"] >= 1
    assert st2["prefill_chunks"] == st1["prefill_chunks"]


@pytest.mark.slow
def test_chunked_prefill_beyond_prefill_ladder(lm):
    """Chunking admits prompts LONGER than the largest prefill bucket
    — each chunk buckets individually."""
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, V, size=30).astype(np.int32)
    e = _engine(lm, prefill_chunk=8, prefill_buckets=[8, 16])
    try:
        out = e.generate(prompt, 4)
    finally:
        e.close()
    e0 = _engine(lm)
    try:
        ref = e0.generate(prompt, 4)
    finally:
        e0.close()
    np.testing.assert_array_equal(ref, out)
    # without chunking the same ladder refuses the prompt loudly
    e1 = _engine(lm, prefill_buckets=[8, 16])
    try:
        with pytest.raises(mx.MXNetError, match="prefill bucket"):
            e1.submit(prompt, 4)
    finally:
        e1.close()


# ---------------------------------------------------------------------------
# env validation (the loud-at-construction contract)
# ---------------------------------------------------------------------------


def test_spec_env_validation(lm, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_SPEC_TOKENS", "banana")
    with pytest.raises(mx.MXNetError, match="SPEC_TOKENS"):
        _engine(lm)
    monkeypatch.setenv("MXNET_SERVING_SPEC_TOKENS", "-1")
    with pytest.raises(mx.MXNetError, match="SPEC_TOKENS"):
        _engine(lm)
    monkeypatch.delenv("MXNET_SERVING_SPEC_TOKENS")
    monkeypatch.setenv("MXNET_SERVING_PROPOSER", "banana")
    with pytest.raises(mx.MXNetError, match="PROPOSER"):
        _engine(lm)
    monkeypatch.delenv("MXNET_SERVING_PROPOSER")
    monkeypatch.setenv("MXNET_SERVING_PREFILL_CHUNK", "-4")
    with pytest.raises(mx.MXNetError, match="PREFILL_CHUNK"):
        _engine(lm)
    monkeypatch.setenv("MXNET_SERVING_PREFILL_CHUNK", "10")
    with pytest.raises(mx.MXNetError, match="multiple of kv_block"):
        _engine(lm)  # kv_block 4 does not divide 10
    monkeypatch.delenv("MXNET_SERVING_PREFILL_CHUNK")
    with pytest.raises(mx.MXNetError, match="propose"):
        _engine(lm, spec_tokens=2, proposer=object())


# ---------------------------------------------------------------------------
# slow: batch composition, prefix hits, mixed load
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_spec_bit_identity_across_batch_composition_and_hits(lm):
    """Concurrent streams with staggered lengths (streams join and
    retire mid-flight, so every batch composition appears), plus a
    repeated prompt (a prefix-cache full hit entering verify through
    the COW replay path): every speculative greedy output equals the
    solo non-speculative one."""
    rng = np.random.RandomState(7)
    reqs = [( _repetitive_prompt(rng, n=10 + 2 * i), 6 + 3 * i)
            for i in range(4)]
    reqs.append((reqs[0][0], 8))  # exact repeat: full/partial hit
    e0 = _engine(lm, spec_tokens=0, prefix_cache=1)
    try:
        want = [e0.generate(p, n) for p, n in reqs]
    finally:
        e0.close()
    e1 = _engine(lm, spec_tokens=3, prefix_cache=1)
    try:
        futs = []
        for i, (p, n) in enumerate(reqs):
            futs.append(e1.submit(p, n))
            if i == 2:  # stagger: let the first batch shrink/grow
                futs[0].result(timeout=60)
        got = [f.result(timeout=120) for f in futs]
        st = e1.stats()
    finally:
        e1.close()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert st["prefix_hits"] >= 1 and st["spec_steps"] > 0


@pytest.mark.slow
def test_chunked_prefill_interleaves_with_decode(lm):
    """While a long prompt prefills in chunks, already-active streams
    keep stepping between chunks — and both outputs stay bit-exact."""
    rng = np.random.RandomState(8)
    long_prompt = rng.randint(1, V, size=28).astype(np.int32)
    chat = rng.randint(1, V, size=6).astype(np.int32)
    e0 = _engine(lm)
    try:
        want_long = e0.generate(long_prompt, 6)
        want_chat = e0.generate(chat, 16)
    finally:
        e0.close()
    e = _engine(lm, prefill_chunk=8)
    try:
        f_chat = e.submit(chat, 16)
        # wait until the chat stream is actively decoding
        deadline = threading.Event()
        for _ in range(200):
            if e.stats()["active_streams"] >= 1:
                break
            deadline.wait(0.01)
        f_long = e.submit(long_prompt, 6)
        got_chat = f_chat.result(timeout=120)
        got_long = f_long.result(timeout=120)
        st = e.stats()
    finally:
        e.close()
    np.testing.assert_array_equal(want_chat, got_chat)
    np.testing.assert_array_equal(want_long, got_long)
    assert st["prefill_chunks"] >= 4  # 28 uncached tokens / 8


@pytest.mark.slow
def test_spec_with_quantized_kv_chains_token_equal(lm):
    """Speculation composes with the int8 KV cache: the verify window
    reads its own keys back through the quantized pools exactly like
    the sequential decode step, so spec-vs-plain chains stay
    token-equal at int8 too."""
    rng = np.random.RandomState(9)
    prompt = _repetitive_prompt(rng, n=12)
    e0 = _engine(lm, kv_dtype="int8")
    try:
        ref = e0.generate(prompt, 10)
    finally:
        e0.close()
    e1 = _engine(lm, kv_dtype="int8", spec_tokens=3)
    try:
        out = e1.generate(prompt, 10)
    finally:
        e1.close()
    np.testing.assert_array_equal(ref, out)
