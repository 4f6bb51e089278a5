"""Multi-tenant serving tests: the paged LoRA adapter pool, per-stream
adapter gather inside the fused decode program, tenant quotas,
SLO-tiered admission, and the draft-LM proposer.

The contracts, in order of appearance:

* :class:`AdapterPool` lifecycle — publish parks, acquire
  revives/shares, eviction is strict LRU over PARKED slots only (a
  held slot id never changes under a stream), retire defers to the
  last holder, an evicted adapter re-installs from the host copy
  (a countable miss, never a failure);
* :class:`TenantQuota` token buckets shed with the TYPED
  :class:`QuotaExceededError` and refill against an injectable clock;
* bit-identity — a no-adapter stream through an adapter-enabled
  engine is BIT-identical to the pre-adapter engine; an adapter
  stream greedy-matches a merged-weights (``W + scale·(A@B)ᵀ``)
  reference run, solo and in mixed-tenant batches, composed with
  prefix cache, speculation, quantized KV, and preemption;
* hot publish/retire under load sheds nothing;
* interactive admission jumps the batch queue;
* per-tenant cost attribution obeys the same conservation the
  per-class records do;
* the draft-LM proposer is deterministic, greedy-safe, and validates
  its env loudly.

Fast variants run in tier-1; the wide sweeps are marked ``slow``.
"""

import threading
import time

import numpy as np
import pytest

from mxnet_tpu.adapters import (AdapterPool, QuotaExceededError,
                                TenantQuota, adapters_enabled,
                                pool_from_env, quota_from_env)
from mxnet_tpu.base import MXNetError
from mxnet_tpu.speculative import DraftLMProposer, make_proposer

from _engines import (KVB, DM, H, L, V,  # noqa: E402
                      dense_engine as _engine, tiny_lm_params,
                      tiny_lm_reference)


# ---------------------------------------------------------------------------
# pool unit tests (no engine)
# ---------------------------------------------------------------------------


def _pool(**kw):
    args = dict(num_layers=L, d_model=DM, slots=2, rank_buckets=(4,))
    args.update(kw)
    return AdapterPool(**args)


def _ab(rng, r=4, scale=0.1):
    return (rng.randn(L, DM, r).astype(np.float32) * scale,
            rng.randn(L, r, 3 * DM).astype(np.float32) * scale)


def test_pool_lifecycle_refcounts():
    rng = np.random.RandomState(0)
    p = _pool()
    a, b = _ab(rng)
    slot = p.publish("x", a, b)
    assert slot >= 1  # slot 0 is the reserved null adapter
    assert p.refcount("x") == 0  # published = parked, not held
    bk, s1 = p.acquire("x")
    assert (bk, s1) == (4, slot) and p.refcount("x") == 1
    bk2, s2 = p.acquire("x")  # second stream shares the slot
    assert s2 == s1 and p.refcount("x") == 2
    p.release("x")
    p.release("x")
    assert p.refcount("x") == 0
    st = p.stats()
    assert st["publishes"] == 1 and st["hits"] == 2
    assert st["buckets"]["r4"]["parked"] == 1
    # retire of a parked adapter frees the slot NOW
    assert p.retire("x") is True
    with pytest.raises(MXNetError, match="unknown adapter"):
        p.acquire("x")


def test_pool_lru_eviction_is_deterministic_and_misses_reinstall():
    rng = np.random.RandomState(1)
    p = _pool(slots=2)
    for name in ("a", "b"):
        p.publish(name, *_ab(rng))
    # touch "a" so "b" is the LRU parked slot
    p.acquire("a")
    p.release("a")
    p.publish("c", *_ab(rng))  # pool full: evicts parked LRU = "b"
    assert p.stats()["evictions"] == 1
    # "b" re-installs from the host copy — a miss, not an error
    misses0 = p.stats()["misses"]
    p.acquire("b")
    assert p.stats()["misses"] == misses0 + 1
    p.release("b")


def test_pool_live_slots_never_evict():
    rng = np.random.RandomState(2)
    p = _pool(slots=1)
    p.publish("x", *_ab(rng))
    p.acquire("x")  # held: the only slot is live
    with pytest.raises(MXNetError, match="held by live streams"):
        p.publish("y", *_ab(rng))
    p.release("x")
    p.publish("y", *_ab(rng))  # parked "x" is now evictable


def test_pool_retire_defers_to_last_holder():
    rng = np.random.RandomState(3)
    p = _pool()
    p.publish("x", *_ab(rng))
    p.acquire("x")
    assert p.retire("x") is False  # deferred: a stream holds it
    with pytest.raises(MXNetError, match="retiring"):
        p.acquire("x")  # no NEW streams during a deferred retire
    p.release("x")  # last holder out -> slot freed, name gone
    with pytest.raises(MXNetError, match="unknown adapter"):
        p.bucket_of("x")


def test_pool_rank_buckets_and_validation():
    rng = np.random.RandomState(4)
    p = _pool(rank_buckets=(4, 8))
    a, b = _ab(rng, r=3)
    p.publish("r3", a, b)
    assert p.bucket_of("r3") == 4  # rank 3 pads into bucket 4
    a, b = _ab(rng, r=8)
    p.publish("r8", a, b)
    assert p.bucket_of("r8") == 8
    with pytest.raises(MXNetError, match="exceeds the largest"):
        p.publish("r9", *_ab(rng, r=9))
    with pytest.raises(MXNetError, match="already published"):
        p.publish("r3", *_ab(rng, r=3))
    with pytest.raises(MXNetError, match="A must be"):
        p.publish("bad", np.zeros((L, DM + 1, 4), np.float32),
                  np.zeros((L, 4, 3 * DM), np.float32))
    with pytest.raises(MXNetError, match="B must be"):
        p.publish("bad", np.zeros((L, DM, 4), np.float32),
                  np.zeros((L, 5, 3 * DM), np.float32))
    with pytest.raises(MXNetError, match="retire of unknown"):
        p.retire("nope")


def test_quota_typed_shed_refund_and_refill():
    q = TenantQuota(10)
    q.charge("t", 6)
    with pytest.raises(QuotaExceededError) as ei:
        q.charge("t", 6)
    assert ei.value.reason == "tenant_quota"
    assert ei.value.tenant == "t" and ei.value.needed == 6
    q.refund("t", 4)
    q.charge("t", 6)  # 4 left + 4 refunded = 8 >= 6
    st = q.stats()
    assert st["t"]["shed"] == 1 and st["t"]["charged"] == 12
    # refill against a pinned clock
    now = [0.0]
    q2 = TenantQuota(10, refill_rate=2.0, clock=lambda: now[0])
    q2.charge("u", 10)
    now[0] = 3.0  # 6 tokens refilled
    assert q2.balance("u") == pytest.approx(6.0)
    q2.charge("u", 6)
    # capacity 0 = quotas off: never charges, never sheds
    TenantQuota(0).charge("v", 10 ** 9)


def test_adapter_env_validation(monkeypatch):
    monkeypatch.setenv("MXNET_ADAPTER_SLOTS", "banana")
    with pytest.raises(MXNetError, match="MXNET_ADAPTER_SLOTS"):
        pool_from_env(L, DM)
    monkeypatch.setenv("MXNET_ADAPTER_SLOTS", "0")
    with pytest.raises(MXNetError, match="MXNET_ADAPTER_SLOTS"):
        pool_from_env(L, DM)
    monkeypatch.setenv("MXNET_ADAPTER_SLOTS", "3")
    monkeypatch.setenv("MXNET_ADAPTER_RANK_BUCKETS", "8,4")
    with pytest.raises(MXNetError, match="MXNET_ADAPTER_RANK_BUCKETS"):
        pool_from_env(L, DM)
    monkeypatch.setenv("MXNET_ADAPTER_RANK_BUCKETS", "4,8")
    p = pool_from_env(L, DM)
    assert p.slots == 3 and p.rank_buckets == (4, 8)
    monkeypatch.setenv("MXNET_ADAPTER_ENABLE", "2")
    with pytest.raises(MXNetError, match="MXNET_ADAPTER_ENABLE"):
        adapters_enabled()
    monkeypatch.setenv("MXNET_TENANT_QUOTA_TOKENS", "-1")
    with pytest.raises(MXNetError, match="MXNET_TENANT_QUOTA_TOKENS"):
        quota_from_env()
    monkeypatch.setenv("MXNET_TENANT_QUOTA_TOKENS", "0")
    assert quota_from_env() is None
    monkeypatch.setenv("MXNET_TENANT_QUOTA_TOKENS", "100")
    monkeypatch.setenv("MXNET_TENANT_QUOTA_REFILL", "nope")
    with pytest.raises(MXNetError, match="MXNET_TENANT_QUOTA_REFILL"):
        quota_from_env()
    monkeypatch.setenv("MXNET_TENANT_QUOTA_REFILL", "2.5")
    q = quota_from_env()
    assert q.capacity == 100 and q.refill_rate == 2.5


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    """The tiny LM's parameters.  Every engine below is its test's own:
    each has its own pool of adapters, quota or proposer."""
    return tiny_lm_params()


def _adapters(rng, n=4):
    """N distinct adapters spanning both rank buckets."""
    out = {}
    for i, r in zip(range(n), (2, 4, 4, 8, 3, 8)):
        a = rng.randn(L, DM, r).astype(np.float32) * 0.25
        b = rng.randn(L, r, 3 * DM).astype(np.float32) * 0.25
        out[f"ad{i}"] = (a, b, 2.0 * r)  # alpha -> scale 2.0
    return out


def _merged(params, a, b, alpha):
    """The merged-weights reference: ``W' = W + scale·(A_i @ B_i)ᵀ``
    on each layer's fused QKV projection — what serving adapter
    streams must greedy-match."""
    r = a.shape[2]
    scale = float(alpha) / r
    out = {k: v for k, v in params.items()}
    for i in range(L):
        w = np.asarray(out[f"layer{i}_qkv_weight"].asnumpy()
                       if hasattr(out[f"layer{i}_qkv_weight"],
                                  "asnumpy")
                       else out[f"layer{i}_qkv_weight"])
        delta = (a[i] @ b[i]) * scale        # (DM, 3DM)
        out[f"layer{i}_qkv_weight"] = (w + delta.T).astype(w.dtype)
    return out


@pytest.fixture(scope="module")
def naive():
    """Greedy reference through the UNPAGED prefill symbol with
    arbitrary (possibly merged) params."""
    return lambda params, prompt, n: \
        tiny_lm_reference(params)[1](prompt, n)


def test_no_adapter_streams_bit_identical_to_pre_adapter_engine(lm):
    """An adapter-enabled engine must not perturb a single bit for
    streams that name no adapter — slot 0 where-selects base bits."""
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (3, 7, 11)]
    e0 = _engine(lm)  # the pre-adapter engine
    pool = AdapterPool(num_layers=L, d_model=DM, slots=4,
                       rank_buckets=(4, 8))
    e1 = _engine(lm, adapters=pool)
    # a published (unused) adapter must not change anything either
    r8 = np.random.RandomState(8)
    e1.publish_adapter("idle",
                       r8.randn(L, DM, 4).astype(np.float32),
                       r8.randn(L, 4, 3 * DM).astype(np.float32))
    try:
        for i, p in enumerate(prompts):
            for temp in (0.0, 0.9):
                g0 = e0.generate(p, max_new_tokens=6,
                                 temperature=temp, seed=100 + i)
                g1 = e1.generate(p, max_new_tokens=6,
                                 temperature=temp, seed=100 + i)
                assert np.array_equal(g0, g1), (temp, i)
    finally:
        e0.close()
        e1.close()


def test_adapter_streams_match_merged_weights_solo_and_mixed(lm, naive):
    """THE acceptance contract: N=4 adapters over one base, each
    stream greedy-equal to a merged-weights solo reference — solo and
    in mixed-tenant batches (batch composition never changes tokens),
    with the no-adapter stream untouched."""
    rng = np.random.RandomState(11)
    ads = _adapters(rng, n=4)
    pool = AdapterPool(num_layers=L, d_model=DM, slots=4,
                       rank_buckets=(4, 8))
    eng = _engine(lm, adapters=pool)
    prompt = rng.randint(1, V, size=5).astype(np.int32)
    NEW = 6
    try:
        refs = {}
        for name, (a, b, alpha) in ads.items():
            eng.publish_adapter(name, a, b, alpha=alpha)
            refs[name] = naive(_merged(lm, a, b, alpha), prompt, NEW)
        refs[None] = naive(lm, prompt, NEW)
        # solo runs
        solo = {}
        for name in list(ads) + [None]:
            solo[name] = eng.generate(prompt, max_new_tokens=NEW,
                                      tenant=name and f"tn-{name}",
                                      adapter=name)
            assert np.array_equal(solo[name], refs[name]), name
        # mixed batch: all four adapters + the plain stream at once
        futs = {name: eng.submit(prompt, NEW,
                                 tenant=name and f"tn-{name}",
                                 adapter=name)
                for name in list(ads) + [None]}
        for name, f in futs.items():
            assert np.array_equal(f.result(timeout=60), solo[name]), \
                f"mixed batch changed stream {name!r}"
        st = eng.stats()
        assert st["adapters"]["published"] == 4
        assert set(st["cost_by_tenant"]) == {f"tn-{n}" for n in ads}
        assert st["tenants"][f"tn-ad0"]["requests"] == 2
    finally:
        eng.close()


def test_adapters_compose_with_prefix_spec_and_preemption(lm, naive):
    """Adapter gather composed with the rest of the serving stack:
    prefix cache + n-gram speculation + a pool small enough to force
    preemption — greedy outputs still match the merged reference."""
    rng = np.random.RandomState(13)
    a = rng.randn(L, DM, 4).astype(np.float32) * 0.25
    b = rng.randn(L, 4, 3 * DM).astype(np.float32) * 0.25
    pool = AdapterPool(num_layers=L, d_model=DM, slots=2,
                       rank_buckets=(4,))
    eng = _engine(lm, adapters=pool, prefix_cache=1, spec_tokens=2,
                  cache_blocks=12)
    prompt = np.asarray([3, 9, 3, 9, 3, 9, 4, 4], np.int32)
    NEW = 5
    try:
        eng.publish_adapter("x", a, b, alpha=8.0)
        ref = naive(_merged(lm, a, b, 8.0), prompt, NEW)
        base = naive(lm, prompt, NEW)
        # twice: the second run rides prefix-cache hits
        for _ in range(2):
            got = eng.generate(prompt, max_new_tokens=NEW,
                               tenant="t", adapter="x")
            assert np.array_equal(got, ref)
            assert np.array_equal(
                eng.generate(prompt, max_new_tokens=NEW), base)
        # saturate the tiny pool to force preemption mid-decode
        futs = [eng.submit(rng.randint(1, V, size=9).astype(np.int32),
                           12, adapter="x" if i % 2 else None,
                           tenant="t" if i % 2 else None)
                for i in range(4)]
        for f in futs:
            f.result(timeout=120)
        # the adapter stream survives preemption with its slot pinned
        got = eng.generate(prompt, max_new_tokens=NEW,
                           tenant="t", adapter="x")
        assert np.array_equal(got, ref)
    finally:
        eng.close()


def test_prefix_cache_is_adapter_namespaced(lm, naive):
    """REGRESSION (found by the merged-weights acceptance test): the
    prefix radix index is salted by adapter name — a prompt prefilled
    plain must not satisfy an adapter stream (its K/V lacks the
    delta), and retire-then-republish of the SAME name must not serve
    chains prefilled under the old weights."""
    rng = np.random.RandomState(31)
    a1 = rng.randn(L, DM, 4).astype(np.float32) * 0.25
    b1 = rng.randn(L, 4, 3 * DM).astype(np.float32) * 0.25
    a2 = rng.randn(L, DM, 4).astype(np.float32) * 0.25
    b2 = rng.randn(L, 4, 3 * DM).astype(np.float32) * 0.25
    pool = AdapterPool(num_layers=L, d_model=DM, slots=2,
                       rank_buckets=(4,))
    eng = _engine(lm, adapters=pool, prefix_cache=1)
    prompt = rng.randint(1, V, size=9).astype(np.int32)
    NEW = 5
    try:
        ref1 = naive(_merged(lm, a1, b1, 4.0), prompt, NEW)
        ref2 = naive(_merged(lm, a2, b2, 4.0), prompt, NEW)
        base = naive(lm, prompt, NEW)
        eng.publish_adapter("x", a1, b1, alpha=4.0)
        # seed the UNSALTED tree first: the adapter stream right after
        # must not ride the plain stream's registered pages
        assert np.array_equal(
            eng.generate(prompt, max_new_tokens=NEW), base)
        assert np.array_equal(
            eng.generate(prompt, max_new_tokens=NEW, adapter="x"),
            ref1)
        # and the salted chains must not leak back into plain streams
        assert np.array_equal(
            eng.generate(prompt, max_new_tokens=NEW), base)
        # retire + republish the SAME name with different weights:
        # the old salted chains must be invalidated, not re-matched
        assert eng.retire_adapter("x") is True
        eng.publish_adapter("x", a2, b2, alpha=4.0)
        assert np.array_equal(
            eng.generate(prompt, max_new_tokens=NEW, adapter="x"),
            ref2)
    finally:
        eng.close()


def test_adapter_with_quantized_kv_token_equal_to_merged_engine(lm):
    """int8 KV pools quantize the adapter stream and the merged
    reference identically, so the engines must emit the same
    tokens."""
    rng = np.random.RandomState(17)
    a = rng.randn(L, DM, 4).astype(np.float32) * 0.25
    b = rng.randn(L, 4, 3 * DM).astype(np.float32) * 0.25
    pool = AdapterPool(num_layers=L, d_model=DM, slots=2,
                       rank_buckets=(4,))
    e1 = _engine(lm, adapters=pool, kv_dtype="int8")
    e2 = _engine(_merged(lm, a, b, 8.0), kv_dtype="int8")
    prompt = rng.randint(1, V, size=6).astype(np.int32)
    try:
        e1.publish_adapter("x", a, b, alpha=8.0)
        got = e1.generate(prompt, max_new_tokens=6, adapter="x")
        ref = e2.generate(prompt, max_new_tokens=6)
        assert np.array_equal(got, ref)
    finally:
        e1.close()
        e2.close()


def test_hot_publish_retire_under_load_sheds_nothing(lm):
    """Publish and retire adapters while a background load runs: no
    request fails, no shed, no drain — and streams submitted against
    each new adapter resolve."""
    rng = np.random.RandomState(19)
    pool = AdapterPool(num_layers=L, d_model=DM, slots=3,
                       rank_buckets=(4,))
    eng = _engine(lm, adapters=pool)
    stop = threading.Event()
    failures = []

    def load():
        i = 0
        while not stop.is_set():
            try:
                eng.generate(rng.randint(1, V, size=4).astype(np.int32),
                             max_new_tokens=4, seed=i)
            except BaseException as exc:  # noqa: BLE001
                failures.append(exc)
                return
            i += 1

    t = threading.Thread(target=load, daemon=True)
    t.start()
    try:
        prompt = np.asarray([5, 4, 3, 2], np.int32)
        for gen in range(4):
            name = f"gen{gen}"
            a = rng.randn(L, DM, 4).astype(np.float32) * 0.2
            b = rng.randn(L, 4, 3 * DM).astype(np.float32) * 0.2
            eng.publish_adapter(name, a, b, alpha=4.0)
            out = eng.generate(prompt, max_new_tokens=4, adapter=name,
                               tenant="hot")
            assert out.size == 4
            eng.retire_adapter(name)
            with pytest.raises(MXNetError):
                eng.generate(prompt, max_new_tokens=4, adapter=name)
    finally:
        stop.set()
        t.join(timeout=30)
        st = eng.stats()
        eng.close()
    assert not failures
    assert st["shed"] == 0 and st["shed_tenant_quota"] == 0


def test_tenant_quota_sheds_typed_with_fairness_counters(lm):
    q = TenantQuota(20)
    eng = _engine(lm, tenant_quota=q)
    prompt = np.asarray([1, 2, 3], np.int32)  # 3 + 5 = 8 tokens/req
    try:
        eng.generate(prompt, max_new_tokens=5, tenant="small")
        eng.generate(prompt, max_new_tokens=5, tenant="small")
        with pytest.raises(QuotaExceededError) as ei:
            eng.submit(prompt, 5, tenant="small")
        assert ei.value.reason == "tenant_quota"
        # another tenant's bucket is untouched — per-tenant fairness
        eng.generate(prompt, max_new_tokens=5, tenant="big")
        st = eng.stats()
        assert st["shed_tenant_quota"] == 1
        assert st["tenants"]["small"]["shed"] == 1
        assert st["tenants"]["small"]["requests"] == 2
        assert st["tenants"]["big"]["shed"] == 0
        assert st["tenants"]["small"]["balance"] == 4
    finally:
        eng.close()


def test_interactive_admission_jumps_batch_queue(lm):
    """With one decode seat, a queued interactive request is admitted
    before batch requests that were enqueued AHEAD of it."""
    eng = _engine(lm, max_streams=1, decode_buckets=[1])
    prompt = np.asarray([2, 4, 6], np.int32)
    order = []
    lock = threading.Lock()

    def tag(name):
        def cb(_f):
            with lock:
                order.append(name)
        return cb

    try:
        f0 = eng.submit(prompt, 10)  # occupies the only seat
        time.sleep(0.05)
        fb = eng.submit(prompt, 2, slo_class="batch")
        fb2 = eng.submit(prompt, 2, slo_class="batch")
        fi = eng.submit(prompt, 2, slo_class="interactive")
        for f, n in ((fb, "batch1"), (fb2, "batch2"), (fi, "inter")):
            f.add_done_callback(tag(n))
        for f in (f0, fb, fb2, fi):
            f.result(timeout=60)
    finally:
        eng.close()
    assert order.index("inter") < order.index("batch1")
    assert order.index("inter") < order.index("batch2")


def test_cost_records_carry_tenant_and_conserve(lm):
    eng = _engine(lm)
    prompt = np.asarray([1, 2, 3, 4], np.int32)
    try:
        eng.generate(prompt, max_new_tokens=4, tenant="a")
        eng.generate(prompt, max_new_tokens=6, tenant="a")
        eng.generate(prompt, max_new_tokens=4, tenant="b")
        eng.generate(prompt, max_new_tokens=4)  # unattributed
        recs = eng.cost_records()
        by_tenant = eng.stats()["cost_by_tenant"]
    finally:
        eng.close()
    assert {r.get("tenant") for r in recs} == {"a", "b", None}
    for ten in ("a", "b"):
        mine = [r for r in recs if r.get("tenant") == ten]
        assert by_tenant[ten]["requests"] == len(mine)
        for field in ("tokens", "decode_steps", "flops_est"):
            assert by_tenant[ten][field] == pytest.approx(
                sum(r[field] for r in mine)), (ten, field)
    # the unattributed stream appears in NO tenant bucket
    assert None not in by_tenant and "None" not in by_tenant


def test_adapter_id_rides_cost_records(lm):
    rng = np.random.RandomState(23)
    pool = AdapterPool(num_layers=L, d_model=DM, slots=2,
                       rank_buckets=(4,))
    eng = _engine(lm, adapters=pool)
    try:
        eng.publish_adapter("x", *(_ab(rng)[:2]), alpha=4.0)
        eng.generate(np.asarray([1, 2], np.int32), max_new_tokens=3,
                     tenant="t", adapter="x")
        rec = eng.cost_records()[-1]
    finally:
        eng.close()
    assert rec["tenant"] == "t" and rec["adapter_id"] == "x"


def test_engine_rejects_adapter_without_pool_and_bad_geometry(lm):
    eng = _engine(lm)
    try:
        with pytest.raises(MXNetError, match="no adapter pool"):
            eng.submit(np.asarray([1, 2], np.int32), 2, adapter="x")
        with pytest.raises(MXNetError, match="publish_adapter"):
            eng.publish_adapter("x", np.zeros((L, DM, 4), np.float32),
                                np.zeros((L, 4, 3 * DM), np.float32))
    finally:
        eng.close()
    bad = AdapterPool(num_layers=L + 1, d_model=DM)
    with pytest.raises(MXNetError, match="geometry"):
        _engine(lm, adapters=bad)


# ---------------------------------------------------------------------------
# draft-LM proposer
# ---------------------------------------------------------------------------


def test_draft_lm_proposer_deterministic_and_greedy(lm, naive):
    prop = DraftLMProposer(lm, num_heads=H, kv_block=KVB)
    assert prop.vocab_size == V
    ctx = np.asarray([3, 1, 4, 1, 5], np.int32)
    d1 = prop.propose(ctx, 4)
    d2 = prop.propose(ctx, 4)
    assert np.array_equal(d1, d2)  # a pure function of the context
    # greedy drafts ARE the model's greedy continuation
    assert np.array_equal(d1, naive(lm, ctx, 4))


def test_draft_lm_speculation_bit_identical_and_accepts(lm):
    """Draft == target here, so speculation must accept nearly every
    draft AND stay bit-identical to the non-speculative engine (the
    verify-op contract extends to the draft-LM proposer)."""
    rng = np.random.RandomState(29)
    prompt = rng.randint(1, V, size=6).astype(np.int32)
    e0 = _engine(lm, spec_tokens=0)
    try:
        ref = e0.generate(prompt, max_new_tokens=10)
    finally:
        e0.close()
    prop = DraftLMProposer(lm, num_heads=H, kv_block=KVB)
    e1 = _engine(lm, spec_tokens=3, proposer=prop)
    try:
        got = e1.generate(prompt, max_new_tokens=10)
        st = e1.stats()
    finally:
        e1.close()
    assert np.array_equal(got, ref)
    assert st["spec_proposed"] > 0
    # identical draft/target: acceptance far above the 12-19% n-gram
    # noise floor recorded in PERF.md
    assert st["accepted_token_rate"] > 0.5


def test_draft_lm_env_and_vocab_validation(lm, monkeypatch, tmp_path):
    monkeypatch.delenv("MXNET_SERVING_DRAFT_CKPT", raising=False)
    with pytest.raises(MXNetError, match="MXNET_SERVING_DRAFT_CKPT"):
        make_proposer("draft_lm")
    with pytest.raises(MXNetError, match="MXNET_SERVING_DRAFT_HEADS"):
        DraftLMProposer(lm, num_heads=0)
    with pytest.raises(MXNetError, match="MXNET_SERVING_DRAFT_HEADS"):
        DraftLMProposer(lm, num_heads=3)  # does not divide d_model
    missing = {k: v for k, v in lm.items() if k != "tok_embed_weight"}
    with pytest.raises(MXNetError, match="MXNET_SERVING_DRAFT_CKPT"):
        DraftLMProposer(missing, num_heads=H)
    # a draft over a DIFFERENT vocab is refused at engine construction
    bigger = {}
    for k, v in lm.items():
        arr = np.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v)
        if k in ("tok_embed_weight", "head_weight"):
            arr = np.concatenate([arr, arr[-1:]], axis=0)
        elif k == "head_bias":
            arr = np.concatenate([arr, arr[-1:]])
        bigger[k] = arr
    prop = DraftLMProposer(bigger, num_heads=H, kv_block=KVB)
    assert prop.vocab_size == V + 1
    with pytest.raises(MXNetError, match="vocab"):
        _engine(lm, spec_tokens=2, proposer=prop)


# ---------------------------------------------------------------------------
# fleet layer
# ---------------------------------------------------------------------------


def test_wire_spec_roundtrips_tenancy_fields():
    from mxnet_tpu.fleet import _pack_spec, _unpack_spec

    spec = {"kind": "decode", "prompt": np.asarray([1, 2, 3], np.int32),
            "max_new": 4, "temperature": None, "eos": None, "seed": 9,
            "phase": 0, "slo_class": "batch", "tenant": "acme",
            "adapter": "fr-legal"}
    got = _unpack_spec(memoryview(_pack_spec(spec)), 0)
    assert got["slo_class"] == "batch"
    assert got["tenant"] == "acme" and got["adapter"] == "fr-legal"
    spec.update(tenant=None, adapter=None, slo_class="interactive")
    got = _unpack_spec(memoryview(_pack_spec(spec)), 0)
    assert got["tenant"] is None and got["adapter"] is None
    assert got["slo_class"] == "interactive"


class _FakeAdapterReplica:
    """Minimal in-process replica with the adapter surface."""

    def __init__(self, rid, fail_publish=False):
        self.rid = rid
        self.fail_publish = fail_publish
        self.published = []
        self.retired = []

    def publish_adapter(self, name, a, b, alpha=None):
        if self.fail_publish:
            raise MXNetError("no pool here")
        self.published.append(name)
        return len(self.published)

    def retire_adapter(self, name):
        self.retired.append(name)
        return True

    def submit(self, spec):
        from concurrent.futures import Future

        fut = Future()
        fut.set_result([np.zeros(int(spec["max_new"]), np.int32)])
        return fut

    def inflight(self):
        return 0

    def drain(self, timeout=30.0):
        return 0

    def resume(self):
        pass

    def stats(self):
        return {}

    def close(self):
        pass


def test_router_broadcasts_publish_and_rolls_back_on_failure():
    from mxnet_tpu.fleet import Router

    reps = [_FakeAdapterReplica(0), _FakeAdapterReplica(1)]
    r = Router(reps, default_deadline_ms=0)
    try:
        a = np.zeros((L, DM, 4), np.float32)
        b = np.zeros((L, 4, 3 * DM), np.float32)
        out = r.publish_adapter("x", a, b, alpha=4.0)
        assert set(out["slots"]) == {0, 1}
        assert all(rep.published == ["x"] for rep in reps)
        assert r.stats()["adapters_published"] == ["x"]
        out = r.retire_adapter("x")
        assert out["freed"] == {0: True, 1: True}
        assert r.stats()["adapters_published"] == []
        # partial failure: the success is rolled back, the call raises
        reps[1].fail_publish = True
        with pytest.raises(MXNetError, match="rolled back"):
            r.publish_adapter("y", a, b)
        assert "y" in reps[0].retired
        assert r.stats()["adapters_published"] == []
    finally:
        r.close()


def test_router_tenant_quota_sheds_typed_at_accept():
    from mxnet_tpu.fleet import Router, ShedError

    reps = [_FakeAdapterReplica(0)]
    r = Router(reps, default_deadline_ms=0,
               tenant_quota=TenantQuota(20))
    prompt = np.asarray([1, 2, 3], np.int32)
    try:
        r.generate(prompt, max_new_tokens=5,
                   tenant="small").result(timeout=30)
        r.generate(prompt, max_new_tokens=5,
                   tenant="small").result(timeout=30)
        with pytest.raises(ShedError) as ei:
            r.generate(prompt, max_new_tokens=5, tenant="small")
        assert ei.value.reason == "tenant_quota"
        r.generate(prompt, max_new_tokens=5,
                   tenant="big").result(timeout=30)
        st = r.stats()
        assert st["shed_tenant_quota"] == 1
        assert st["tenants"]["small"]["shed"] == 1
        assert st["tenants"]["small"]["requests"] == 2
        assert st["tenants"]["big"]["requests"] == 1
    finally:
        r.close()
