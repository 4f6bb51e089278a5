"""The hybrid language-model family (``models/hybrid_lm.py``) against
its plain reference (``benchmark/reference/solar_open2.py``) at small
sizes, seeded weights, float32: each new op against its plain form, the
engine through pages AND slots against the reference's full forward,
the refusals, and the test that ties one chip's share of the experts to
the whole layer."""

import copy
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.kv_cache import SlotAllocator, conv_tail_shape  # noqa: E402
from mxnet_tpu.ops import hybrid  # noqa: E402
from mxnet_tpu.ops.registry import OpContext, get_op  # noqa: E402

from benchmark.reference import solar_open2 as ref  # noqa: E402

# the published shape at a size a test can hold: one period (layer 0
# gated GQA, layers 1-3 KDA), 16 query heads over 2 KV heads, 4 of 32
# experts held, 4 a token, one shared
CFG = {
    "family": "solar_open2", "hidden_size": 64, "num_hidden_layers": 4,
    "num_hidden_layers_published": 48, "num_attention_heads": 16,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 96,
    "rms_norm_eps": 1e-5, "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
    "kda_allow_neg_eigval": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4},
    "n_routed_experts": 4, "n_routed_experts_published": 32,
    "first_expert": 0, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "initializer_range": 0.02,
}


@pytest.fixture(params=[False, True], ids=["lax", "pallas"])
def kernels(request, monkeypatch):
    """Both bodies of every op: the lax fallback and the Pallas kernels
    (interpreted on the CPU)."""
    monkeypatch.setenv("MXNET_PALLAS", "1" if request.param else "0")
    return request.param


def run_op(name, inputs, **attrs):
    attrs = {k: str(v) for k, v in attrs.items()}
    return get_op(name).compute(OpContext(is_train=False, rng=None), attrs,
                                [jnp.asarray(x) for x in inputs], [])


# -- KDA: chunk = step by step = the recurrence as written ---------------

def kda_plain(q, k, v, alpha, beta):
    """S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T; o_t = S_t^T q_t."""
    T, H, D = q.shape
    S = np.zeros((H, D, D))
    out = np.zeros((T, H, D))
    eye = np.eye(D)
    for t in range(T):
        for h in range(H):
            kk = k[t, h][:, None]
            S[h] = (eye - beta[t, h] * kk @ kk.T) @ (alpha[t, h][:, None]
                                                     * S[h]) \
                + beta[t, h] * kk @ v[t, h][None, :]
            out[t, h] = S[h].T @ q[t, h]
    return out, S


def _draw(rng, T, H, D):
    """The raw gate projections as the initialisation draws them."""
    return dict(
        decay=rng.standard_normal((1, T, H * D)),
        braw=2.0 + rng.standard_normal((1, T, H)),
        a_log=np.log(rng.uniform(1, 4, H)),
        dt=rng.standard_normal(H * D))


def _strongest(rng, T, H, D):
    """exp(A) = 16 and softplus arguments up to +8: g down to -128 a
    token, alpha underflows to 0 in float32."""
    return dict(_draw(rng, T, H, D), a_log=np.full(H, np.log(16.0)),
                decay=rng.uniform(-2, 8, (1, T, H * D)), dt=np.zeros(H * D))


def _no_decay(rng, T, H, D):
    """softplus(-200) is 0 in float32: alpha = 1 everywhere."""
    return dict(_draw(rng, T, H, D), decay=np.full((1, T, H * D), -200.0),
                dt=np.zeros(H * D))


def _beta_ends(rng, T, H, D):
    """beta = 2 sigmoid(+-12): both ends of (0, 2), token by token."""
    return dict(_draw(rng, T, H, D),
                braw=12.0 * rng.choice([-1.0, 1.0], (1, T, H)))


def _slow_beside_fast(rng, T, H, D):
    """Even channels of every head hardly decay (g ~ -1e-4), odd ones
    lose everything in a token (g ~ -128)."""
    lane = np.where(np.arange(H * D) % 2 == 0, -10.0, 8.0)
    return dict(_draw(rng, T, H, D), a_log=np.full(H, np.log(16.0)),
                decay=np.broadcast_to(lane, (1, T, H * D)),
                dt=np.zeros(H * D))


# (T, n, H, D, gates): the first is the old body's test and is also fed
# token by token; the rest cross the chunk form's boundaries (a
# sub-block of 16, a chunk of 64, a tile of 128), padded (n < T) and
# not, and the decays that form can break on
_KDA_CASES = [(12, 9, 4, 8, _draw)] + [
    (T, n, 4, 8, _draw)
    for n, T in [(1, 1), (1, 15), (15, 15), (15, 16), (16, 16), (16, 17),
                 (17, 17), (17, 63), (63, 63), (63, 64), (64, 64),
                 (64, 65), (65, 65), (65, 200), (200, 200)]] + [
    (200, 137, 2, 128, _draw),
    (200, 200, 4, 8, _strongest), (65, 63, 2, 128, _strongest),
    (200, 137, 4, 8, _no_decay), (200, 200, 4, 8, _beta_ends),
    (200, 137, 4, 8, _slow_beside_fast)]


@pytest.mark.parametrize(
    "T, n, H, D, gates", _KDA_CASES,
    ids=[f"T{T}-n{n}-H{H}-D{D}-{g.__name__.strip('_')}"
         for T, n, H, D, g in _KDA_CASES])
def test_kda_chunk_is_kda_step_token_by_token_is_the_recurrence(
        kernels, T, n, H, D, gates):
    """KDAChunk (both bodies: the lax scan and the chunk-form kernels)
    against the recurrence as written, in float64, at every live
    position and in the slot; no further from it than 4 x what the
    float32 scan itself is (at least 4 float32 roundings of the largest
    number compared)."""
    rng = np.random.default_rng(0)
    f32 = lambda x: np.asarray(x, np.float32)
    c = f32(rng.standard_normal((1, T, 3 * H * D)))
    raw = {k: f32(x) for k, x in gates(rng, T, H, D).items()}
    decay, braw, a_log, dt = (raw[k] for k in
                              ("decay", "braw", "a_log", "dt"))
    pool = f32(rng.standard_normal((3, H, D, D)))  # dirty
    attrs = dict(num_heads=H, neg_eigval=True)

    o_chunk, pool_c = run_op(
        "KDAChunk", [c, decay, braw, a_log, dt, pool, [2], [n]], **attrs)
    q, k, v = (np.asarray(x)[0] for x in hybrid.kda_qkv(jnp.asarray(c), H))
    alpha, beta, g = (np.asarray(x)[0] for x in hybrid.kda_gates(
        jnp.asarray(decay), jnp.asarray(braw), jnp.asarray(a_log),
        jnp.asarray(dt), H, True))
    assert beta.max() > 1.0 and beta.min() > 0.0   # negative eigenvalues
    if gates is _strongest:
        assert g.min() < -120 and alpha.min() == 0.0
    if gates is _no_decay:
        assert alpha.min() == 1.0
    want, S = kda_plain(*(x[:n].astype(np.float64) for x in (q, k, v)),
                        np.exp(g[:n].astype(np.float64)),
                        beta[:n].astype(np.float64))
    o_scan, s_scan = hybrid.kda_scan(
        *(jnp.asarray(x[None, :n]) for x in (q, k, v, alpha, beta)),
        jnp.zeros((1, H, D, D), jnp.float32))

    def close(got, ref, scan):
        tol = 4 * max(np.abs(np.asarray(scan, np.float64) - ref).max(),
                      np.finfo(np.float32).eps * np.abs(ref).max())
        got = np.asarray(got, np.float64)
        assert np.isfinite(got).all()
        assert np.abs(got - ref).max() <= tol, (np.abs(got - ref).max(),
                                                tol)

    close(np.asarray(o_chunk)[0, :n], want.reshape(n, -1),
          np.asarray(o_scan)[0].reshape(n, -1))
    # the pools hold a head's state transposed, (d_v, d_k)
    close(np.asarray(pool_c)[2], S.transpose(0, 2, 1), s_scan[0])
    # slots nobody named are untouched
    np.testing.assert_array_equal(np.asarray(pool_c)[:2], pool[:2])
    if (T, n) != _KDA_CASES[0][:2]:
        return
    # the old body's case, fed to KDAStep token by token as well; the
    # slot was dirty and is overwritten: step by step from zero
    pool_s = jnp.asarray(pool).at[1].set(0.0)
    o_step = []
    for t in range(n):
        o, pool_s = run_op(
            "KDAStep", [c[:, t:t + 1], decay[:, t:t + 1], braw[:, t:t + 1],
                        a_log, dt, pool_s, [1], [t + 1]], **attrs)
        o_step.append(np.asarray(o)[0, 0])
    np.testing.assert_allclose(np.stack(o_step), want.reshape(n, -1),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(pool_s)[1],
                               S.transpose(0, 2, 1), atol=2e-5)


# -- ShortConv: the tail crosses the prefill / decode boundary -----------

def test_short_conv_tail_carried_across_prefill_decode_boundary(kernels):
    rng = np.random.default_rng(1)
    T, C, K, n = 10, 6, 4, 6
    x = rng.standard_normal((1, T, C)).astype(np.float32)
    w = rng.standard_normal((C, K)).astype(np.float32)
    pool = rng.standard_normal(conv_tail_shape(3, K, C)).astype(np.float32)
    whole, _ = run_op("ShortConv", [x, w, pool, [1], [T]], step=False)
    # prefill the first n (padded to T), then one token at a time
    head, pool2 = run_op("ShortConv", [x, w, pool, [2], [n]], step=False)
    got = [np.asarray(head)[0, :n]]
    for t in range(n, T):
        y, pool2 = run_op("ShortConv", [x[:, t:t + 1], w, pool2, [2],
                                        [t + 1]], step=True)
        got.append(np.asarray(y)[0])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(whole)[0],
                               atol=1e-6)
    # a prompt shorter than the kernel leaves zeros before it
    _, pool3 = run_op("ShortConv", [x, w, pool, [1], [2]], step=False)
    tail = np.asarray(pool3)[1].reshape(-1)[:(K - 1) * C].reshape(K - 1, C)
    np.testing.assert_array_equal(tail[0], 0.0)
    np.testing.assert_allclose(tail[1:], x[0, :2])


# -- MoEFFN ---------------------------------------------------------------

def moe_layer(cfg, seed=3):
    """One expert layer's drawn weights (float32) and its sizes."""
    z = ref.sizes(cfg)
    drawn = ref.draw(cfg, seed, embed_dtype="float32", dtype="float32")
    return z, {k: np.array(v) for k, v in drawn["layers"][1].items()}


def routed(p, h, first, held_slice, top_k, lengths, step=False):
    out, counters = run_op(
        "MoEFFN", [h, p["router_weight"],
                   p["experts_gate_weight"][held_slice],
                   p["experts_up_weight"][held_slice],
                   p["experts_down_weight"][held_slice], lengths,
                   np.zeros(4, np.int32)],
        top_k=top_k, first_expert=first, step=step, count=True)
    return np.asarray(out), np.asarray(counters)


def test_moe_ffn_uneven_routing_and_an_expert_without_tokens(kernels):
    cfg = dict(CFG, n_shared_experts=0)
    z, p = moe_layer(cfg)
    rng = np.random.default_rng(4)
    h = rng.standard_normal((1, 24, z["d"])).astype(np.float32)
    # held expert 1 is never chosen, held expert 2 by every token: a
    # constant feature, weighed against the one and for the other
    h[..., 0] = 3.0
    p["router_weight"][1, 0] = -50.0
    p["router_weight"][2, 0] = 5.0
    got, counters = routed(p, h, 0, slice(None), z["top_k"], [20])
    want, chosen = ref.moe({k: jnp.asarray(v) for k, v in p.items()},
                           jnp.asarray(h[0]), z, "float32")
    chosen = np.asarray(chosen)[:20]
    assert not (chosen == 1).any() and (chosen == 2).sum() > 10
    np.testing.assert_allclose(got[0, :20], np.asarray(want)[:20],
                               atol=1e-5)
    np.testing.assert_array_equal(got[0, 20:], 0.0)   # padding: no pair
    here = int((chosen < z["held"]).sum())
    loads = [(chosen == e).sum() for e in range(z["held"])]
    assert list(counters) == [here, 20 * z["top_k"] - here,
                              sum(1 for n in loads if n), max(loads)]


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each chip of the deployment adds its own experts' part; with what
    every chip computes alike (the shared expert) counted once, the
    parts are the whole layer."""
    whole = dict(CFG, n_routed_experts=32)            # nothing cut
    z, p = moe_layer(whole)
    rng = np.random.default_rng(5)
    h = rng.standard_normal((1, 16, z["d"])).astype(np.float32)
    pj = {k: jnp.asarray(v) for k, v in p.items()}
    want, _ = ref.moe(pj, jnp.asarray(h[0]), z, "float32")
    total = np.asarray(ref.gated_ffn(
        jnp.asarray(h[0]), pj["shared_gate_weight"],
        pj["shared_up_weight"], pj["shared_down_weight"], "float32"))
    pairs = 0
    for share in range(8):
        part, counters = routed(p, h, 4 * share,
                                slice(4 * share, 4 * share + 4),
                                z["top_k"], [16])
        total = total + part[0]
        pairs += counters[0]
        assert counters[0] + counters[1] == 16 * z["top_k"]
    assert pairs == 16 * z["top_k"]          # every pair on one chip
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5)


# -- grouped queries over the paged cache ---------------------------------

def test_gqa_paged_decode_kernel_matches_the_gather(monkeypatch):
    rng = np.random.default_rng(6)
    B, H, Hkv, D, KVB, P, MB = 3, 16, 2, 8, 4, 12, 3
    q = rng.standard_normal((B, 1, H * D)).astype(np.float32)
    kv = rng.standard_normal((2, B, 1, Hkv * D)).astype(np.float32)
    pools = rng.standard_normal((2, P, KVB, Hkv * D)).astype(np.float32)
    table = np.array([[3, 5, 0], [7, 1, 2], [0, 0, 0]], np.int32)
    lengths = np.array([6, 11, 0], np.int32)
    outs = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("MXNET_PALLAS", flag)
        outs[flag] = run_op("GQAPagedDecode",
                            [q, kv[0], kv[1], pools[0], pools[1], table,
                             lengths], num_heads=H, kv_heads=Hkv)
    for a, b in zip(outs["0"], outs["1"]):
        np.testing.assert_allclose(np.asarray(a)[:2], np.asarray(b)[:2],
                                   atol=1e-5)


# -- the engine: pages and slots against the reference's full forward ----

def make_engine(seed=7, **kw):
    drawn = ref.draw(CFG, seed, embed_dtype="float32", dtype="float32")
    args = dict(model=ref.spec(CFG), max_len=96, kv_block=4, max_streams=3,
                decode_buckets=(1, 2, 4), cache_buckets=(8, 24),
                prefill_buckets=(16, 32, 96), ctx=mx.cpu(),
                dtype="float32")
    args.update(kw)
    return mx.DecodeEngine(ref.program_names(drawn), **args), drawn


def served_gap(drawn, prompt, out):
    """How far below the reference's best logit the served tokens lie,
    teacher-forced through the reference's full forward."""
    seq = np.concatenate([prompt, out])
    z = np.asarray(ref.forward(CFG, drawn, seq))
    rows = z[len(prompt) - 1:len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def prompts(rng, sizes):
    return [rng.integers(1, CFG["vocab_size"], n).astype(np.int32)
            for n in sizes]


def watch_slots(eng):
    """Record every slot's owners; fail the moment one is handed out
    while held."""
    alloc = eng._slot_alloc
    held, history = {}, []
    real_alloc, real_free = alloc.alloc, alloc.free

    def a(owner=None):
        slot = real_alloc(owner=owner)
        assert slot not in held, f"slot {slot} given to two streams"
        held[slot] = owner
        history.append((slot, owner))
        return slot

    def f(slot):
        del held[slot]
        real_free(slot)

    alloc.alloc, alloc.free = a, f
    return held, history


def test_engine_joins_and_retirements_match_the_reference():
    eng, drawn = make_engine()
    held, history = watch_slots(eng)
    ps = prompts(np.random.default_rng(8), (9, 20, 13, 27, 6))
    news = (44, 41, 50, 43, 47)                      # >= 40 decode steps
    with eng:
        outs = [f.result(timeout=300) for f in
                [eng.submit(p, max_new_tokens=m) for p, m in zip(ps, news)]]
        st = eng.stats()
    assert [len(o) for o in outs] == list(news)
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    # five streams through three slots: slots were reused, never shared,
    # and every reuse was overwritten (the later streams agree too)
    assert len(history) == 5 and len({s for s, _ in history}) <= 3
    assert not held and st["state_slots_live"] == 0
    assert st["state_slots"] == 3 and st["state_pool_bytes"] > 0
    assert st["moe_pairs_here"] + st["moe_pairs_elsewhere"] == \
        st["stream_steps"] * CFG["num_experts_per_tok"] * 4
    assert 0 < st["moe_experts_hit"] <= st["steps"] * 4 * 4
    assert st["moe_load_max"] >= st["moe_experts_hit"] / 4


def test_engine_preemption_recomputes_the_slot():
    # 17 pages for three streams that want ~13 each: someone is thrown
    # out, its slot freed, and its re-prefill writes a slot anew
    eng, drawn = make_engine(cache_blocks=34)
    held, history = watch_slots(eng)
    ps = prompts(np.random.default_rng(9), (10, 12, 9))
    with eng:
        outs = [f.result(timeout=300) for f in
                [eng.submit(p, max_new_tokens=40) for p in ps]]
        st = eng.stats()
    assert st["preempted"] >= 1 and len(history) > 3
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    assert not held


def test_returned_state_is_the_reference_scans_last_state():
    # five streams through three slots, so slots are reused; two ask for
    # their state: what the slot holds at retirement is the scan's state
    # after prompt + every generated token but the last, never fed
    eng, drawn = make_engine()
    ps = prompts(np.random.default_rng(11), (9, 21, 14, 30, 5))
    with eng:
        futs = [eng.submit(p, max_new_tokens=42, return_state=(i % 2 == 1))
                for i, p in enumerate(ps)]
        outs = [f.result(timeout=300) for f in futs]
    assert isinstance(outs[0], np.ndarray)
    for p, out in ((ps[1], outs[1]), (ps[3], outs[3])):
        seq = np.concatenate([p, out["tokens"]])
        assert served_gap(drawn, p, out["tokens"]) < 1e-4
        want = ref.final_states(CFG, drawn, jnp.asarray(seq), len(seq) - 1)
        assert sorted(out["state"]) == sorted(want) == [
            "layer1_state", "layer2_state", "layer3_state"]
        for name, st in out["state"].items():
            np.testing.assert_allclose(
                st, np.asarray(want[name]).transpose(0, 2, 1), atol=2e-5)
            assert np.abs(st).max() > 1e-3
        # ... and not the state one token later
        late = ref.final_states(CFG, drawn, jnp.asarray(seq), len(seq))
        assert np.abs(np.asarray(late["layer1_state"]).transpose(0, 2, 1)
                      - out["state"]["layer1_state"]).max() > 1e-4


def test_return_state_needs_a_model_with_slots():
    from benchmark.reference import gpt2

    cfg = {"n_layer": 1, "n_embd": 16, "n_head": 2, "vocab_size": 32,
           "n_positions": 16, "initializer_range": 0.02}
    with mx.DecodeEngine(
            gpt2.program_names(gpt2.draw(cfg, 3, "float32", "float32")),
            vocab_size=32, num_layers=1, num_heads=2, d_model=16,
            max_len=16, ctx=mx.cpu(), dtype="float32") as eng:
        with pytest.raises(MXNetError, match="return_state.*kda"):
            eng.submit(np.arange(1, 5, dtype=np.int32), 4,
                       return_state=True)


def test_reset_stats_zeroes_the_routing_counters():
    eng, _ = make_engine()
    with eng:
        eng.generate(prompts(np.random.default_rng(10), (8,))[0], 6)
        assert eng.stats()["moe_pairs_here"] + \
            eng.stats()["moe_pairs_elsewhere"] > 0
        eng.reset_stats()
        st = eng.stats()
    assert st["moe_pairs_here"] == st["moe_pairs_elsewhere"] == 0
    assert st["moe_experts_hit"] == st["moe_load_max"] == 0


# -- what slots cannot do yet is refused by name --------------------------

@pytest.mark.parametrize("kw, feature", [
    (dict(prefix_cache=1), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(spec_tokens=2), "spec_tokens"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(tp=2), "tp=2"),
    (dict(adapters=True), "adapters"),
])
def test_features_over_slots_are_refused_by_name(kw, feature):
    with pytest.raises(MXNetError) as err:
        make_engine(**kw)
    assert feature in str(err.value) and "kda" in str(err.value)


def test_page_export_and_import_are_refused_by_name():
    eng, _ = make_engine()
    with eng:
        with pytest.raises(MXNetError, match="page export.*kda"):
            eng.submit(np.arange(1, 6, dtype=np.int32), prefill_only=True)
        with pytest.raises(MXNetError, match="page import.*kda"):
            eng.import_stream({}, [])


def test_spec_or_dense_keywords_not_both():
    eng, drawn = make_engine()
    eng.close()
    with pytest.raises(MXNetError, match="not both"):
        mx.DecodeEngine(ref.program_names(drawn), model=ref.spec(CFG),
                        num_heads=4, max_len=32, ctx=mx.cpu())
    with pytest.raises(MXNetError, match="model=<spec> or all of"):
        mx.DecodeEngine({}, vocab_size=10, ctx=mx.cpu())


def test_spec_is_plain_data():
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    spec = ref.spec(CFG)
    again = HybridSpec.from_dict(copy.deepcopy(spec.to_dict()))
    assert again.to_dict() == spec.to_dict()
    assert spec.cache_kinds() == ("pages", "slots", "slots", "slots")
    assert (spec.kv_heads, spec.head_dim) == (2, 8)
    names = [n for n, _, _, _ in spec.pools(9, 4, 4, "float32")]
    assert names == ["layer0_kpool", "layer0_vpool", "layer1_state",
                     "layer1_tail", "layer2_state", "layer2_tail",
                     "layer3_state", "layer3_tail", "moe_counters"]
    with pytest.raises(MXNetError, match="verify"):
        spec.symbol("verify")


def test_slot_allocator():
    a = SlotAllocator(2)
    s1, s2 = a.alloc("x"), a.alloc("y")
    assert {s1, s2} == {1, 2} and a.alloc() is None and a.live == 2
    a.free(s1)
    assert a.owner(s2) == "y" and a.alloc("z") == s1
    with pytest.raises(MXNetError):
        a.free(0)
