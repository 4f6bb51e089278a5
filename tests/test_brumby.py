"""The ``retention`` mixer and what came with it (``ops/hybrid.py``,
``ops/pallas_hybrid.py``, ``models/hybrid_lm.py``) against the plain
reference ``benchmark/reference/brumby.py`` at small sizes, seeded
weights, float32: the packed symmetric square, each op against the
reference's ATTENTION form (both bodies: the lax fallback and the Pallas
kernels interpreted), the engine — a prompt then decode through a slot,
no page anywhere — against the reference's full forward on logits and
on the slots' last states, what a slot is to the streams that pass
through it, and the refusals.  A file of its own, so that an xdist
worker of its own takes the interpreted cases."""

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
from mxnet_tpu.models.hybrid_lm import HybridSpec, mixer_state  # noqa: E402
from mxnet_tpu.ops import hybrid, pallas_hybrid  # noqa: E402

from benchmark.reference import brumby as ref  # noqa: E402
from _engines import WAIT, Family, run_op  # noqa: E402

# the published shape at a size a test can hold: 3 layers, 4 query heads
# over 2 KV heads of 16 (a state of 9 x 16 rows of 16 a KV head), a gate
# whose memory is a few tokens to a few dozen, an untied head
CFG = {
    "family": "brumby", "hidden_size": 64, "num_hidden_layers": 3,
    "num_hidden_layers_published": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "vocab_size": 97, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "initializer_range": 0.3, "gate_forget_min": 0.02,
    "gate_forget_max": 0.3,
}
H, J, D = 4, 2, 16
G = H // J


@pytest.fixture
def kernels(kernels, monkeypatch):
    """conftest's two bodies, the prompt in chunks of 16 so that a test's
    prompt is several."""
    monkeypatch.setattr(pallas_hybrid, "RETENTION_CHUNK", 16)
    return kernels


f32 = lambda v: np.asarray(v, np.float32)


# -- the packed symmetric square ------------------------------------------

@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_dot_phi_is_the_square_of_the_dot(d):
    rng = np.random.default_rng(d)
    x, y = f32(rng.standard_normal((2, 5, d)))
    px, py = np.asarray(hybrid.retention_phi(x)), \
        np.asarray(hybrid.retention_phi(y))
    assert px.shape == (5, d // 2 + 1, d)
    assert hybrid.retention_rows(d) == px.shape[1] * d
    # every row counts, the d / 2 doubled ones of the last block too
    # (float32 sums of terms that cancel: held to the terms' size)
    np.testing.assert_allclose(
        (px.astype(np.float64) * py).sum((1, 2)), (x * y).sum(1) ** 2,
        rtol=2e-5, atol=1e-6 * float((x * x).sum(1).max()
                                     * (y * y).sum(1).max()))
    # the last block holds each pair half a head apart twice, under 1
    np.testing.assert_allclose(px[:, -1, :d // 2], px[:, -1, d // 2:],
                               rtol=1e-6)
    np.testing.assert_allclose(px[:, -1, 0], x[:, 0] * x[:, d // 2],
                               rtol=1e-6)
    assert hybrid.retention_rows(128) == 8320


def test_the_references_pack_is_the_ops_layout():
    """phi(k) v^T in the textbook order, packed by the reference's own
    lines, is the op's transposed blocks."""
    rng = np.random.default_rng(1)
    k, v = f32(rng.standard_normal((2, D)))
    ia, ib, wt = ref._pairs(D)
    S = (k[ia] * k[ib] * wt)[None, :, None] * v[None, None, :]
    got = np.asarray(ref.pack(jnp.asarray(S), D))[0]        # (R, D)
    want = v[None, :, None] * np.asarray(hybrid.retention_phi(k))[:, None, :]
    np.testing.assert_allclose(got, want.reshape(-1, D), rtol=1e-5,
                               atol=1e-7)


# -- RetentionChunk = RetentionStep token by token = the attention form ---

def _inputs(T, rng):
    q = f32(rng.standard_normal((1, T, H * D)))
    k = f32(rng.standard_normal((1, T, J * D)))
    v = f32(rng.standard_normal((1, T, J * D)))
    g = f32(rng.standard_normal((1, T, J)))
    bias = f32(rng.uniform(1.0, 4.0, J))
    return q, k, v, g, bias


def _attention_form(q, k, v, g, bias, n, theta=1e4):
    """The reference's attention form over the first ``n`` tokens."""
    q, k, v = (jnp.asarray(t[0, :n]) for t in (q, k, v))
    s = float(D) ** -0.25
    qr = ref.rotate(q.reshape(n, H, D), theta) * s
    kr = ref.rotate(k.reshape(n, J, D), theta) * s
    gamma = np.log(1 / (1 + np.exp(-(g[0, :n] + bias))))
    return np.asarray(ref.attend(qr, kr, v.reshape(n, J, D),
                                 jnp.asarray(f32(gamma)), 2, True))


_LENGTHS = [(1, 1), (12, 9), (16, 16), (40, 33), (48, 48)]
_ATTRS = dict(num_heads=H, kv_heads=J, rope_theta=1e4)


@pytest.mark.parametrize("T, n", _LENGTHS,
                         ids=[f"T{T}-n{n}" for T, n in _LENGTHS])
def test_chunk_is_step_by_step_is_the_attention_form(kernels, T, n):
    rng = np.random.default_rng(T)
    q, k, v, g, bias = _inputs(T, rng)
    R = hybrid.retention_rows(D)
    pool = f32(rng.standard_normal((3, J, R, D)))           # dirty
    norm = f32(rng.standard_normal((3, J, D, D)))
    pos = np.arange(T, dtype=np.int32)[None]
    slots = np.array([2], np.int32)
    want = _attention_form(q, k, v, g, bias, n)

    y, p1, z1 = run_op("RetentionChunk",
                       [q, k, v, g, bias, pool, norm, slots,
                        np.array([n], np.int32), pos], **_ATTRS)
    np.testing.assert_allclose(np.asarray(y)[0, :n], want, rtol=2e-4,
                               atol=2e-5)
    assert np.array_equal(np.asarray(p1)[1], pool[1])       # others' slots

    # token by token from a zeroed slot: the same outputs, the same slot
    p2, z2 = jnp.zeros_like(pool), jnp.zeros_like(norm)
    step = jax.jit(lambda *a: run_op("RetentionStep", list(a), **_ATTRS))
    ys = []
    for t in range(n):
        yt, p2, z2 = step(
            q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], g[:, t:t + 1],
            bias, p2, z2, slots, np.array([t + 1], np.int32),
            pos[:, t:t + 1])
        ys.append(np.asarray(yt)[0, 0])
    np.testing.assert_allclose(np.stack(ys), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(p2)[2], np.asarray(p1)[2],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(z2)[2], np.asarray(z1)[2],
                               rtol=2e-4, atol=2e-5)


def test_kernels_interpreted_are_their_lax_bodies(monkeypatch):
    """``retention_chunk`` (a prompt that ends inside its second chunk of
    three: the third is not walked, its rows leave as 0) against the lax
    body; ``retention_step`` has the test below."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    monkeypatch.setattr(pallas_hybrid, "RETENTION_CHUNK", 16)
    rng = np.random.default_rng(5)
    B, T = 2, 48
    n = np.array([T, 23], np.int32)
    q = f32(rng.standard_normal((B, T, J, G, D)))
    live = (np.arange(T)[None] < n[:, None])[..., None]
    k = f32(rng.standard_normal((B, T, J, D))) * live[..., None]
    v = f32(rng.standard_normal((B, T, J, D))) * live[..., None]
    la = f32(-rng.uniform(0.01, 0.5, (B, T, J))) * live
    yk, sk, zk = pallas_hybrid.retention_chunk(
        jnp.asarray(q.reshape(B, T, -1)), jnp.asarray(k.reshape(B, T, -1)),
        jnp.asarray(v.reshape(B, T, -1)), jnp.asarray(la), jnp.asarray(n))
    yl, sl, zl = hybrid.retention_chunked(*(jnp.asarray(t)
                                            for t in (q, k, v, la)))
    yk, yl = np.asarray(yk), np.asarray(yl).reshape(B, T, -1)
    np.testing.assert_allclose(yk[0], yl[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(yk[1, :23], yl[1, :23], rtol=1e-4, atol=1e-5)
    assert not yk[1, 32:].any()
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sl), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zl), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize("g", [1, 4, 5, 8])
def test_step_kernel_interpreted_is_its_lax_body(monkeypatch, g, rows):
    """``retention_step`` at every group size its walk is scheduled for
    (``_retention_step_blocks``), on permuted, non-adjacent slots: the
    outputs, the advanced slots and their normalisers are the lax
    body's, every other slot is left bit for bit."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    rng = np.random.default_rng(10 * g + rows)
    R = hybrid.retention_rows(D)
    pool = jnp.asarray(f32(rng.standard_normal((7, J, R, D))))
    norm = jnp.asarray(f32(np.abs(rng.standard_normal((7, J, D, D)))))
    slots = np.array([5, 0, 3][:rows], np.int32)
    q = f32(rng.standard_normal((rows, J, g, D)))
    k, v = f32(rng.standard_normal((2, rows, J, D)))
    a = f32(rng.uniform(0.6, 0.99, (rows, J)))
    args = [jnp.asarray(t) for t in (q, k, v, a)]
    y1, p1, z1 = pallas_hybrid.retention_step(*args, pool, norm,
                                              jnp.asarray(slots))
    y2, s2, z2 = hybrid.retention_step(*args, pool[slots], norm[slots])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(p1)[slots], np.asarray(s2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(z1)[slots], np.asarray(z2),
                               rtol=1e-5, atol=1e-6)
    for untouched in sorted(set(range(7)) - set(slots.tolist())):
        assert np.array_equal(np.asarray(p1)[untouched],
                              np.asarray(pool)[untouched])
        assert np.array_equal(np.asarray(z1)[untouched],
                              np.asarray(norm)[untouched])
    # the schedule the build chose is on ``/metrics``
    groups, _ = pallas_hybrid._retention_step_blocks(g, D)
    gauges = mx.profiler.metrics_summary()["gauges"]
    assert gauges["retention.step_row_groups"] == groups
    assert gauges["retention.step_loads_per_register"] == \
        1 + (g + 1) / groups


def test_step_schedule_fits_the_register_file():
    """The walk's schedule for G = 1 .. 8 query heads a KV head at heads
    of 128 and 256: a pass's accumulators, value columns and a block's
    rows of phi fit the 64 registers with room for the products in
    flight, its groups divide the head's groups of 8 value rows and its
    turns the head's blocks."""
    for d in (128, 256):
        for g in range(1, 9):
            groups, blocks = pallas_hybrid._retention_step_blocks(g, d)
            held = groups * g + groups + (g + 1)
            assert held <= 48, (g, d, groups, held)
            assert (d // 8) % groups == 0 and (d // 2 + 1) % blocks == 0
            assert groups * g * blocks >= 48
    # the gen cell's heads: a phi row is loaded once for four registers
    assert pallas_hybrid._retention_step_blocks(5, 128) == (4, 5)


def test_five_query_heads_read_one_kv_heads_state():
    """A KV head's G query heads share its state: with the same query in
    every head of a group the outputs are equal, and the pool has J
    states, not H."""
    rng = np.random.default_rng(2)
    q1 = f32(rng.standard_normal((1, 1, J, 1, D)))
    q = np.broadcast_to(q1, (1, 1, J, G, D)).reshape(1, 1, H * D)
    k, v = f32(rng.standard_normal((2, 1, 1, J * D)))
    g = f32(rng.standard_normal((1, 1, J)))
    R = hybrid.retention_rows(D)
    pool = f32(rng.standard_normal((2, J, R, D)))
    norm = f32(np.abs(rng.standard_normal((2, J, D, D))))
    y, p, _ = run_op("RetentionStep",
                     [q, k, v, g, f32(np.ones(J)), pool, norm,
                      np.array([1], np.int32), np.array([1], np.int32),
                      np.array([[5]], np.int32)], **_ATTRS)
    y = np.asarray(y).reshape(J, G, D)
    np.testing.assert_allclose(y[:, 0], y[:, 1], rtol=1e-6)
    assert np.asarray(p).shape == (2, J, R, D)


# -- the spec ---------------------------------------------------------------

def test_spec_sizes_its_slot_by_the_mixer_and_keeps_no_page():
    spec = ref.spec(CFG)
    assert spec.mixer_kinds() == ("retention",) * 3
    assert spec.cache_kinds() == ("slots",) * 3
    assert (spec.kv_heads, spec.head_dim, spec.window) == (0, 0, 0)
    assert spec.feeds == ("data", "lengths", "block_table", "slots",
                          "positions")
    m = spec.layers[0]["mixer"]
    assert mixer_state(m) == ((J, 9 * D, D), (J, D, D))
    full = dict(m, heads=40, kv_heads=8, head_dim=128)
    assert mixer_state(full) == ((8, 8320, 128), (8, 128, 128))
    pools = spec.pools(cache_blocks=7, kv_block=8, slots=4, dtype="float32")
    assert [(n, s, d) for n, s, d, _ in pools[:2]] == [
        ("layer0_state", (4, J, 9 * D, D), "float32"),
        ("layer0_zsum", (4, J, D, D), "float32")]
    # both halves of a layer's sums are what ``return_state`` reads
    assert spec.pool_kinds() == ("slots", "slots") * 3
    assert HybridSpec.from_dict(spec.to_dict()).to_dict() == spec.to_dict()
    args = spec.symbol("decode").list_arguments()
    for leaf in ("q_weight", "k_weight", "v_weight", "q_norm_gamma",
                 "k_norm_gamma", "g_weight", "g_bias", "o_weight", "state",
                 "zsum", "ffn_gate_weight"):
        assert f"layer1_{leaf}" in args
    # the mixer's keys are data: none is optional machinery
    assert set(m) == {"kind", "heads", "kv_heads", "head_dim", "degree",
                      "rope_theta", "qk_norm"}


@pytest.mark.parametrize("change, word", [
    ({"degree": 3}, "degree"), ({"degree": 4}, "degree"),
    ({"head_dim": 15}, "even"), ({"kv_heads": 3}, "divide"),
    ({"rope_theta": 0}, "rope_theta is required"),
    ({"rope_theta": None}, "rope_theta is required"),
    ({"gate_bias": True}, "no key"), ({"conv": 4}, "no key")])
def test_spec_refuses_by_name(change, word):
    m = dict(ref.spec(CFG).layers[0]["mixer"], **change)
    with pytest.raises(MXNetError, match=word):
        HybridSpec(97, 64, [{"mixer": m, "ffn": {"kind": "dense",
                                                 "width": 96}}])


# -- the engine -----------------------------------------------------------

FAMILY = Family(ref, CFG, pad=64, max_len=64, kv_block=8, max_streams=2,
                decode_buckets=(2,), prefill_buckets=(16, 32),
                temperature=0.0)


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


@pytest.fixture(scope="module")
def weights():
    return FAMILY.draw()


def _gaps(w, prompt, served):
    """The served tokens' logit gaps below the reference's best, and the
    reference's states once all but the last token have been fed."""
    toks = np.concatenate([prompt, served])
    lg = FAMILY.logits(w, toks)[len(prompt) - 1:-1]
    gap = lg.max(-1) - np.take_along_axis(lg, served[:, None], -1)[:, 0]
    states = ref.final_states(CFG, w, jnp.asarray(np.pad(
        toks, (0, 64 - len(toks)))), len(toks) - 1)
    return gap, {k: np.asarray(v) for k, v in states.items()}


def _state_gap(got, want):
    """The runner's number: a head's relative Frobenius gap, the worst."""
    got = np.asarray(got, np.float64).transpose(0, 2, 1)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(((got - want) ** 2).sum((1, 2))
                         / (want ** 2).sum((1, 2))).max())


# what float32 leaves between two orders of the same sums (the chunk
# form against the attention form, a step against the scan): measured
# 0 on logits (every served token the reference's best) and 1.1e-6 on a
# head's state; bfloat16 products read 3e-3 and more (the last test)
LOGIT_TOL = 1e-4
STATE_TOL = 2e-5


def test_engine_prompt_then_decode_is_the_references_full_forward(
        kernels, weights):
    """Three streams through two slots (the third waits for a slot and
    starts from zero in one a retired stream left dirty), prompts in both
    buckets and of several chunks: every served token's logit gap and
    every slot's last state against the reference."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in (11, 29, 7)]
    # an engine a body (and the chunk of 16 is this test's alone)
    with FAMILY.engine()[0] as eng:
        futs = [eng.submit(p, max_new_tokens=10, return_state=True)
                for p in prompts]
        outs = [f.result(timeout=WAIT) for f in futs]
        st = eng.stats()
    assert st["state_slots"] == 2 and st["state_slots_live"] == 0
    for p, o in zip(prompts, outs):
        gap, states = _gaps(weights, p, np.asarray(o["tokens"]))
        assert gap.max() <= LOGIT_TOL
        assert sorted(o["state"]) == sorted(states)
        for name, want in states.items():
            assert _state_gap(o["state"][name], want) <= STATE_TOL
        assert sorted(states) == sorted(
            f"layer{i}_{leaf}" for i in range(3)
            for leaf in ("state", "zsum"))


def test_interleaved_streams_do_not_share_and_a_slot_starts_from_zero(
        engines):
    """Two streams decode side by side, then a third takes a freed slot:
    each serves what it serves alone (one after another through an engine
    of one stream: the one slot is dirty from the second on)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 97, n).astype(np.int32) for n in (9, 14, 12)]
    eng, _ = engines(FAMILY.engine, max_streams=1, decode_buckets=(1,))
    alone = [np.asarray(eng.submit(p, max_new_tokens=8).result(timeout=WAIT))
             for p in prompts]
    eng, _ = engines(FAMILY.engine)
    futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    together = [np.asarray(f.result(timeout=WAIT)) for f in futs]
    for a, b in zip(alone, together):
        assert np.array_equal(a, b)


def test_a_page_less_spec_builds_its_engine_and_carries_no_page(engines):
    eng, _ = engines(FAMILY.engine)
    out = eng.submit(np.arange(1, 10, dtype=np.int32),
                     max_new_tokens=4).result(timeout=WAIT)
    st = eng.stats()
    assert len(out) == 4
    assert st["cache_util"] == 0.0 and st["context_tokens"] >= 0
    assert st["state_pool_bytes"] == 3 * 3 * J * (9 * D * D + D * D) * 4
    gauges = mx.profiler.metrics_summary()["gauges"]
    assert gauges["serving.kv_pool_bytes"] == 0
    assert gauges["serving.state_pool_bytes"] == st["state_pool_bytes"]


@pytest.mark.parametrize("kw, word", [
    ({"prefix_cache": 1}, "prefix_cache"),
    ({"prefill_chunk": 8}, "prefill_chunk"),
    ({"spec_tokens": 2}, "spec_tokens"),
    ({"kv_dtype": "bf16"}, "kv_dtype='bf16'"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"tp": 2}, "tp=2")])
def test_engine_refuses_by_name(weights, kw, word):
    with pytest.raises(MXNetError, match=word) as e:
        FAMILY.engine(weights, **kw)
    assert "slot" in str(e.value)


def test_bfloat16_products_fail_the_float32_tolerances(weights):
    """A computation below the stated precision is seen: the reference
    itself with bfloat16 products, against its float32 form, reads far
    over what the engine is held to."""
    rng = np.random.default_rng(4)
    toks = rng.integers(1, 97, 40).astype(np.int32)
    hi = np.asarray(ref.forward(CFG, weights, toks))
    lo = np.asarray(ref.forward(CFG, weights, toks, "bfloat16"))
    first = lo.argmax(-1)
    gap = hi.max(-1) - np.take_along_axis(hi, first[:, None], -1)[:, 0]
    assert np.abs(hi - lo).max() > 30 * LOGIT_TOL
    row = jnp.asarray(np.pad(toks, (0, 24)))
    want = ref.final_states(CFG, weights, row, 39)
    got = ref.final_states(CFG, weights, row, 39, "bfloat16")
    worst = max(_state_gap(np.asarray(got[k]).transpose(0, 2, 1), want[k])
                for k in want)
    assert worst > 30 * STATE_TOL
    assert gap.max() >= 0.0
