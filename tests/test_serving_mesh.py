"""Model-parallel serving tests: the tp(+pp) DecodeEngine on the
virtual 8-device CPU mesh must be BIT-IDENTICAL (fp32/lax) to the
single-device engine — same tokens for the same (engine seed, stream
seed, position) triples — with the prefix cache, speculative decoding,
int8 KV storage and preemption composing unchanged on top.

Tier-1 carries one fast tp=2 smoke plus the at-construction env
validation; the full (tp, pp) x feature matrix is ``slow``.
"""

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError

from _engines import WAIT, build

V, KVB, L, H, DM, DFF, MAXLEN = 61, 4, 2, 2, 32, 128, 32


def _mesh_devices(n):
    import jax

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")


@pytest.fixture(scope="module")
def lm_params():
    rng = np.random.RandomState(0)
    p = {"tok_embed_weight":
         (rng.randn(V, DM) * 0.1).astype(np.float32),
         "pos_embed_weight":
         (rng.randn(MAXLEN, DM) * 0.1).astype(np.float32)}
    for i in range(L):
        p[f"layer{i}_ln1_gamma"] = np.ones(DM, np.float32)
        p[f"layer{i}_ln1_beta"] = np.zeros(DM, np.float32)
        p[f"layer{i}_qkv_weight"] = \
            (rng.randn(3 * DM, DM) * 0.1).astype(np.float32)
        p[f"layer{i}_qkv_bias"] = \
            (rng.randn(3 * DM) * 0.1).astype(np.float32)
        p[f"layer{i}_proj_weight"] = \
            (rng.randn(DM, DM) * 0.1).astype(np.float32)
        p[f"layer{i}_proj_bias"] = \
            (rng.randn(DM) * 0.1).astype(np.float32)
        p[f"layer{i}_ln2_gamma"] = np.ones(DM, np.float32)
        p[f"layer{i}_ln2_beta"] = np.zeros(DM, np.float32)
        p[f"layer{i}_ff1_weight"] = \
            (rng.randn(DFF, DM) * 0.1).astype(np.float32)
        p[f"layer{i}_ff1_bias"] = \
            (rng.randn(DFF) * 0.1).astype(np.float32)
        p[f"layer{i}_ff2_weight"] = \
            (rng.randn(DM, DFF) * 0.1).astype(np.float32)
        p[f"layer{i}_ff2_bias"] = \
            (rng.randn(DM) * 0.1).astype(np.float32)
    p["ln_f_gamma"] = np.ones(DM, np.float32)
    p["ln_f_beta"] = np.zeros(DM, np.float32)
    p["head_weight"] = (rng.randn(V, DM) * 0.1).astype(np.float32)
    p["head_bias"] = (rng.randn(V) * 0.1).astype(np.float32)
    return p


def _engine(params, **kw):
    args = dict(vocab_size=V, num_layers=L, num_heads=H, d_model=DM,
                d_ff=DFF, max_len=MAXLEN, kv_block=KVB, max_streams=2,
                decode_buckets=[1, 2], temperature=0.8, seed=7,
                prefix_cache=0, spec_tokens=0, prefill_chunk=0)
    args.update(kw)
    return build(params, **args)


_PROMPTS = [np.array([3, 7, 1, 9, 2], np.int32),
            np.array([11, 4], np.int32)]


def _generate_all(eng, prompts=_PROMPTS, n=5):
    futs = [eng.submit(p, n, seed=i) for i, p in enumerate(prompts)]
    return [np.asarray(f.result(timeout=WAIT)) for f in futs]


@pytest.fixture(scope="module")
def ref_run(lm_params):
    """One single-device reference run shared by the fast tests:
    (expected tokens, tp=1 per-device pool bytes)."""
    with _engine(lm_params) as ref:
        return _generate_all(ref), ref.stats()["pool_bytes_per_device"]


# ---------------------------------------------------------------------------
# tier-1 smoke: tp=2 equals single-device, stats tell the truth
# ---------------------------------------------------------------------------


def test_tp2_bit_identical_smoke(lm_params, ref_run):
    """tp=2 engine decodes BIT-IDENTICAL tokens to the single-device
    engine (greedy + temperature sampling), reports the mesh shape,
    and each device holds half the tp=1 pool."""
    _mesh_devices(2)
    expect, pool_tp1 = ref_run
    with _engine(lm_params, tp=2) as eng:
        got = _generate_all(eng)
        st = eng.stats()
    for a, b in zip(expect, got):
        np.testing.assert_array_equal(a, b)
    assert st["mesh"]["tp"] == 2 and st["mesh"]["pp"] == 1
    assert len(st["mesh"]["devices"]) == 2
    assert st["mesh"]["sharded"]["heads"]
    assert st["pool_bytes_per_device"] == pool_tp1 // 2
    assert st["kv_dtype"] == "fp32"


def test_mesh_params_roundtrip_and_swap(lm_params, ref_run):
    """get_params returns the checkpoint layout (qkv rows restored);
    swap_params re-shards and decode stays bit-identical."""
    _mesh_devices(2)
    expect = ref_run[0]
    with _engine(lm_params, tp=2) as eng:
        host = eng.get_params()
        for k, v in lm_params.items():
            np.testing.assert_array_equal(host[k], v)
        eng.swap_params(host)
        got = _generate_all(eng)
    for a, b in zip(expect, got):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# at-construction validation: bad tp/pp/devices raise loudly
# ---------------------------------------------------------------------------


def test_env_tp_garbage_raises(lm_params, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_TP", "banana")
    with pytest.raises(MXNetError, match="MXNET_SERVING_TP"):
        _engine(lm_params)


def test_env_tp_negative_raises(lm_params, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_TP", "-1")
    with pytest.raises(MXNetError, match="MXNET_SERVING_TP"):
        _engine(lm_params)


def test_env_pp_garbage_raises(lm_params, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_PP", "0")
    with pytest.raises(MXNetError, match="MXNET_SERVING_PP"):
        _engine(lm_params)


def test_tp_not_dividing_heads_raises(lm_params):
    with pytest.raises(MXNetError, match="num_heads"):
        _engine(lm_params, tp=H + 1)


def test_pp_not_dividing_layers_raises(lm_params):
    with pytest.raises(MXNetError, match="num_layers"):
        _engine(lm_params, pp=L + 1)


def test_devices_wrong_count_raises(lm_params):
    _mesh_devices(2)
    with pytest.raises(MXNetError, match="MXNET_SERVING_DEVICES"):
        _engine(lm_params, tp=2, devices=[0])


def test_devices_duplicate_raises(lm_params):
    _mesh_devices(2)
    with pytest.raises(MXNetError, match="repeats"):
        _engine(lm_params, tp=2, devices=[1, 1])


def test_devices_env_garbage_raises(lm_params, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_DEVICES", "0,banana")
    with pytest.raises(MXNetError, match="MXNET_SERVING_DEVICES"):
        _engine(lm_params, tp=2)


def test_devices_out_of_range_raises(lm_params):
    with pytest.raises(MXNetError, match="out of"):
        _engine(lm_params, tp=2, devices=[0, 4096])


def test_explicit_devices_select_mesh(lm_params, ref_run):
    """An explicit non-default device set serves identically (mesh
    placement is positional, not ordinal-dependent)."""
    _mesh_devices(4)
    expect = ref_run[0]
    with _engine(lm_params, tp=2, devices=[2, 3]) as eng:
        got = _generate_all(eng)
        assert len(eng.stats()["mesh"]["devices"]) == 2
    for a, b in zip(expect, got):
        np.testing.assert_array_equal(a, b)


def test_spawn_replica_exports_device_set(monkeypatch, tmp_path):
    """fleet.spawn_replica(devices=...) hands the replica its mesh
    slice through MXNET_SERVING_DEVICES."""
    from mxnet_tpu import fleet

    seen = {}

    class _FakeProc:
        def __init__(self, cmd, env=None):
            seen["env"] = env

    monkeypatch.setattr(fleet.subprocess, "Popen",
                        lambda cmd, env=None: _FakeProc(cmd, env))
    fleet.spawn_replica(0, str(tmp_path), "mod:fn", devices=[2, 3])
    assert seen["env"]["MXNET_SERVING_DEVICES"] == "2,3"


# ---------------------------------------------------------------------------
# the slow matrix: (tp, pp) x serving feature, all bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("tp,pp", [(2, 1), (2, 2), (1, 2)])
@pytest.mark.parametrize("feature", ["plain", "prefix", "spec",
                                     "int8kv", "chunked", "all"])
def test_mesh_matrix_bit_identical(lm_params, tp, pp, feature):
    """Every serving feature composes with the mesh unchanged: the
    sharded engine's tokens equal the single-device engine's tokens
    bitwise, including resubmission (prefix hits) of the first
    prompt."""
    _mesh_devices(tp * pp)
    kw = {"prefix": dict(prefix_cache=1),
          "spec": dict(spec_tokens=3),
          "int8kv": dict(kv_dtype="int8"),
          "chunked": dict(prefill_chunk=4),
          "all": dict(prefix_cache=1, spec_tokens=3, kv_dtype="int8",
                      prefill_chunk=4),
          "plain": {}}[feature]
    with _engine(lm_params, **kw) as ref:
        expect = _generate_all(ref)
        expect += [np.asarray(
            ref.submit(_PROMPTS[0], 5, seed=0).result(timeout=WAIT))]
    with _engine(lm_params, tp=tp, pp=pp, **kw) as eng:
        got = _generate_all(eng)
        got += [np.asarray(
            eng.submit(_PROMPTS[0], 5, seed=0).result(timeout=WAIT))]
    for a, b in zip(expect, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_mesh_preemption_bit_identical(lm_params):
    """A pool too small for all streams forces preemption under the
    mesh too; preempted streams re-prefill and still emit exactly the
    single-device tokens."""
    _mesh_devices(2)
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(7, 12, dtype=np.int32),
               np.arange(13, 18, dtype=np.int32)]
    kw = dict(max_streams=3, decode_buckets=[1, 2, 4], cache_blocks=10,
              temperature=0.0)
    with _engine(lm_params, **kw) as ref:
        futs = [ref.submit(p, 14) for p in prompts]
        expect = [np.asarray(f.result(timeout=WAIT)) for f in futs]
    with _engine(lm_params, tp=2, **kw) as eng:
        futs = [eng.submit(p, 14) for p in prompts]
        got = [np.asarray(f.result(timeout=WAIT)) for f in futs]
        st = eng.stats()
    assert st["preempted"] > 0
    for a, b in zip(expect, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_mesh_warmup_compiles_full_matrix(lm_params):
    """warmup() under the mesh AOT-compiles every bucket executable
    (pools donated) without touching the scheduler."""
    _mesh_devices(4)
    with _engine(lm_params, tp=2, pp=2, prefix_cache=1,
                 spec_tokens=2) as eng:
        eng.warmup()
        compiled = set(k.split("'")[1] for k in
                       eng.stats()["compiles"])
    assert {"decode", "prefill", "verify", "prefix_prefill"} <= compiled
