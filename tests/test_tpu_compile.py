"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached: libtpu's compiler is installed here and lowers
for a topology that is only described (on-chip-measurement guide §2,
third rehearsal).  What Mosaic refuses — an illegal contraction, more
VMEM than a kernel may hold — fails here at no chip time, at the
widths the engine and the benchmarks really run (H=12, D=64, page 16,
8,732 SSD anchors).  Interpret mode, which every other test uses,
cannot see any of it.

The topology is described inside a module-scoped fixture: only the
xdist worker that is handed this file loads libtpu.  Nothing here runs
at import, in a ``skipif`` or in a ``parametrize`` argument.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from mxnet_tpu import hlo, parallel
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import attention, pallas_kernels as pk

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip — keep the cache out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Steer the kernels' own CPU detection: compiled, not interpreted."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "enabled", lambda: True)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


# LM widths: batch 8, T 1024, 12 heads x 64 — fused QKV is 3*768 wide
_QKV = ((8, 1024, 2304), bf16)


def test_flash_mha_packed_fwd(on_chip, one_chip):
    _compile(lambda x: pk.flash_mha_packed(x, 12, causal=True),
             one_chip, _QKV)


def test_flash_mha_packed_grad(on_chip, one_chip):
    def loss(x):
        return pk.flash_mha_packed(x, 12, causal=True).astype(f32).sum()

    _compile(jax.grad(loss), one_chip, _QKV)


def test_flash_mha(on_chip, one_chip):
    s = ((8 * 12, 1024, 64), bf16)
    _compile(lambda q, k, v: pk.flash_mha(q, k, v, causal=True),
             one_chip, s, s, s)


def test_flash_attention_partial(on_chip, one_chip):
    s = ((2, 1024, 12, 64), bf16)
    _compile(lambda q, k, v: pk.flash_attention_partial(
        q, k, v, True, 512, 0), one_chip, s, s, s)


# the engine's decode step: 8 streams, 12 heads x 64, page 16, a
# 1024-token table (64 pages a stream) over a 640-page pool
_B, _H, _D, _KVB, _MB, _P = 8, 12, 64, 16, 64, 640


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_attention_decode(on_chip, one_chip, dtype):
    dt = jnp.dtype(dtype)
    pool = ((_P, _KVB, _H, _D), dt)
    _compile(pk.paged_attention_decode, one_chip,
             ((_B, _H, _D), dt), pool, pool, ((_B, _MB), i32), ((_B,), i32))


def test_paged_attention_decode_quant_int8(on_chip, one_chip):
    pool = ((_P, _KVB, _H, _D), i8)
    scale = ((_P, _KVB, _H), f32)
    _compile(pk.paged_attention_decode_quant, one_chip,
             ((_B, _H, _D), bf16), pool, pool, scale, scale,
             ((_B, _MB), i32), ((_B,), i32))


def test_paged_attention_verify_w5(on_chip, one_chip):
    pool = ((_P, _KVB, _H, _D), bf16)
    _compile(pk.paged_attention_verify, one_chip,
             ((_B, 5, _H, _D), bf16), pool, pool, ((_B, _MB), i32),
             ((_B,), i32))


def test_lstm_scan_ptb(on_chip, one_chip):
    # PTB LSTM (tools/bench_secondary.py): T=32, batch 32, hidden 200
    T, B, H = 32, 32, 200
    _compile(pk.lstm_scan, one_chip, ((T, B, 4 * H), f32), ((B, H), f32),
             ((B, H), f32), ((H, 4 * H), f32))


def test_nms_ssd300_anchors(on_chip, one_chip):
    _compile(functools.partial(pk.nms, nms_threshold=0.45,
                               force_suppress=False),
             one_chip, ((1, 8732, 6), f32))


# ---------------------------------------------------------------------------
# four chips: the dp=2 x tp=2 mesh of `chip_smoke.py --chips 4`
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plan(topo):
    return parallel.MeshPlan(topo.devices, dp=2, tp=2,
                             rules=transformer.lm_partition_rules())


def _qkv_on(plan):
    # the fused QKV projection's output under the LM rules table
    return jax.ShapeDtypeStruct(
        *_QKV, sharding=NamedSharding(plan.mesh, P("dp", None, "tp")))


def test_bare_kernel_cannot_be_partitioned(on_chip, plan):
    """Why the packed kernel shard_maps itself under a plan: the
    compiler refuses to partition a Mosaic kernel."""
    with pytest.raises(NotImplementedError, match="automatically"):
        jax.jit(lambda x: pk.flash_mha_packed(x, 12, causal=True)).lower(
            _qkv_on(plan)).compile()


def test_flash_mha_packed_grad_on_dp2_tp2_mesh(on_chip, plan):
    def loss(x):
        with parallel.tracing_for(plan):
            out = attention._flash_mha_packed_on_plan(x, 12, True, 0)
        return out.astype(f32).sum()

    text = jax.jit(jax.grad(loss)).lower(_qkv_on(plan)).compile().as_text()
    assert "tpu_custom_call" in text
    # each device gathers the tp peer's half of qkv before its kernel:
    # the collectives are in the program and the report reads them
    report = hlo.overlap_report(text)
    assert any(k.startswith("all-gather") or k.startswith("all-to-all")
               or k.startswith("collective-permute")
               for k in report["collectives"]), report
