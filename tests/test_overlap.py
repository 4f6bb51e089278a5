"""In-program comm/compute overlap (ISSUE 15 tentpole a) on the
virtual 8-device CPU mesh.

Three contracts:

1. **Structure** — the compiled (scheduled) HLO of the fused training
   step shows its per-bucket gradient collectives distributed through
   the backward/update compute, not clumped into one monolithic
   region: async ``*-start``/``*-done`` pairs with compute between
   them on toolchains that split collectives (TPU/GPU with
   MXNET_ASYNC_COLLECTIVES), or >= 2 collective groups separated by
   scheduled compute on sync-collective backends (this CPU build).
   ``mxnet_tpu.hlo.overlap_report`` is the single reader of both.

2. **Numerics** — the bucketed program (MXNET_ZERO_BUCKET_BYTES small
   => many buckets) matches the monolithic-collective program
   (``=0`` => one bucket) within 2e-5 on dp, dp x tp and
   dp x tp x pp meshes; on the dp-only mesh the match is BITWISE (the
   pack -> sum -> unpack layout is per-lane deterministic — the PR-3
   comm.py contract carried into the fused program).

3. **Attribution** — Module.account_program_comm feeds the goodput
   tracker a collective fraction from the compiled step's own cost
   surface, and the step-time decomposition keeps summing to 1.
"""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import hlo as mxhlo
from mxnet_tpu import parallel, profiler

RULES = (("hidden", "tp"), ("embed", None))


def _sym(blocks=4, hidden=32, pp_annot=False):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(
        data, num_hidden=hidden, name="inproj",
        weight=mx.sym.Variable("inproj_weight",
                               attr=parallel.logical_axes("hidden",
                                                          "embed")))
    for i in range(blocks):
        scope = mx.AttrScope(__pp_block__=str(i)) if pp_annot else None
        if scope is not None:
            with scope:
                h = mx.sym.FullyConnected(net, num_hidden=hidden,
                                          name=f"blk{i}_fc")
                net = net + mx.sym.Activation(h, act_type="relu")
        else:
            h = mx.sym.FullyConnected(net, num_hidden=hidden,
                                      name=f"blk{i}_fc")
            net = net + mx.sym.Activation(h, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _train(plan, steps=3, pp_annot=False, optimizer="adam", batch=32):
    mx.random.seed(5)
    rng = np.random.RandomState(0)
    X = rng.randn(batch * steps, 16).astype(np.float32)
    y = rng.randint(0, 8, size=batch * steps).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(_sym(pp_annot=pp_annot), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.07))
    mod.set_mesh_plan(plan)
    mod.init_optimizer(kvstore="tpu", optimizer=optimizer,
                       optimizer_params={"learning_rate": 0.05})
    for b in it:
        mod.forward_backward(b)
        mod.update()
    args, _ = mod.get_params()
    return mod, {k: np.asarray(mx.nd.gather_global(v))
                 for k, v in args.items()}


def _plans():
    import jax

    devs = jax.devices()
    return {
        "dp": lambda: parallel.MeshPlan(devs, dp=8, rules=RULES),
        "dp_tp": lambda: parallel.MeshPlan(devs, dp=4, tp=2,
                                           rules=RULES),
        "dp_tp_pp": lambda: parallel.MeshPlan(devs, dp=2, tp=2, pp=2,
                                              microbatches=2,
                                              rules=RULES),
    }


# ---------------------------------------------------------------------------
# 1. structural overlap in the compiled HLO
# ---------------------------------------------------------------------------

def test_fused_step_hlo_shows_overlap_structure(monkeypatch):
    """What the CPU program still guarantees: the buckets are formed,
    every bucket's parameters come back through its OWN all-gather,
    and the gradients are reduced by collectives — per bucket, or
    merged into fewer by XLA's all-reduce combiner, never more than
    one per bucket.  Any async start/done pairs the backend creates
    must bracket real compute.

    XLA:CPU (jaxlib 0.9) schedules these collectives as one clump
    after the compute, so the interleaving claim lives where it can be
    true: ``chip_smoke.py --chips 4`` prints this same report for the
    dp=2 x tp=2 step compiled for four real chips."""
    monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", "4096")
    mod, _ = _train(_plans()["dp"]())
    n_buckets = len(mod._zero_buckets)
    assert n_buckets >= 2  # the decomposition happened
    report = mxhlo.overlap_report(mod.fused_hlo_text())

    def count(*kinds):
        return sum(v for k, v in report["collectives"].items()
                   if k.removesuffix("-start") in kinds
                   and not k.endswith("-done"))

    assert count("all-gather") == n_buckets, report
    assert 1 <= count("all-reduce", "reduce-scatter") <= n_buckets, report
    if any(k.endswith("-start") for k in report["collectives"]):
        assert report["async_pairs"] > 0, report


def test_fused_step_hlo_pp_has_collective_permute(monkeypatch):
    """The stage-resident pipelined step moves activations between
    stages with collective-permute (the shard_map ppermute helpers) —
    visible in the compiled HLO."""
    monkeypatch.setenv("MXNET_PP_RESIDENT", "1")
    mod, _ = _train(_plans()["dp_tp_pp"](), pp_annot=True)
    assert mod._pp_resident
    report = mxhlo.overlap_report(mod.fused_hlo_text())
    names = set(report["collectives"])
    assert any("collective-permute" in n for n in names), report


def test_overlap_report_async_pairs_branch():
    """The inspector's TPU/GPU branch: ``*-start``/``*-done`` pairs
    count as overlapped ONLY when compute is scheduled between them."""
    overlapped = """HloModule m, is_scheduled=true
ENTRY %main {
  %p0 = f32[8,8]{1,0} parameter(0)
  %ags = (f32[8]{0}, f32[64]{0}) all-gather-start(f32[8]{0} %x)
  %f1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p0), kind=kLoop
  %d1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %f1, f32[8,8]{1,0} %p0)
  %agd = f32[64]{0} all-gather-done((f32[8]{0}, f32[64]{0}) %ags)
  ROOT %r = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %d1), kind=kLoop
}
"""
    r = mxhlo.overlap_report(overlapped)
    assert r["async_pairs"] == 1 and r["overlapped"]
    serialized = overlapped.replace(
        "  %f1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p0), kind=kLoop\n"
        "  %d1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %f1, f32[8,8]{1,0} %p0)\n",
        "").replace(
        "ROOT %r = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %d1), kind=kLoop",
        "ROOT %r = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p0), kind=kLoop")
    r2 = mxhlo.overlap_report(serialized)
    assert r2["async_pairs"] == 0  # back-to-back start/done = no overlap
    assert not r2["overlapped"]
    # byte accounting: the start's tuple counts only the RESULT
    # component (f32[64] = 256B), not the carried operand buffer
    assert mxhlo.collective_bytes(overlapped) == 256


# ---------------------------------------------------------------------------
# 2. bucketed == monolithic numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", ["dp", "dp_tp", "dp_tp_pp"])
def test_bucketed_matches_monolithic(mesh, monkeypatch):
    plans = _plans()
    pp_annot = mesh == "dp_tp_pp"
    monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", "0")  # monolithic
    _, mono = _train(plans[mesh](), pp_annot=pp_annot)
    monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", "2048")  # many buckets
    mod, bucketed = _train(plans[mesh](), pp_annot=pp_annot)
    if not pp_annot:  # resident pp routes the trunk via slabs instead
        assert len(mod._zero_buckets) >= 2
    for k in mono:
        np.testing.assert_allclose(mono[k], bucketed[k], rtol=2e-4,
                                   atol=2e-5, err_msg=f"{mesh}:{k}")


def test_bucketed_is_bitwise_on_dp(monkeypatch):
    """The per-lane pack -> sum -> unpack determinism contract: on the
    dp-only mesh the bucket width never changes a single bit."""
    monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", "0")
    _, mono = _train(_plans()["dp"]())
    monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", "2048")
    _, bucketed = _train(_plans()["dp"]())
    for k in mono:
        np.testing.assert_array_equal(mono[k], bucketed[k], err_msg=k)


def test_buckets_are_backward_ordered_and_capped(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", "4096")
    mod, _ = _train(_plans()["dp"]())
    order = [n for b in mod._zero_buckets for n in b]
    assert order == list(reversed(mod._grad_param_names))
    dp = mod._mesh_plan.dp
    for bucket in mod._zero_buckets:
        nbytes = sum(mod._zero_meta[n][1] * 4 for n in bucket)
        assert len(bucket) == 1 or nbytes <= 4096


# ---------------------------------------------------------------------------
# 3. goodput attribution of in-program collectives
# ---------------------------------------------------------------------------

def test_account_program_comm_feeds_tracker():
    mod, _ = _train(_plans()["dp"]())
    frac = mod.account_program_comm()
    assert frac is not None and 0 < frac <= 0.9
    assert mod._program_comm_fraction == frac


def test_program_comm_fraction_decomposition_sums_to_one():
    g = profiler.GoodputTracker(registry=profiler.MetricsRegistry())
    g.set_program_comm_fraction(0.25)
    for _ in range(4):
        g.step(0.1, io_s=0.02)
    s = g.summary()
    d = s["decomposition"]
    assert sum(d.values()) == pytest.approx(1.0)
    # 25% of the in-step time books as comm WITHOUT any scheduler waits
    assert d["comm"] == pytest.approx(0.025 / 0.12, rel=1e-6)
    assert s["program_comm_fraction"] == 0.25
    # composes with host-side comm: scheduler waits come off the top
    g2 = profiler.GoodputTracker(registry=profiler.MetricsRegistry())
    g2.set_program_comm_fraction(0.5)
    g2.add_comm(0.04)
    g2.step(0.1)
    d2 = g2.summary()["decomposition"]
    assert sum(d2.values()) == pytest.approx(1.0)
    assert d2["comm"] == pytest.approx((0.04 + 0.5 * 0.06) / 0.1,
                                       rel=1e-6)


# ---------------------------------------------------------------------------
# env validation + flag wiring
# ---------------------------------------------------------------------------

def test_zero_bucket_bytes_validation(monkeypatch):
    for bad in ("banana", "-1"):
        monkeypatch.setenv("MXNET_ZERO_BUCKET_BYTES", bad)
        with pytest.raises(mx.MXNetError, match="MXNET_ZERO_BUCKET"):
            _train(_plans()["dp"](), steps=1)


def test_pp_resident_validation(monkeypatch):
    monkeypatch.setenv("MXNET_PP_RESIDENT", "banana")
    with pytest.raises(mx.MXNetError, match="MXNET_PP_RESIDENT"):
        _train(_plans()["dp_tp_pp"](), steps=1, pp_annot=True)


def test_async_collectives_validation(monkeypatch):
    from mxnet_tpu import config

    monkeypatch.setenv("MXNET_ASYNC_COLLECTIVES", "banana")
    with pytest.raises(mx.MXNetError, match="MXNET_ASYNC_COLLECTIVES"):
        config.ensure_overlap_flags()


def test_async_flags_go_to_libtpu_only_on_tpu(monkeypatch):
    from mxnet_tpu import config

    # XLA_FLAGS is never touched: jaxlib aborts on flags it does not
    # know, and these are libtpu's (read from LIBTPU_INIT_ARGS)
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    # CPU: nothing appended
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_bar=1")
    assert config.ensure_overlap_flags() is False
    assert os.environ["LIBTPU_INIT_ARGS"] == "--xla_bar=1"
    # TPU: the async-collective set lands, user flags never overridden
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv(
        "LIBTPU_INIT_ARGS", "--xla_enable_async_all_gather=false")
    assert config.ensure_overlap_flags() is True
    flags = os.environ["LIBTPU_INIT_ARGS"].split()
    assert "--xla_enable_async_all_gather=false" in flags  # user wins
    assert flags.count("--xla_enable_async_all_gather=false") == 1
    assert not any(f == "--xla_enable_async_all_gather=true"
                   for f in flags)
    assert "--xla_tpu_enable_async_collective_fusion=true" in flags
    assert not any("xla_gpu" in f for f in flags)
    assert config.ensure_overlap_flags() is False  # idempotent
    # off switch
    monkeypatch.setenv("MXNET_ASYNC_COLLECTIVES", "0")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "")
    assert config.ensure_overlap_flags() is False
    assert os.environ["LIBTPU_INIT_ARGS"] == ""
    assert os.environ["XLA_FLAGS"] == "--xla_foo=1"


def test_launchers_refuse_to_share_a_chip(monkeypatch):
    """One process for each chip: a JAX child that targets the TPU
    without a chip of its own is refused with a message (it would
    otherwise fail or hang at its first backend); CPU children pass."""
    from mxnet_tpu import config, fleet

    config.refuse_shared_chip({"JAX_PLATFORMS": "cpu"}, "t")
    config.refuse_shared_chip(
        {"JAX_PLATFORMS": "tpu,cpu", "TPU_VISIBLE_CHIPS": "1"}, "t")
    with pytest.raises(mx.MXNetError, match="TPU_VISIBLE_CHIPS"):
        config.refuse_shared_chip({"JAX_PLATFORMS": "tpu,cpu"}, "t")
    # spawn_replica goes through it before any process is started
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    with pytest.raises(mx.MXNetError, match="spawn_replica"):
        fleet.spawn_replica(0, "/nonexistent", "m:f")
