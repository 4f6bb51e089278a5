"""ZeRO-1 sharded optimizer (MXNET_ZERO) on the virtual 8-device CPU
mesh: sharded-vs-replicated weight equivalence, state sharding and
per-device byte reduction, layout-independent checkpoints, bucketed
state migration, and the bench tool.

Tolerances: the sharded update computes each element's update on
exactly ONE device from the same psum'd gradient the replicated update
uses; the only permitted difference is fp reassociation of the
gradient reduction (reduce-scatter vs all-reduce schedules), so
equivalence is asserted at rtol=1e-6.
"""

import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx


@pytest.fixture(autouse=True)
def _clean_zero_env():
    old = os.environ.pop("MXNET_ZERO", None)
    yield
    if old is None:
        os.environ.pop("MXNET_ZERO", None)
    else:
        os.environ["MXNET_ZERO"] = old


def _sym(tp_shard=False):
    from mxnet_tpu import parallel

    data = mx.sym.Variable("data")
    kw = {"attr": parallel.shard_attr("tp", 0)} if tp_shard else {}
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1", **kw)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(steps=6, batch=16, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(batch * steps, 8).astype(np.float32)
    y = rng.randint(0, 4, size=batch * steps).astype(np.float32)
    return X, y


def _make_mod(zero, optimizer="adam", arg_params=None, tp=0, batch=16,
              opt_params=None):
    os.environ["MXNET_ZERO"] = "1" if zero else "0"
    mx.random.seed(7)
    X, y = _data(batch=batch)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(_sym(tp_shard=bool(tp)), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.1), arg_params=arg_params)
    if tp:
        from mxnet_tpu import parallel

        mod.set_mesh_plan(parallel.make_plan(tp=tp))
    mod.init_optimizer(kvstore="tpu", optimizer=optimizer,
                       optimizer_params=opt_params
                       or {"learning_rate": 0.05})
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
    return {k: v.asnumpy() for k, v in args.items()}


def _train(zero, optimizer="adam", **kw):
    mod, it = _make_mod(zero, optimizer, **kw)
    return mod, _run(mod, it)


@pytest.mark.parametrize("optimizer", ["adam", "sgd", "rmsprop"])
def test_zero_matches_replicated(optimizer):
    """Same model, same data: MXNET_ZERO=1 and =0 reach equal weights."""
    opt_params = {"learning_rate": 0.05}
    if optimizer == "sgd":
        opt_params["momentum"] = 0.9
    _, rep = _train(False, optimizer, opt_params=opt_params)
    mod, zer = _train(True, optimizer, opt_params=opt_params)
    assert mod._zero, "dp>1 mesh must default ZeRO on"
    for k in rep:
        np.testing.assert_allclose(rep[k], zer[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_zero_state_sharded_and_smaller():
    """Adam m/v live flat, 'dp'-sharded; per-device bytes drop ~dp×;
    the executor.opt_state_bytes gauge reports the sharded number."""
    import jax
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import profiler

    mod_rep, _ = _train(False)
    rep_bytes = mod_rep._opt_state_bytes_per_device()
    mod, _ = _train(True)
    zero_bytes = mod._opt_state_bytes_per_device()
    dp = mod._mesh_plan.dp
    assert dp == len(jax.devices())
    for n, tree in mod._fused_state.items():
        size, padded = mod._zero_meta[n]
        assert padded % dp == 0 and padded >= size
        for leaf in jax.tree_util.tree_leaves(tree):
            assert leaf.shape == (padded,)
            assert leaf.sharding.spec == P("dp")
    # equality would need pad-free divisibility; bias params pad up
    assert zero_bytes <= rep_bytes / dp * 1.5, (zero_bytes, rep_bytes)
    assert profiler.metrics_summary()["gauges"][
        "executor.opt_state_bytes"] == zero_bytes


def test_zero_off_without_mesh():
    """Single-device training never shards (dp=1 ⇒ replicated path)."""
    os.environ["MXNET_ZERO"] = "1"
    mx.random.seed(7)
    X, y = _data()
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(optimizer="adam")
    b = next(iter(it))
    mod.forward_backward(b)
    mod.update()
    assert not mod._zero


def test_zero_env_opt_out():
    """MXNET_ZERO=0 keeps the replicated update even on a dp>1 mesh
    (the mode is latched when the fused step is first built)."""
    mod, it = _make_mod(True)
    os.environ["MXNET_ZERO"] = "0"  # before the first update
    b = next(iter(it))
    mod.forward_backward(b)
    mod.update()
    assert not mod._zero


@pytest.mark.parametrize("save_zero,load_zero",
                         [(True, False), (False, True), (True, True)])
def test_zero_checkpoint_cross_layout(save_zero, load_zero):
    """Optimizer states saved under one layout load under the other:
    split training (3 steps, save, load elsewhere, 3 more) equals 6
    uninterrupted replicated steps."""
    mod_ref, it_ref = _make_mod(False)
    ref = _run(mod_ref, it_ref, n_steps=6)

    mod1, it1 = _make_mod(save_zero)
    _run(mod1, it1, n_steps=3)
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "opt.states")
        mod1.save_optimizer_states(fname)
        args, _ = mod1.get_params()
        mod2, it2 = _make_mod(load_zero, arg_params=args)
        mod2.load_optimizer_states(fname)
        got = _run(mod2, it2, n_steps=3, skip=3)
    for k in ref:
        np.testing.assert_allclose(ref[k], got[k], rtol=1e-6, atol=1e-7,
                                   err_msg=f"{save_zero}->{load_zero} {k}")


def test_zero_checkpoint_via_module_save(tmp_path):
    """Module.save_checkpoint/save_optimizer_states writes REAL fused
    state (not the empty eager Updater) and Module.load restores it."""
    mod, it = _make_mod(True, "adam")
    _run(mod, it, n_steps=4)
    prefix = str(tmp_path / "zckpt")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    import pickle

    with open(prefix + "-0001.states", "rb") as f:
        data = pickle.loads(f.read())
    assert data["format"] == "mxnet_tpu-fused-states-v1"
    assert data["step"] == 4
    # Adam m/v are param-shaped (layout-independent), nonzero after 4
    # steps
    m, v = data["states"]["fc1_weight"]
    assert m.shape == (16, 8) and np.abs(m).sum() > 0


def test_zero_save_right_after_load_preserves_states():
    """load → save with NO step in between must round-trip the blob
    (regression: the pre-build save path wrote an empty Updater dict,
    silently dropping the checkpoint on e.g. rotation-at-resume)."""
    import pickle

    mod1, it1 = _make_mod(True)
    _run(mod1, it1, n_steps=3)
    with tempfile.TemporaryDirectory() as d:
        f1 = os.path.join(d, "a.states")
        f2 = os.path.join(d, "b.states")
        mod1.save_optimizer_states(f1)
        args, _ = mod1.get_params()
        mod2, _ = _make_mod(False, arg_params=args)
        mod2.load_optimizer_states(f1)
        mod2.save_optimizer_states(f2)  # fused programs not built yet
        with open(f2, "rb") as fh:
            data = pickle.loads(fh.read())
        assert data["format"] == "mxnet_tpu-fused-states-v1"
        assert data["step"] == 3
        m1, _ = data["states"]["fc1_weight"]
        with open(f1, "rb") as fh:
            orig = pickle.loads(fh.read())
        np.testing.assert_array_equal(m1, orig["states"]["fc1_weight"][0])


def test_zero_with_tensor_parallel():
    """ZeRO composes with a 'tp'-sharded param: the updated weight is
    gathered back to its tp layout and training matches ZeRO-off."""
    from jax.sharding import PartitionSpec as P

    _, rep = _train(False, tp=2)
    mod, zer = _train(True, tp=2)
    assert mod._zero and mod._mesh_plan.tp == 2
    assert mod._exec.arg_dict["fc1_weight"]._data.sharding.spec \
        == P("tp", None)
    for k in rep:
        np.testing.assert_allclose(rep[k], zer[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_zero_bucketing_state_migration():
    """_adopt_fused_state carries the sharded slots (and the ZeRO
    layout metadata) to the next bucket's module."""
    os.environ["MXNET_ZERO"] = "1"
    mx.random.seed(7)
    X, y = _data(batch=16)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.1))
    mod.init_optimizer(kvstore="tpu", optimizer="adam")
    b = next(iter(it))
    mod.forward_backward(b)
    mod.update()
    assert mod._zero

    mod2 = mx.mod.Module(_sym(), context=mx.cpu())
    mod2.bind(data_shapes=[("data", (8, 8))],
              label_shapes=[("softmax_label", (8,))],
              for_training=True, shared_module=mod)
    mod2.set_mesh_plan(mod._mesh_plan)
    mod2.borrow_optimizer(mod)
    mod2._adopt_fused_state(mod)
    assert mod2._zero and mod2._zero_meta == mod._zero_meta
    assert mod2._fused_state is mod._fused_state
    b2 = mx.io.DataBatch(data=[mx.nd.array(X[:8])],
                         label=[mx.nd.array(y[:8])])
    mod2.forward(b2, is_train=True)
    mod2.backward()
    mod2.update()
    out = mod2.get_outputs()[0].asnumpy()
    assert np.isfinite(out).all()
