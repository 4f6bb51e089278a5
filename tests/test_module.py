"""Module tests (modeled on tests/python/unittest/test_module.py +
tests/python/train/test_mlp.py convergence check)."""

import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx


def _make_data(n=400, d=16, k=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = np.argmax(X @ rng.randn(d, k), axis=1).astype(np.float32)
    return X, y


def _mlp_sym(k=3):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=k, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_module_states_and_shapes():
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu())
    assert not mod.binded
    mod.bind(data_shapes=[("data", (8, 16))], label_shapes=[("softmax_label", (8,))])
    assert mod.binded
    assert mod.data_shapes == [("data", (8, 16))]
    mod.init_params()
    assert mod.params_initialized
    arg_params, aux_params = mod.get_params()
    assert set(arg_params) == {"fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"}


def test_module_fit_convergence():
    np.random.seed(42)  # NDArrayIter shuffle draws from the global RNG
    # ... and the initializer from the process-global mx.random key,
    # which is wherever the tests this xdist worker ran before left it
    # (1 start in 12 ends at 0.88)
    mx.random.seed(0)
    X, y = _make_data()
    train = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            num_epoch=6)
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=20), "acc")
    assert score[0][1] > 0.9, f"accuracy {score} too low"


def test_module_predict():
    X, y = _make_data(n=64)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=False)
    mod.init_params()
    out = mod.predict(it)
    assert out.shape == (64, 3)
    np.testing.assert_allclose(out.asnumpy().sum(1), np.ones(64), rtol=1e-4)


def test_module_checkpoint_roundtrip():
    X, y = _make_data(n=100)
    train = mx.io.NDArrayIter(X, y, batch_size=10)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            num_epoch=2)
    ref = mod.score(mx.io.NDArrayIter(X, y, batch_size=10), "acc")[0][1]
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "model")
        mod.save_checkpoint(prefix, 2, save_optimizer_states=True)
        assert os.path.exists(prefix + "-symbol.json")
        assert os.path.exists(prefix + "-0002.params")
        mod2 = mx.mod.Module.load(prefix, 2)
        it = mx.io.NDArrayIter(X, y, batch_size=10)
        mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
                  for_training=False)
        got = mod2.score(it, "acc")[0][1]
        assert abs(got - ref) < 1e-6


def test_module_input_grads():
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 16))], label_shapes=[("softmax_label", (4,))],
             inputs_need_grad=True)
    mod.init_params()
    X, y = _make_data(n=4)
    batch = mx.io.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    mod.forward(batch, is_train=True)
    mod.backward()
    (din,) = mod.get_input_grads()
    assert din.shape == (4, 16)
    assert np.abs(din.asnumpy()).sum() > 0


def test_module_fixed_params():
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu(), fixed_param_names=["fc1_weight"])
    mod.bind(data_shapes=[("data", (4, 16))], label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.5})
    w_before = mod._exec.arg_dict["fc1_weight"].asnumpy().copy()
    X, y = _make_data(n=4)
    batch = mx.io.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    mod.forward_backward(batch)
    mod.update()
    w_after = mod._exec.arg_dict["fc1_weight"].asnumpy()
    np.testing.assert_array_equal(w_before, w_after)


def test_module_kvstore_local():
    X, y = _make_data()
    train = mx.io.NDArrayIter(X, y, batch_size=20)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, optimizer="sgd", kvstore="local",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9}, num_epoch=4)
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=20), "acc")
    assert score[0][1] > 0.85


def test_module_bucketing_shared():
    # shared-module rebinding path used by BucketingModule
    sym = _mlp_sym()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 16))], label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod2 = mx.mod.Module(sym, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 16))], label_shapes=[("softmax_label", (4,))],
              shared_module=mod)
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    np.testing.assert_allclose(a1["fc1_weight"].asnumpy(), a2["fc1_weight"].asnumpy())


def test_module_tied_param_buffers_train():
    """Two trainable params sharing one buffer must not break the fused
    (donating) step — regression for 'donate the same buffer twice'."""
    data = mx.sym.Variable("data")
    a = mx.sym.FullyConnected(data, num_hidden=16, no_bias=True, name="enc")
    a = mx.sym.Activation(a, act_type="tanh")
    out = mx.sym.FullyConnected(a, num_hidden=16, no_bias=True, name="dec")
    net = mx.sym.LinearRegressionOutput(out, name="lro")

    rng = np.random.RandomState(3)
    X = rng.randn(64, 16).astype(np.float32)
    it = mx.io.NDArrayIter(X, X[:, :16], batch_size=16, label_name="lro_label")
    mod = mx.mod.Module(net, context=mx.cpu(), label_names=("lro_label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.1))
    # tie: both weights literally share one jax buffer
    w = mod._exec.arg_dict["enc_weight"]
    mod._exec.arg_dict["dec_weight"]._set_data(w._data)
    assert mod._exec.arg_dict["dec_weight"]._data is w._data
    mod.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    for b in it:
        mod.forward_backward(b)
        mod.update()
    out = mod.get_outputs()[0].asnumpy()
    assert np.all(np.isfinite(out))


def test_module_copy_initialized_states_train():
    """arg_params built from an array and its .copy() (the RNN-state
    pattern) must produce distinct donated buffers and train."""
    z = mx.nd.zeros((4, 4))
    z2 = z.copy()
    assert z2._data is not z._data

    data = mx.sym.Variable("data")
    a = mx.sym.Variable("a_weight")
    b = mx.sym.Variable("b_weight")
    net = mx.sym.FullyConnected(data, weight=a, num_hidden=4, no_bias=True,
                                name="fa")
    net = mx.sym.FullyConnected(net, weight=b, num_hidden=4, no_bias=True,
                                name="fb")
    net = mx.sym.LinearRegressionOutput(mx.sym.sum(net, axis=1), name="lro")
    rng = np.random.RandomState(0)
    X = rng.randn(32, 4).astype(np.float32)
    it = mx.io.NDArrayIter(X, X.sum(axis=1), batch_size=8, label_name="lro_label")
    mod = mx.mod.Module(net, context=mx.cpu(), label_names=("lro_label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    w = mx.nd.array(rng.randn(4, 4).astype(np.float32) * 0.1)
    mod.init_params(arg_params={"a_weight": w, "b_weight": w.copy()},
                    allow_missing=True)
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.01})
    for _ in range(2):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
    out = mod.get_outputs()[0].asnumpy()
    assert np.all(np.isfinite(out))


def test_module_param_aliased_to_frozen_buffer_train():
    """A trainable param sharing a buffer with a frozen (grad_req null)
    param must not get the shared buffer deleted by donation."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, no_bias=True, name="enc")
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, num_hidden=16, no_bias=True, name="dec")
    net = mx.sym.LinearRegressionOutput(net, name="lro")
    rng = np.random.RandomState(1)
    X = rng.randn(64, 16).astype(np.float32)
    it = mx.io.NDArrayIter(X, X, batch_size=16, label_name="lro_label")
    mod = mx.mod.Module(net, context=mx.cpu(), label_names=("lro_label",),
                        fixed_param_names=["dec_weight"])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.initializer.Uniform(0.1))
    # frozen dec_weight shares the trainable enc_weight's buffer
    w = mod._exec.arg_dict["enc_weight"]
    mod._exec.arg_dict["dec_weight"]._set_data(w._data)
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.01})
    for _ in range(3):  # >1 step: step 2 re-reads the frozen buffer
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
    out = mod.get_outputs()[0].asnumpy()
    assert np.all(np.isfinite(out))
    # the frozen param's buffer must still be alive and unchanged shape
    assert mod._exec.arg_dict["dec_weight"].asnumpy().shape == (16, 16)


def test_resnet_s2d_stem_equivalence():
    """The space-to-depth stem with an embedded 7x7 weight computes the
    identical function to the reference conv7 stem (models/resnet.py
    _s2d_stem / conv7_to_s2d_weight)."""
    import importlib
    R = importlib.import_module("mxnet_tpu.models.resnet")

    rng = np.random.RandomState(0)
    batch, hw = 2, 64  # >32 so the imagenet stem is selected
    X = rng.randn(batch, 3, hw, hw).astype(np.float32)
    outs = {}
    for stem in ("conv7", "s2d"):
        sym = R.get_symbol(num_classes=10, num_layers=50,
                           image_shape=(3, hw, hw), stem=stem)
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[mx.io.DataDesc("data", (batch, 3, hw, hw))],
                 label_shapes=[mx.io.DataDesc("softmax_label", (batch,))],
                 for_training=False)
        mod.init_params(mx.initializer.Xavier(rnd_type="gaussian"))
        if stem == "conv7":
            arg_params, aux_params = mod.get_params()
            saved = ({k: v.asnumpy() for k, v in arg_params.items()},
                     {k: v.asnumpy() for k, v in aux_params.items()})
        else:
            args, auxs = saved
            args = dict(args)
            args["conv0_weight"] = R.conv7_to_s2d_weight(
                args["conv0_weight"])
            mod.set_params({k: mx.nd.array(v) for k, v in args.items()},
                           {k: mx.nd.array(v) for k, v in auxs.items()})
        mod.forward(mx.io.DataBatch(
            [mx.nd.array(X)], [mx.nd.array(np.zeros(batch, np.float32))]),
            is_train=False)
        outs[stem] = mod.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(outs["s2d"], outs["conv7"],
                               rtol=1e-4, atol=1e-5)
