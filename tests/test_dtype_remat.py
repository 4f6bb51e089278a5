"""Low-precision training + gradient-mirroring + optimizer-op tests
(reference: tests/python/train/test_dtype.py; MXNET_BACKWARD_DO_MIRROR
graph_executor.cc:199-212; optimizer_op.cc)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _data(n=300, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8).astype(np.float32)
    y = np.argmax(X @ rng.randn(8, 3), axis=1).astype(np.float32)
    return X, y


@pytest.mark.parametrize("dtype", ["float16", "bfloat16"])
def test_low_precision_training(dtype):
    """infer_type propagates the input dtype into every param and the
    model still converges (reference test_dtype.py fp16 check)."""
    import jax.numpy as jnp

    X, y = _data()
    sym = _mlp()
    arg_types, out_types, _ = sym.infer_type(data=dtype)
    named = dict(zip(sym.list_arguments(), arg_types))
    assert str(named["fc1_weight"]) == dtype
    assert str(named["fc2_bias"]) == dtype
    assert str(named["softmax_label"]) == "float32"

    exe = sym.simple_bind(mx.cpu(), grad_req="write",
                          type_dict={"data": dtype},
                          data=(20, 8), softmax_label=(20,))
    assert str(exe.arg_dict["fc1_weight"].dtype) == dtype

    mod = mx.mod.Module(sym, context=mx.cpu())
    it = mx.io.NDArrayIter(X.astype(dtype), y, batch_size=20)
    # the iterator's DataDesc carries the dtype; bind propagates it
    # into the parameters via infer_type
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    assert str(mod._exec.arg_dict["fc1_weight"].dtype) == dtype
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.2,
                                         "momentum": 0.9})
    for _ in range(8):
        it.reset()
        for b in it:
            mod.forward_backward(b)
            mod.update()
    assert str(mod._exec.arg_dict["fc1_weight"].dtype) == dtype
    score = mod.score(mx.io.NDArrayIter(X.astype(dtype), y, batch_size=20),
                      "acc")
    assert score[0][1] > 0.8, score


def test_backward_do_mirror_same_numerics():
    """Remat changes memory, not math: loss trajectory identical."""
    script = r"""
import os, sys
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import mxnet_tpu as mx
rng = np.random.RandomState(0)
X = rng.randn(100, 8).astype(np.float32)
y = np.argmax(X @ rng.randn(8, 3), axis=1).astype(np.float32)
data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")
it = mx.io.NDArrayIter(X, y, batch_size=20)
mod = mx.mod.Module(net, context=mx.cpu())
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
         for_training=True)
mx.random.seed(3)
mod.init_params(mx.initializer.Xavier())
mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
for _ in range(3):
    it.reset()
    for b in it:
        mod.forward_backward(b); mod.update()
w = mod.get_params()[0]["fc1_weight"].asnumpy()
np.save(sys.argv[1], w)
"""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        outs = []
        for mirror in ("0", "1"):
            out = os.path.join(d, f"w{mirror}.npy")
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       MXNET_BACKWARD_DO_MIRROR=mirror)
            r = subprocess.run([sys.executable, "-c", script % REPO, out],
                               capture_output=True, text=True, env=env,
                               timeout=120)
            assert r.returncode == 0, r.stderr
            outs.append(np.load(out))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_sgd_update_op():
    rng = np.random.RandomState(0)
    w = rng.randn(4, 5).astype(np.float32)
    g = rng.randn(4, 5).astype(np.float32)
    out = mx.nd.sgd_update(mx.nd.array(w), mx.nd.array(g), lr="0.1",
                           wd="0.01", rescale_grad="0.5").asnumpy()
    np.testing.assert_allclose(out, w - 0.1 * (0.5 * g + 0.01 * w),
                               rtol=1e-5)
    # clipping
    out = mx.nd.sgd_update(mx.nd.array(w), mx.nd.array(g * 100), lr="0.1",
                           clip_gradient="1.0").asnumpy()
    np.testing.assert_allclose(out, w - 0.1 * np.clip(g * 100, -1, 1),
                               rtol=1e-5)


def test_sgd_mom_update_op():
    rng = np.random.RandomState(1)
    w = rng.randn(6).astype(np.float32)
    g = rng.randn(6).astype(np.float32)
    mom = rng.randn(6).astype(np.float32)
    new_w, new_mom = mx.nd.sgd_mom_update(
        mx.nd.array(w), mx.nd.array(g), mx.nd.array(mom),
        lr="0.1", momentum="0.9")
    expect_mom = 0.9 * mom - 0.1 * g
    np.testing.assert_allclose(new_mom.asnumpy(), expect_mom, rtol=1e-5)
    np.testing.assert_allclose(new_w.asnumpy(), w + expect_mom, rtol=1e-5)


def test_adam_update_op():
    rng = np.random.RandomState(2)
    w = rng.randn(8).astype(np.float32)
    g = rng.randn(8).astype(np.float32)
    mean = np.zeros(8, np.float32)
    var = np.zeros(8, np.float32)
    new_w, new_mean, new_var = mx.nd.adam_update(
        mx.nd.array(w), mx.nd.array(g), mx.nd.array(mean), mx.nd.array(var),
        lr="0.01", beta1="0.9", beta2="0.999", epsilon="1e-8")
    em = 0.1 * g
    ev = 0.001 * g * g
    np.testing.assert_allclose(new_mean.asnumpy(), em, rtol=1e-5)
    np.testing.assert_allclose(new_var.asnumpy(), ev, rtol=1e-4)
    np.testing.assert_allclose(
        new_w.asnumpy(), w - 0.01 * em / (np.sqrt(ev) + 1e-8), rtol=1e-5)


def test_variable_dtype_pin():
    """Variable(dtype=...) pins propagate through infer_type."""
    data = mx.sym.Variable("data", dtype="float16")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    at, ot, _ = net.infer_type()
    named = dict(zip(net.list_arguments(), at))
    assert str(named["data"]) == "float16"
    assert str(named["fc_weight"]) == "float16"
    assert str(ot[0]) == "float16"


def test_adam_update_op_with_wd():
    """wd applies as decoupled decay, moments stay wd-free (reference
    optimizer_op-inl.h:160-176)."""
    rng = np.random.RandomState(3)
    w = rng.randn(5).astype(np.float32)
    g = rng.randn(5).astype(np.float32)
    mean = rng.randn(5).astype(np.float32) * 0.1
    var = np.abs(rng.randn(5)).astype(np.float32) * 0.1
    new_w, new_mean, new_var = mx.nd.adam_update(
        mx.nd.array(w), mx.nd.array(g), mx.nd.array(mean), mx.nd.array(var),
        lr="0.01", wd="0.01", beta1="0.9", beta2="0.999", epsilon="1e-8")
    em = 0.9 * mean + 0.1 * g
    ev = 0.999 * var + 0.001 * g * g
    np.testing.assert_allclose(new_mean.asnumpy(), em, rtol=1e-5)
    np.testing.assert_allclose(new_var.asnumpy(), ev, rtol=1e-4)
    np.testing.assert_allclose(
        new_w.asnumpy(),
        (1 - 0.01 * 0.01) * w - 0.01 * em / (np.sqrt(ev) + 1e-8), rtol=1e-5)


def test_infer_type_multi_branch():
    """A known output dtype flows back into untyped branches."""
    a = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fca")
    b = mx.sym.FullyConnected(mx.sym.Variable("side"), num_hidden=4,
                              name="fcb")
    out = a + b
    at, _, _ = out.infer_type(data="float16")
    named = dict(zip(out.list_arguments(), at))
    assert str(named["fcb_weight"]) == "float16", named
    assert str(named["side"]) == "float16", named
