"""Predict API + standalone export tests (reference:
c_predict_api.cc workflow + amalgamation deployability)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trained_module(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(120, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Activation(
                mx.sym.FullyConnected(mx.sym.Variable("data"),
                                      num_hidden=8, name="fc1"),
                act_type="relu"),
            num_hidden=2, name="fc2"), name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3})
    prefix = str(tmp_path / "model")
    mod.save_checkpoint(prefix, 4)
    return mod, net, prefix, X


def test_predictor_matches_module(tmp_path):
    mod, net, prefix, X = _trained_module(tmp_path)
    batch = X[:20]
    pred = mx.Predictor.from_checkpoint(prefix, 4,
                                        {"data": (20, 6),
                                         "softmax_label": (20,)})
    pred.set_input("data", batch)
    pred.set_input("softmax_label", np.zeros((20,), np.float32))
    out = pred.forward().get_output(0)
    mod.forward(mx.io.DataBatch([mx.nd.array(batch)],
                                [mx.nd.zeros((20,))]), is_train=False)
    expect = mod.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


def test_predictor_rejects_bad_input(tmp_path):
    _, _, prefix, _ = _trained_module(tmp_path)
    pred = mx.Predictor.from_checkpoint(prefix, 4,
                                        {"data": (4, 6),
                                         "softmax_label": (4,)})
    with pytest.raises(mx.MXNetError, match="shape"):
        pred.set_input("data", np.zeros((4, 7), np.float32))
    with pytest.raises(mx.MXNetError, match="unknown input"):
        pred.set_input("fc1_weight", np.zeros((8, 6), np.float32))
    with pytest.raises(mx.MXNetError, match="not set"):
        pred.forward(data=np.zeros((4, 6), np.float32))


def test_export_and_load(tmp_path):
    mod, net, prefix, X = _trained_module(tmp_path)
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "model.mxtpu")
    mx.predictor.export_model(
        net, arg_params, aux_params,
        {"data": (20, 6), "softmax_label": (20,)}, path=path)
    fn, meta = mx.predictor.load_exported(path)
    assert meta["inputs"] == ["data", "softmax_label"]
    out = np.asarray(fn(X[:20], np.zeros((20,), np.float32))[0])
    mod.forward(mx.io.DataBatch([mx.nd.array(X[:20])],
                                [mx.nd.zeros((20,))]), is_train=False)
    np.testing.assert_allclose(out, mod.get_outputs()[0].asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_exported_artifact_runs_without_mxnet_tpu(tmp_path):
    """The amalgamation claim: the artifact runs with jax alone."""
    mod, net, prefix, X = _trained_module(tmp_path)
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "model.mxtpu")
    mx.predictor.export_model(
        net, arg_params, aux_params,
        {"data": (20, 6), "softmax_label": (20,)}, path=path)
    mod.forward(mx.io.DataBatch([mx.nd.array(X[:20])],
                                [mx.nd.zeros((20,))]), is_train=False)
    expect_path = str(tmp_path / "expect.npy")
    np.save(expect_path, mod.get_outputs()[0].asnumpy())
    in_path = str(tmp_path / "in.npy")
    np.save(in_path, X[:20])
    script = f"""
import sys
import numpy as np
from jax import export
raw = open({path!r}, 'rb').read()
assert raw.startswith(b'MXTPUEXP1')
n = int.from_bytes(raw[9:17], 'little')
fn = export.deserialize(raw[17 + n:]).call
x = np.load({in_path!r})
out = np.asarray(fn(x, np.zeros((20,), np.float32))[0])
np.testing.assert_allclose(out, np.load({expect_path!r}),
                           rtol=1e-5, atol=1e-6)
forbidden = [m for m in sys.modules if m.startswith('mxnet_tpu')]
assert not forbidden, forbidden
print('standalone artifact OK')
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "standalone artifact OK" in r.stdout


def test_predictor_dict_params_with_aux(tmp_path):
    """In-memory params dict incl. BatchNorm aux states works."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.BatchNorm(mx.sym.Variable("data"), name="bn"),
            num_hidden=2, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    arg_params, aux_params = mod.get_params()
    pred = mx.Predictor(net, {**arg_params, **aux_params},
                        {"data": (4, 6), "softmax_label": (4,)})
    x = np.random.RandomState(0).randn(4, 6).astype(np.float32)
    out = pred.forward(data=x,
                       softmax_label=np.zeros(4, np.float32)).get_output(0)
    mod.forward(mx.io.DataBatch([mx.nd.array(x)], [mx.nd.zeros((4,))]),
                is_train=False)
    np.testing.assert_allclose(out, mod.get_outputs()[0].asnumpy(),
                               rtol=1e-5, atol=1e-6)
