"""Pallas kernel tests — run in interpreter mode on CPU (same kernel
code path as TPU) and compare against the scan/fori formulations."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _np_lstm(xw, h0, c0, ut):
    T, B, G = xw.shape
    H = G // 4
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    h, c = h0.copy(), c0.copy()
    ys = np.zeros((T, B, H), np.float64)
    for t in range(T):
        pre = xw[t] + h @ ut
        i, f, g, o = [pre[:, k * H:(k + 1) * H] for k in range(4)]
        c = sig(f) * c + sig(i) * np.tanh(g)
        h = sig(o) * np.tanh(c)
        ys[t] = h
    return ys, h, c


def test_lstm_scan_kernel_matches_numpy(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "1")
    rng = np.random.RandomState(0)
    T, B, H = 5, 4, 8
    xw = rng.randn(T, B, 4 * H).astype(np.float32) * 0.5
    h0 = rng.randn(B, H).astype(np.float32) * 0.1
    c0 = rng.randn(B, H).astype(np.float32) * 0.1
    ut = rng.randn(H, 4 * H).astype(np.float32) * 0.2
    y, hT, cT = pk.lstm_scan(xw, h0, c0, ut)
    ey, eh, ec = _np_lstm(xw.astype(np.float64), h0, c0, ut)
    np.testing.assert_allclose(np.asarray(y), ey, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), eh, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cT), ec, rtol=1e-4, atol=1e-5)


def test_lstm_scan_kernel_gradients(monkeypatch):
    """custom_vjp (remat through the scan) == direct scan gradients."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("MXNET_PALLAS", "1")
    rng = np.random.RandomState(1)
    T, B, H = 4, 3, 6
    xw = jnp.asarray(rng.randn(T, B, 4 * H).astype(np.float32) * 0.4)
    h0 = jnp.zeros((B, H), jnp.float32)
    c0 = jnp.zeros((B, H), jnp.float32)
    ut = jnp.asarray(rng.randn(H, 4 * H).astype(np.float32) * 0.3)

    def loss_pallas(xw, ut):
        y, hT, cT = pk.lstm_scan(xw, h0, c0, ut)
        return jnp.sum(y ** 2) + jnp.sum(hT * cT)

    def loss_scan(xw, ut):
        y, hT, cT = pk._lstm_reference(xw, h0, c0, ut)
        return jnp.sum(y ** 2) + jnp.sum(hT * cT)

    gp = jax.grad(loss_pallas, argnums=(0, 1))(xw, ut)
    gs = jax.grad(loss_scan, argnums=(0, 1))(xw, ut)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_rnn_op_uses_pallas_same_result():
    """mx.nd.RNN under MXNET_PALLAS=1 equals MXNET_PALLAS=0 (subprocess
    so the op caches can't mix the two modes)."""
    script = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import jax
import jax.numpy as jnp
import numpy as np
import mxnet_tpu as mx
rng = np.random.RandomState(0)
T, B, I, H = 6, 4, 5, 8
from mxnet_tpu.ops.rnn import rnn_param_size
x = rng.randn(T, B, I).astype(np.float32)
p = rng.randn(rnn_param_size(1, I, H, 1, "lstm")).astype(np.float32) * 0.2
s = np.zeros((1, B, H), np.float32)
out = mx.nd.RNN(mx.nd.array(x), mx.nd.array(p), mx.nd.array(s),
                mx.nd.array(s.copy()), state_size=H, num_layers=1,
                mode="lstm")
np.save(sys.argv[1], out.asnumpy())
"""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        outs = []
        for flag in ("0", "1"):
            path = os.path.join(d, f"o{flag}.npy")
            env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_PALLAS=flag,
                       PYTHONPATH=REPO)
            r = subprocess.run([sys.executable, "-c", script % REPO, path],
                               capture_output=True, text=True, env=env,
                               timeout=120)
            assert r.returncode == 0, r.stderr
            outs.append(np.load(path))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


def test_nms_kernel_matches_fallback(monkeypatch):
    rng = np.random.RandomState(2)
    B, A = 2, 32
    # random sorted-by-score rows with clustered boxes
    rows = np.zeros((B, A, 6), np.float32)
    for b in range(B):
        score = np.sort(rng.rand(A))[::-1]
        cls = rng.randint(0, 3, size=A).astype(np.float32)
        cls[score < 0.2] = -1.0
        centers = rng.rand(A, 2) * 0.6 + 0.2
        wh = rng.rand(A, 2) * 0.3 + 0.05
        rows[b, :, 0] = cls
        rows[b, :, 1] = score
        rows[b, :, 2:4] = centers - wh / 2
        rows[b, :, 4:6] = centers + wh / 2

    import jax.numpy as jnp

    monkeypatch.setenv("MXNET_PALLAS", "1")
    got = np.asarray(pk.nms(jnp.asarray(rows), 0.4, False))

    # python reference of the reference's greedy loop
    expect = rows.copy()
    for b in range(B):
        r = expect[b]
        for i in range(A):
            if r[i, 0] < 0:
                continue
            for j in range(i + 1, A):
                if r[j, 0] < 0 or r[j, 0] != r[i, 0]:
                    continue
                l = max(r[i, 2], r[j, 2]); t = max(r[i, 3], r[j, 3])
                rr = min(r[i, 4], r[j, 4]); bb = min(r[i, 5], r[j, 5])
                inter = max(rr - l, 0) * max(bb - t, 0)
                u = ((r[i, 4] - r[i, 2]) * (r[i, 5] - r[i, 3])
                     + (r[j, 4] - r[j, 2]) * (r[j, 5] - r[j, 3]) - inter)
                if u > 0 and inter / u >= 0.4:
                    r[j, 0] = -1.0
    np.testing.assert_allclose(got[:, :, 0], expect[:, :, 0])
    np.testing.assert_allclose(got[:, :, 1:], expect[:, :, 1:], rtol=1e-6)


def test_multibox_detection_pallas_parity():
    """MultiBoxDetection output identical with and without the kernel."""
    script = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import jax
import jax.numpy as jnp
import numpy as np
import mxnet_tpu as mx
rng = np.random.RandomState(3)
B, C, A = 2, 4, 24
anchors = np.zeros((1, A, 4), np.float32)
c = rng.rand(A, 2) * 0.6 + 0.2; wh = rng.rand(A, 2) * 0.2 + 0.1
anchors[0, :, :2] = c - wh / 2; anchors[0, :, 2:] = c + wh / 2
cls_prob = rng.rand(B, C, A).astype(np.float32)
cls_prob /= cls_prob.sum(axis=1, keepdims=True)
loc = (rng.rand(B, A * 4).astype(np.float32) - 0.5) * 0.1
out = mx.nd.MultiBoxDetection(mx.nd.array(cls_prob), mx.nd.array(loc),
                              mx.nd.array(anchors), nms_threshold="0.45")
np.save(sys.argv[1], out.asnumpy())
"""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        outs = []
        for flag in ("0", "1"):
            path = os.path.join(d, f"d{flag}.npy")
            env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_PALLAS=flag,
                       PYTHONPATH=REPO)
            r = subprocess.run([sys.executable, "-c", script % REPO, path],
                               capture_output=True, text=True, env=env,
                               timeout=120)
            assert r.returncode == 0, r.stderr
            outs.append(np.load(path))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_kernel_parity(monkeypatch, causal):
    """The Pallas flash kernel (interpret mode on CPU, native on TPU)
    matches the lax.scan blockwise formulation — outputs AND the
    un-normalized partial state used by ring attention, including a
    nonzero kv_offset (the ring's rotated-shard masking)."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import attention as A

    rng = np.random.RandomState(0)
    B, T, H, D = 2, 96, 3, 48
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    for koff in (0, -32):
        o1, m1, l1 = A._blockwise_attention_partial_lax(
            q, k, v, causal, 64, koff)
        o2, m2, l2 = A.blockwise_attention_partial(
            q, k, v, causal=causal, block_size=64, kv_offset=koff)
        out1 = A.normalize_attention_state(o1, m1, l1, q.dtype)
        out2 = A.normalize_attention_state(o2, m2, l2, q.dtype)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out1),
                                   rtol=1e-5, atol=1e-5)


def test_flash_attention_kernel_grad(monkeypatch):
    """custom_vjp backward (remat through lax.scan) equals the pure
    lax path's gradient."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import attention as A

    rng = np.random.RandomState(1)
    B, T, H, D = 1, 64, 2, 32
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))

    def loss_kernel(q, k, v):
        return A.blockwise_attention(q, k, v, causal=True,
                                     block_size=64).sum()

    def loss_lax(q, k, v):
        o, m, l = A._blockwise_attention_partial_lax(q, k, v, True, 64, 0)
        return A.normalize_attention_state(o, m, l, q.dtype).sum()

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_lax, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
