"""NDArray tests (modeled on tests/python/unittest/test_ndarray.py)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal, reldiff


def test_ndarray_creation():
    a = mx.nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert a.asnumpy().sum() == 0
    b = mx.nd.ones((2, 2), dtype=np.int32)
    assert b.dtype == np.int32
    assert b.asnumpy().sum() == 4
    c = mx.nd.full((2,), 7.5)
    assert c.asnumpy()[0] == 7.5
    d = mx.nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    e = mx.nd.arange(0, 10, 2)
    assert_almost_equal(e.asnumpy(), np.arange(0, 10, 2))


def test_ndarray_elementwise():
    rng = np.random.RandomState(0)
    for shape in [(4,), (3, 5), (2, 3, 4)]:
        a_np = rng.rand(*shape).astype(np.float32)
        b_np = rng.rand(*shape).astype(np.float32) + 0.1
        a, b = mx.nd.array(a_np), mx.nd.array(b_np)
        assert_almost_equal((a + b).asnumpy(), a_np + b_np, rtol=1e-5)
        assert_almost_equal((a - b).asnumpy(), a_np - b_np, rtol=1e-5)
        assert_almost_equal((a * b).asnumpy(), a_np * b_np, rtol=1e-5)
        assert_almost_equal((a / b).asnumpy(), a_np / b_np, rtol=1e-5)
        assert_almost_equal((a + 2).asnumpy(), a_np + 2, rtol=1e-5)
        assert_almost_equal((2 - a).asnumpy(), 2 - a_np, rtol=1e-5)
        assert_almost_equal((-a).asnumpy(), -a_np, rtol=1e-5)


def test_ndarray_inplace():
    a = mx.nd.ones((2, 2))
    a += 1
    assert (a.asnumpy() == 2).all()
    a *= 3
    assert (a.asnumpy() == 6).all()
    a[:] = 0.5
    assert (a.asnumpy() == 0.5).all()


def test_ndarray_setitem():
    a = mx.nd.zeros((3, 3))
    a[1] = 2.0
    expected = np.zeros((3, 3))
    expected[1] = 2.0
    assert_almost_equal(a.asnumpy(), expected)
    a[0, 2] = 5.0
    expected[0, 2] = 5.0
    assert_almost_equal(a.asnumpy(), expected)


def test_ndarray_slice_reshape():
    a_np = np.arange(24, dtype=np.float32).reshape(4, 6)
    a = mx.nd.array(a_np)
    assert_almost_equal(a.slice(1, 3).asnumpy(), a_np[1:3])
    assert_almost_equal(a[2].asnumpy(), a_np[2])
    assert_almost_equal(a.reshape((2, 12)).asnumpy(), a_np.reshape(2, 12))
    assert_almost_equal(a.reshape((-1, 4)).asnumpy(), a_np.reshape(-1, 4))
    assert_almost_equal(a.T.asnumpy(), a_np.T)


def test_ndarray_copy():
    a = mx.nd.ones((2, 2))
    b = mx.nd.zeros((2, 2))
    a.copyto(b)
    assert (b.asnumpy() == 1).all()
    c = a.copyto(mx.cpu(0))
    assert (c.asnumpy() == 1).all()
    d = a.as_in_context(mx.cpu(0))
    assert d.context.device_type == "cpu"


def test_ndarray_saveload():
    import tempfile, os

    rng = np.random.RandomState(0)
    arrays = [mx.nd.array(rng.rand(3, 4)), mx.nd.array(rng.rand(5))]
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "t.params")
        mx.nd.save(fname, arrays)
        loaded = mx.nd.load(fname)
        for a, b in zip(arrays, loaded):
            assert_almost_equal(a.asnumpy(), b.asnumpy())
        named = {"x": arrays[0], "y": arrays[1]}
        mx.nd.save(fname, named)
        loaded = mx.nd.load(fname)
        assert set(loaded) == {"x", "y"}
        assert_almost_equal(loaded["x"].asnumpy(), arrays[0].asnumpy())


def test_ndarray_functions():
    rng = np.random.RandomState(0)
    a_np = rng.rand(3, 4).astype(np.float32)
    a = mx.nd.array(a_np)
    assert_almost_equal(mx.nd.exp(a).asnumpy(), np.exp(a_np), rtol=1e-5)
    assert_almost_equal(mx.nd.square(a).asnumpy(), a_np ** 2, rtol=1e-5)
    assert_almost_equal(mx.nd.sum(a).asnumpy(), a_np.sum().reshape(1), rtol=1e-5)
    assert_almost_equal(mx.nd.sum(a, axis=0).asnumpy(), a_np.sum(0), rtol=1e-5)
    assert_almost_equal(mx.nd.max(a, axis=1).asnumpy(), a_np.max(1), rtol=1e-5)
    b_np = rng.rand(4, 5).astype(np.float32)
    assert_almost_equal(mx.nd.dot(a, mx.nd.array(b_np)).asnumpy(), a_np @ b_np, rtol=1e-4)
    assert_almost_equal(mx.nd.transpose(a).asnumpy(), a_np.T)
    assert_almost_equal(mx.nd.clip(a, a_min=0.2, a_max=0.8).asnumpy(),
                        np.clip(a_np, 0.2, 0.8), rtol=1e-6)


def test_ndarray_onehot():
    idx = mx.nd.array([0, 2, 1])
    out = mx.nd.zeros((3, 3))
    mx.nd.onehot_encode(idx, out)
    assert_almost_equal(out.asnumpy(), np.eye(3)[[0, 2, 1]])


def test_ndarray_astype_scalar():
    a = mx.nd.array([1.5])
    assert a.astype(np.int32).dtype == np.int32
    assert a.asscalar() == 1.5
    assert float(a.asscalar()) == 1.5


def test_ndarray_random():
    mx.random.seed(0)
    a = mx.nd.uniform(low=-1, high=1, shape=(100,))
    assert a.shape == (100,)
    assert -1 <= a.asnumpy().min() and a.asnumpy().max() < 1
    mx.random.seed(7)
    x = mx.nd.normal(loc=0, scale=1, shape=(50,)).asnumpy()
    mx.random.seed(7)
    y = mx.nd.normal(loc=0, scale=1, shape=(50,)).asnumpy()
    assert np.allclose(x, y)


def test_ndarray_waitall():
    a = mx.nd.ones((10, 10))
    b = a * 2
    mx.nd.waitall()
    b.wait_to_read()
    assert (b.asnumpy() == 2).all()


def test_gather_global_local_fast_paths():
    """gather_global: the explicit bulk-synchronous collective that
    asnumpy() refuses to hide.  Single-process arrays are fully
    addressable, so both fast paths must return without communication."""
    a = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    np.testing.assert_array_equal(mx.nd.gather_global(a), a.asnumpy())
    np.testing.assert_array_equal(mx.nd.gather_global(np.ones(3)),
                                  np.ones(3))


def test_accelerator_context_never_falls_back():
    """mx.tpu(i) / mx.gpu(i) with no such accelerator is an error —
    never a CPU device, never another chip (ordinals do not wrap) —
    and num_devices() counts 0 accelerators where there are none, so
    `mx.tpu() if mx.context.num_devices() else mx.cpu()` means what it
    says.  cpu ids stay logical and wrap over the host devices."""
    import jax

    assert jax.default_backend() == "cpu"  # the suite's platform
    assert mx.context.num_devices("tpu") == 0
    for ctx in (mx.tpu(), mx.tpu(5), mx.gpu(0)):
        with pytest.raises(mx.MXNetError, match="accelerator"):
            ctx.jax_device()
    with pytest.raises(mx.MXNetError, match="accelerator"):
        mx.nd.zeros((2,), ctx=mx.tpu())
    n = mx.context.num_devices("cpu")
    assert n >= 1
    assert mx.cpu(n + 1).jax_device() == mx.cpu(1 % n).jax_device()
