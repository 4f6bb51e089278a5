"""``KDAChunk`` (``ops/hybrid.py``) against the recurrence as written,
both bodies: the lax scan and the three chunk-form Pallas kernels
interpreted on the CPU.  A file of its own: the interpreted cases are the
longest of the hybrid family's ops and would hold ``test_hybrid_ops.py``'s
worker for the whole run."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ops import hybrid  # noqa: E402
from mxnet_tpu.ops import pallas_hybrid as ph  # noqa: E402

from _engines import run_op  # noqa: E402

# Lowering an interpreted kernel takes ~5 s whatever its size, and the
# cases differ in T, n and the gate draw, not in program: the op pads T
# to whole tiles of 128 before its kernels, so 22 cases make four shapes
# of each.  A kernel is jitted once for each (body, static arguments,
# grid, operand shapes) and the cases that share it call it; the op
# around it (the padding, the gates, the slot's write) runs as it is.
_BUILT = {}
_pallas_call = ph.pl.pallas_call


def _built_once(kernel, **kw):
    call = _pallas_call(kernel, **kw)

    def run(*args):
        key = (kw["name"], tuple(sorted(getattr(kernel, "keywords",
                                                {}).items())),
               kw.get("grid"), tuple((a.shape, str(a.dtype)) for a in args))
        if key not in _BUILT:
            _BUILT[key] = jax.jit(call)
        return _BUILT[key](*args)

    return run


@pytest.fixture
def kernels(kernels, monkeypatch):
    """conftest's two bodies, the interpreted kernels built once a
    shape."""
    monkeypatch.setattr(ph.pl, "pallas_call", _built_once)
    return kernels


def kda_plain(q, k, v, alpha, beta):
    """S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T; o_t = S_t^T q_t."""
    T, H, D = q.shape
    S = np.zeros((H, D, D))
    out = np.zeros((T, H, D))
    eye = np.eye(D)
    for t in range(T):
        for h in range(H):
            kk = k[t, h][:, None]
            S[h] = (eye - beta[t, h] * kk @ kk.T) @ (alpha[t, h][:, None]
                                                     * S[h]) \
                + beta[t, h] * kk @ v[t, h][None, :]
            out[t, h] = S[h].T @ q[t, h]
    return out, S


def _draw(rng, T, H, D):
    """The raw gate projections as the initialisation draws them."""
    return dict(
        decay=rng.standard_normal((1, T, H * D)),
        braw=2.0 + rng.standard_normal((1, T, H)),
        a_log=np.log(rng.uniform(1, 4, H)),
        dt=rng.standard_normal(H * D))


def _strongest(rng, T, H, D):
    """exp(A) = 16 and softplus arguments up to +8: g down to -128 a
    token, alpha underflows to 0 in float32."""
    return dict(_draw(rng, T, H, D), a_log=np.full(H, np.log(16.0)),
                decay=rng.uniform(-2, 8, (1, T, H * D)), dt=np.zeros(H * D))


def _no_decay(rng, T, H, D):
    """softplus(-200) is 0 in float32: alpha = 1 everywhere."""
    return dict(_draw(rng, T, H, D), decay=np.full((1, T, H * D), -200.0),
                dt=np.zeros(H * D))


def _beta_ends(rng, T, H, D):
    """beta = 2 sigmoid(+-12): both ends of (0, 2), token by token."""
    return dict(_draw(rng, T, H, D),
                braw=12.0 * rng.choice([-1.0, 1.0], (1, T, H)))


def _slow_beside_fast(rng, T, H, D):
    """Even channels of every head hardly decay (g ~ -1e-4), odd ones
    lose everything in a token (g ~ -128)."""
    lane = np.where(np.arange(H * D) % 2 == 0, -10.0, 8.0)
    return dict(_draw(rng, T, H, D), a_log=np.full(H, np.log(16.0)),
                decay=np.broadcast_to(lane, (1, T, H * D)),
                dt=np.zeros(H * D))


# (T, n, H, D, gates): the first is the old body's test and is also fed
# token by token; the rest cross the chunk form's boundaries (a
# sub-block of 16, a chunk of 64, a tile of 128), padded (n < T) and
# not, and the decays that form can break on
_KDA_CASES = [(12, 9, 4, 8, _draw)] + [
    (T, n, 4, 8, _draw)
    for n, T in [(1, 1), (1, 15), (15, 15), (15, 16), (16, 16), (16, 17),
                 (17, 17), (17, 63), (63, 63), (63, 64), (64, 64),
                 (64, 65), (65, 65), (65, 200), (200, 200)]] + [
    (200, 137, 2, 128, _draw),
    (200, 200, 4, 8, _strongest), (65, 63, 2, 128, _strongest),
    (200, 137, 4, 8, _no_decay), (200, 200, 4, 8, _beta_ends),
    (200, 137, 4, 8, _slow_beside_fast)]


@pytest.mark.parametrize(
    "T, n, H, D, gates", _KDA_CASES,
    ids=[f"T{T}-n{n}-H{H}-D{D}-{g.__name__.strip('_')}"
         for T, n, H, D, g in _KDA_CASES])
def test_kda_chunk_is_kda_step_token_by_token_is_the_recurrence(
        kernels, T, n, H, D, gates):
    """KDAChunk (both bodies: the lax scan and the chunk-form kernels)
    against the recurrence as written, in float64, at every live
    position and in the slot; no further from it than 4 x what the
    float32 scan itself is (at least 4 float32 roundings of the largest
    number compared)."""
    rng = np.random.default_rng(0)
    f32 = lambda x: np.asarray(x, np.float32)
    c = f32(rng.standard_normal((1, T, 3 * H * D)))
    raw = {k: f32(x) for k, x in gates(rng, T, H, D).items()}
    decay, braw, a_log, dt = (raw[k] for k in
                              ("decay", "braw", "a_log", "dt"))
    pool = f32(rng.standard_normal((3, H, D, D)))  # dirty
    attrs = dict(num_heads=H, neg_eigval=True)

    o_chunk, pool_c = run_op(
        "KDAChunk", [c, decay, braw, a_log, dt, pool, [2], [n]], **attrs)
    q, k, v = (np.asarray(x)[0] for x in hybrid.kda_qkv(jnp.asarray(c), H))
    alpha, beta, g = (np.asarray(x)[0] for x in hybrid.kda_gates(
        jnp.asarray(decay), jnp.asarray(braw), jnp.asarray(a_log),
        jnp.asarray(dt), H, True))
    assert beta.max() > 1.0 and beta.min() > 0.0   # negative eigenvalues
    if gates is _strongest:
        assert g.min() < -120 and alpha.min() == 0.0
    if gates is _no_decay:
        assert alpha.min() == 1.0
    want, S = kda_plain(*(x[:n].astype(np.float64) for x in (q, k, v)),
                        np.exp(g[:n].astype(np.float64)),
                        beta[:n].astype(np.float64))
    o_scan, s_scan = hybrid.kda_scan(
        *(jnp.asarray(x[None, :n]) for x in (q, k, v, alpha, beta)),
        jnp.zeros((1, H, D, D), jnp.float32))

    def close(got, ref, scan):
        tol = 4 * max(np.abs(np.asarray(scan, np.float64) - ref).max(),
                      np.finfo(np.float32).eps * np.abs(ref).max())
        got = np.asarray(got, np.float64)
        assert np.isfinite(got).all()
        assert np.abs(got - ref).max() <= tol, (np.abs(got - ref).max(),
                                                tol)

    close(np.asarray(o_chunk)[0, :n], want.reshape(n, -1),
          np.asarray(o_scan)[0].reshape(n, -1))
    # the pools hold a head's state transposed, (d_v, d_k)
    close(np.asarray(pool_c)[2], S.transpose(0, 2, 1), s_scan[0])
    # slots nobody named are untouched
    np.testing.assert_array_equal(np.asarray(pool_c)[:2], pool[:2])
    if (T, n) != _KDA_CASES[0][:2]:
        return
    # the old body's case, fed to KDAStep token by token as well; the
    # slot was dirty and is overwritten: step by step from zero
    pool_s = jnp.asarray(pool).at[1].set(0.0)
    o_step = []
    for t in range(n):
        o, pool_s = run_op(
            "KDAStep", [c[:, t:t + 1], decay[:, t:t + 1], braw[:, t:t + 1],
                        a_log, dt, pool_s, [1], [t + 1]], **attrs)
        o_step.append(np.asarray(o)[0, 0])
    np.testing.assert_allclose(np.stack(o_step), want.reshape(n, -1),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(pool_s)[1],
                               S.transpose(0, 2, 1), atol=2e-5)
