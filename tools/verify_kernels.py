#!/usr/bin/env python
"""On-hardware gradient-parity matrix for the attention kernels.

Round 5's fused-backward incident (PERF.md): a kernel passed a hardware
probe, interpret-mode parity, AND the benchmark shape, yet returned
~100% wrong dk at other grid shapes.  Interpret mode cannot catch
Mosaic-level races, so this tool exists: it sweeps the packed and
per-head flash kernels across a (T, block, causal, H) matrix ON THE
CHIP and compares forward + all input gradients against the lax
formulation.  Run it after ANY kernel change:

    python tools/verify_kernels.py          # full matrix (~5 min)
    python tools/verify_kernels.py --quick  # smoke subset
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

from mxnet_tpu.config import place_compile_cache

place_compile_cache()

import jax.numpy as jnp
import numpy as np

TOL = 2e-2  # bf16 end-to-end class


def _lax_packed(qkv, B, T, H, D, causal):
    from mxnet_tpu.ops import attention as att

    q, k, v = (jnp.reshape(y, (B, T, H, D)) for y in jnp.split(qkv, 3, -1))
    o, m, l = att._blockwise_attention_partial_lax(q, k, v, causal, 512, 0)
    return jnp.reshape(att.normalize_attention_state(o, m, l, qkv.dtype),
                       (B, T, H * D))


def check_packed(T, block, causal, H, B=2, D=64):
    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(B, T, 3 * H * D).astype(np.float32)
                      * 0.5).astype(jnp.bfloat16)
    HD = H * D

    def f_kern(x):
        return pk.flash_mha_packed(x, H, causal=causal, block_size=block)

    fwd_k = jax.jit(f_kern)(qkv).astype(jnp.float32)
    fwd_l = jax.jit(lambda x: _lax_packed(x, B, T, H, D, causal))(
        qkv).astype(jnp.float32)
    gk = jax.jit(jax.grad(lambda x: jnp.sum(
        f_kern(x).astype(jnp.float32))))(qkv).astype(jnp.float32)
    gl = jax.jit(jax.grad(lambda x: jnp.sum(
        _lax_packed(x, B, T, H, D, causal).astype(jnp.float32))))(
            qkv).astype(jnp.float32)
    errs = {"fwd": float(jnp.abs(fwd_k - fwd_l).max()
                         / jnp.maximum(jnp.abs(fwd_l).max(), 1e-9))}
    for name, s0 in (("dq", 0), ("dk", HD), ("dv", 2 * HD)):
        a, b = gk[:, :, s0:s0 + HD], gl[:, :, s0:s0 + HD]
        errs[name] = float(jnp.abs(a - b).max()
                           / jnp.maximum(jnp.abs(b).max(), 1e-9))
    ok = all(e < TOL for e in errs.values())
    print(f"{'OK ' if ok else 'FAIL'} packed T={T} block={block or 'auto'} "
          f"causal={causal} H={H}: "
          + " ".join(f"{k}={v:.4f}" for k, v in errs.items()), flush=True)
    return ok


def check_mha(T, block, causal, B=2, H=8, D=128):
    """The (BH, T, D) normalized kernel via blockwise_attention."""
    from mxnet_tpu.ops import attention as att

    rng = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)
                             * 0.5).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def f_kern(q, k, v):
        return att.blockwise_attention(q, k, v, causal=causal,
                                       block_size=block)

    def f_lax(q, k, v):
        o, m, l = att._blockwise_attention_partial_lax(q, k, v, causal,
                                                       512, 0)
        return att.normalize_attention_state(o, m, l, q.dtype)

    gk = jax.jit(jax.grad(lambda *a: jnp.sum(
        f_kern(*a).astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, v)
    gl = jax.jit(jax.grad(lambda *a: jnp.sum(
        f_lax(*a).astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, v)
    errs = {}
    for name, a, b in zip(("dq", "dk", "dv"), gk, gl):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        errs[name] = float(jnp.abs(a - b).max()
                           / jnp.maximum(jnp.abs(b).max(), 1e-9))
    ok = all(e < TOL for e in errs.values())
    print(f"{'OK ' if ok else 'FAIL'} mha    T={T} block={block or 'auto'} "
          f"causal={causal}: "
          + " ".join(f"{k}={v:.4f}" for k, v in errs.items()), flush=True)
    return ok


def main():
    quick = "--quick" in sys.argv
    results = []
    # packed: sweep revisit counts, block sizes, head counts, causality
    matrix = [(1024, 0, True, 12), (4096, 0, True, 12)] if quick else [
        (1024, 0, True, 12), (1024, 0, False, 12),
        (2048, 0, True, 12), (3072, 0, True, 12),
        (4096, 0, True, 12), (4096, 0, False, 12),
        (4096, 512, True, 12), (4096, 1024, True, 4),
        (1536, 512, True, 8),
    ]
    for T, block, causal, H in matrix:
        results.append(check_packed(T, block, causal, H))
    for T, block, causal in ([(4096, 0, True)] if quick else
                             [(1024, 0, True), (4096, 0, True),
                              (4096, 1024, False), (2048, 512, True)]):
        results.append(check_mha(T, block, causal))
    n_fail = results.count(False)
    print(f"\n{len(results) - n_fail}/{len(results)} kernel parity checks "
          f"passed")
    if n_fail:
        raise SystemExit(f"{n_fail} kernel parity checks FAILED")


if __name__ == "__main__":
    main()
