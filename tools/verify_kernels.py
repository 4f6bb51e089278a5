#!/usr/bin/env python
"""On-hardware gradient-parity matrix for the attention kernels.

Round 5's fused-backward incident (PERF.md): a kernel passed a hardware
probe, interpret-mode parity, AND the benchmark shape, yet returned
~100% wrong dk at other grid shapes.  Interpret mode cannot catch
Mosaic-level races, so this tool exists: it sweeps the packed and
per-head flash kernels across a (T, tiles, causal, H) matrix ON THE
CHIP — the packed ones at the benchmark cells' own shapes too, with the
ms a call of each kernel — and compares forward + all input gradients
against the lax
formulation, and the paged kernel — decode, verify window, int8 pools
— over lane-dense (P, KVB, H·D) pools against the lax gather.  Run it
after ANY kernel change:

    python tools/verify_kernels.py          # full matrix (~5 min)
    python tools/verify_kernels.py --quick  # smoke subset
    python tools/verify_kernels.py --paged  # the paged kernel alone
    python tools/verify_kernels.py --mamba2 # the Mamba-2 kernels alone
    python tools/verify_kernels.py --retention # the power-retention
                                            # kernels at the gen cell's
                                            # shapes against their lax bodies
    python tools/verify_kernels.py --window # the sliding-window kernels
                                            # (prefill band, decode walk)
    python tools/verify_kernels.py --longdoc # the four attention kernels
                                            # at the longdoc cell's shapes:
                                            # 48 / 8 heads x 128, T = 32,768,
                                            # a 2,080-page table
    python tools/verify_kernels.py --packed # the packed flash kernels alone
    python tools/verify_kernels.py --tiles  # the packed kernels' tile
                                            # schedules at the cells'
                                            # shapes, ms a call each
    python tools/verify_kernels.py --gqa-tiles # the grouped-query prompt
                                            # kernel's tiles and sub-blocks
                                            # at the longdoc and mixed
                                            # cells' shapes, ms a call each
    python tools/verify_kernels.py --pages  # a prefill's K/V write: the
                                            # page kernel against the row
                                            # scatter at the cells' shapes
    python tools/verify_kernels.py --moe    # the experts' dispatch round
                                            # at the four expert cells'
                                            # shapes: the gathered pieces
                                            # beside the ones by index
    python tools/verify_kernels.py --mla    # the latent-attention kernels
                                            # (prefill, paged decode, page
                                            # write) at the longctx cell's
                                            # widths, ms a call each; the
                                            # prefill kernel's chosen tiles
                                            # and share of its roofline
    python tools/verify_kernels.py --mla-tiles --fill 1,0.6,0.75
                                            # the latent prefill kernel's
                                            # tiles, sub-blocks and heads a
                                            # step at the longctx cell's
                                            # four buckets, ms a call each
    python tools/verify_kernels.py --longdoc --window --mla --fill 1,0.6
                                            # the prompt kernels alone at
                                            # a prompt of F x T rows in
                                            # each bucket of T (F = 1,
                                            # then 0.6), given the length
                                            # and not
"""

import functools
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


@functools.lru_cache(maxsize=2)
def _packed_case(B, T, H, D, causal, grad):
    """One shape's input and the lax body's answers, made once however
    many tile schedules are held against them."""
    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(B, T, 3 * H * D).astype(np.float32)
                      * 0.5).astype(jnp.bfloat16)
    fwd = jax.jit(lambda x: _lax_packed(x, B, T, H, D, causal))(
        qkv).astype(jnp.float32)
    g = jax.jit(jax.grad(lambda x: jnp.sum(
        _lax_packed(x, B, T, H, D, causal).astype(jnp.float32))))(
            qkv).astype(jnp.float32) if grad else None
    return qkv, fwd, g


def _kernel_ms(fn, x, n=10):
    """Device ms a call of each Mosaic kernel ``fn(x)`` runs, by the
    ``name=`` it carries, from a profiler trace of n calls read with the
    benchmark's own reader; {} where the trace has no device plane."""
    import shutil
    import tempfile

    from benchmark.trace_reduce import KERNEL_TAG, Trace

    jax.block_until_ready(fn(x))
    d = tempfile.mkdtemp(prefix="verify_kernels_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(n):
            out = fn(x)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        out = {}
        for name, sec in Trace.from_dir(d).op_seconds().items():
            if KERNEL_TAG in name:
                key = name.split(KERNEL_TAG)[0].strip("%_ ").split(".")[0]
                out[key] = out.get(key, 0.0) + 1e3 * sec / n
        return out
    except (Exception, SystemExit) as e:  # noqa: BLE001 — timing only
        print(f"   (no device trace: {e})", flush=True)
        return {}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def check_packed(T, tiles, causal, H, B=2, D=64, grad=True):
    """The packed kernels at one shape under one tile schedule —
    ``tiles`` = (block_q, block_k, sub[, lanes]), None for the one
    ``pk._mhap_tiles`` picks — against the lax body; beside the verdict
    the ms a call: host clock of the forward and of forward + backward,
    and each kernel's own device time from a trace."""
    from mxnet_tpu.ops import pallas_kernels as pk

    qkv, fwd_l, gl = _packed_case(B, T, H, D, causal, grad)
    HD = H * D
    chooser = pk._mhap_tiles
    if tiles is not None:
        if len(tiles) == 3:  # the chosen head group
            tiles = (*tiles, chooser(T, HD, D)[3])
        pk._mhap_tiles = lambda t, hd, d: tiles
    try:
        f_kern = jax.jit(lambda x: pk.flash_mha_packed(x, H, causal=causal))
        g_kern = jax.jit(jax.grad(lambda x: jnp.sum(
            pk.flash_mha_packed(x, H, causal=causal).astype(jnp.float32))))
        errs = {"fwd": float(jnp.abs(f_kern(qkv).astype(jnp.float32) - fwd_l)
                             .max() / jnp.maximum(jnp.abs(fwd_l).max(), 1e-9))}
        ms = {"fwd": _time_ms(f_kern, qkv, n=10)}
        if grad:
            gk = g_kern(qkv).astype(jnp.float32)
            for name, s0 in (("dq", 0), ("dk", HD), ("dv", 2 * HD)):
                a, b = gk[:, :, s0:s0 + HD], gl[:, :, s0:s0 + HD]
                errs[name] = float(jnp.abs(a - b).max()
                                   / jnp.maximum(jnp.abs(b).max(), 1e-9))
            ms["fwd+bwd"] = _time_ms(g_kern, qkv, n=10)
        ms.update(_kernel_ms(g_kern if grad else f_kern, qkv))
    finally:
        pk._mhap_tiles = chooser
    bq, bk, sub, lanes = tiles or chooser(T, HD, D)
    done, needed = pk._mhap_scores(T, bq, bk, sub, causal)
    ok = all(e < TOL for e in errs.values())
    print(f"{'OK ' if ok else 'FAIL'} packed ({B}, {T}, {3 * HD}) H={H} "
          f"causal={causal} tiles={bq}x{bk}/{sub} lanes={lanes}"
          f"{'' if tiles else ' (chosen)'} scores x{done / needed:.3f}: "
          + " ".join(f"{k}={v:.4f}" for k, v in errs.items()) + " | ms: "
          + " ".join(f"{k}={v:.3f}" for k, v in ms.items()), flush=True)
    return ok


# the packed kernels' own cells — gpt2-large's 1,024 prefill (forward
# only), gpt2-medium's training step — and the tools' benches' T = 4096
CELL_SHAPES = (((1, 1024, 20), False), ((8, 1024, 16), True),
               ((4, 4096, 12), True))


def sweep_tiles():
    """The kernel-alone table of PERF.md (PR 32): every tile schedule
    worth holding against the chosen one, at the cells' shapes.  A
    schedule (q, q, q) masks its whole diagonal tile, as the kernels did
    before they walked it; 128 is what a 16-token page once gave the
    1,024 prefill; a fourth number is the lanes of a grid step's head
    group (all of H·D: every head unrolled in one step, as before)."""
    results = []
    for (B, T, H), grad in CELL_SHAPES:
        hd = H * 64
        cands = [None, (512, 512, 256), (512, 512, 512), (1024, 1024, 128),
                 (1024, 1024, 256, 256), (1024, 1024, 256, hd)]
        if T == 1024:
            cands += [(1024, 1024, 512), (256, 256, 256), (128, 128, 128)]
        else:
            cands += [(512, 512, 128), (1024, 1024, 256),
                      (1024, 1024, 1024), (512, 1024, 256),
                      (512, 2048, 256)]
        for tiles in cands:
            try:
                results.append(check_packed(T, tiles, True, H, B=B,
                                            grad=grad))
            except Exception as e:  # noqa: BLE001 — the compiler's refusal
                print(f"FAIL packed ({B}, {T}) H={H} tiles={tiles}: "
                      f"{type(e).__name__}: {str(e)[:300]}", flush=True)
                results.append(False)
    return results


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


def check_paged(H, D, W, kv_dtype, B=8, MB=64, KVB=16, Hq=None, window=0,
                rows=None):
    """The paged kernel over (P, KVB, H·D) pools (``kv_cache.
    value_pool_shape``) against a lax gather of the pages and the
    fallbacks' blockwise body: W = 1 is the decode step, W > 1 a
    verify window, ``int8`` the quantized pools.  Streams of every
    length from one token to a full table — three of them ending one
    key short of the kernel's first chunk, on it and one past it —
    pages handed out in a shuffled order, one idle row.  ``Hq``:
    grouped queries, that many query heads over the H KV heads.
    ``window`` (W = 1): a sliding window of that many keys; the table
    then names the scratch page for every page wholly behind a row's
    window, as the engine's does once it has given them back.  ``rows``:
    the streams the lax body is computed for (all of them where None;
    a few where the gathered and repeated K/V of all would not fit)."""
    Hq = Hq or H
    from mxnet_tpu.kv_cache import value_pool_shape
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(H * 1000 + W)
    P = B * MB + 1
    shape = value_pool_shape(P, KVB, H, D)
    pools = tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5)
        .astype(jnp.bfloat16) for _ in range(2))
    if kv_dtype == "int8":
        kq, ks = att._quantize_rows(pools[0], H, jnp.int8)
        vq, vs = att._quantize_rows(pools[1], H, jnp.int8)
        pools = (kq, vq, ks, vs)
    table = jnp.asarray((1 + rng.permutation(P - 1))
                        .reshape(B, MB).astype(np.int32))
    # tokens cached before the window: row 0 none, the last row all
    # but the window, row 1 idle (a decode step's lengths == 0)
    start = np.linspace(0, MB * KVB - W, B).astype(np.int32)
    chunk = KVB * pk._paged_pages_per_chunk(
        W, Hq, H, D, KVB, MB, 2, 1 if kv_dtype == "int8" else 2,
        kv_dtype == "int8")[1]
    if chunk + 1 <= MB * KVB:
        start[2:5] = np.arange(chunk - 1, chunk + 2) - W
    if W == 1:
        start[1] = -1
    if window:
        first = np.maximum(start + 1 - window, 0) // KVB
        table = jnp.where(jnp.arange(MB)[None, :] < first[:, None], 0,
                          table)
    start = jnp.asarray(start)
    q = jnp.asarray(rng.randn(B, W, Hq * D).astype(np.float32)
                    * 0.5).astype(jnp.bfloat16)
    scales = pools[2:]
    kernel = jax.jit(lambda q, t, s, *p: pk._paged_attention(
        q, p[0], p[1], p[2:], t, s, Hq, kv_heads=H, window=window))
    if H * D % 128 and not pk._interpret():
        # compiled, the kernel copies page rows in whole lane tiles and
        # refuses another width by name (ops.attention's lax body
        # serves it: pk.paged_enabled)
        from mxnet_tpu.base import MXNetError
        try:
            kernel(q, table, start, *pools)
            ok = False
        except MXNetError as e:
            ok = "whole lane tiles" in str(e)
        print(f"{'OK ' if ok else 'FAIL'} paged  H={Hq}/{H} D={D} W={W}: "
              f"{H * D} lanes refused by name", flush=True)
        return ok
    got = kernel(q, table, start, *pools)

    def gather_and_attend(q, t, s, *p):
        # the gathered ROWS take the head dim, never a pool
        n = q.shape[0]
        kg, vg = (x[t].reshape(n, MB * KVB, H, D) for x in p[:2])
        if scales:
            kg = att.dequantize_kv(kg, p[2][t].reshape(n, MB * KVB, H))
            vg = att.dequantize_kv(vg, p[3][t].reshape(n, MB * KVB, H))
        # query head i reads KV head i // (Hq / H)
        kg, vg = (jnp.repeat(x, Hq // H, axis=2) for x in (kg, vg))
        o, m, l = att._blockwise_attention_partial_lax(
            q.reshape(n, W, Hq, D), kg, vg, False, KVB, 0,
            lengths=s + 1, diagonal=not window, window=window)
        return att.normalize_attention_state(o, m, l, q.dtype).reshape(
            n, W, Hq * D)

    at = np.arange(B) if rows is None else np.asarray(rows)
    want = jax.jit(gather_and_attend)(q[at], table[at], start[at], *pools)
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    idle = got[np.asarray(start) < 0]
    got, live = got[at], np.asarray(start)[at] >= 0
    err = float(np.abs(got[live] - want[live]).max()
                / max(np.abs(want[live]).max(), 1e-9))
    ok = err < TOL and bool(np.isfinite(got).all()) \
        and not np.abs(idle).any()
    ms = _kernel_ms(lambda x: kernel(x, table, start, *pools), q) \
        if window or rows is not None else {}
    print(f"{'OK ' if ok else 'FAIL'} paged  H={Hq}/{H} D={D} W={W} "
          f"B={B} MB={MB} chunk={chunk} "
          f"pools={kv_dtype}{' +scales' if scales else ''}"
          f"{f' window={window}' if window else ''}"
          f"{f' rows={[int(r) for r in at]}' if rows is not None else ''}: "
          f"fwd={err:.4f}"
          + "".join(f" {k}={v:.3f}ms" for k, v in ms.items()), flush=True)
    return ok


def check_window_flash(T, window, Hq=28, Hkv=4, D=128):
    """The windowed prefill kernel (one prompt, ``Hq`` query heads over
    ``Hkv`` KV heads) against the lax body under the same band — and
    the global layers' kernel (``window`` 0 of the same call: every key
    up to the query, ``flash_fwd_mha``) against the causal lax body —
    with both kernels' ms a call.  The lax body goes a KV head's query
    heads at a time: the scores of all of them would not fit at
    T = 32,768."""
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(T + window)
    q, k, v = (jnp.asarray(rng.randn(1, T, n, D).astype(np.float32) * 0.5)
               .astype(jnp.bfloat16) for n in (Hq, Hkv, Hkv))

    def heads_first(x):
        return x[0].transpose(1, 0, 2)

    kern = jax.jit(lambda q, k, v: pk.flash_mha_window(
        heads_first(q), heads_first(k), heads_first(v), window, Hq, Hkv))
    full = jax.jit(lambda q, k, v: pk.flash_mha_window(
        heads_first(q), heads_first(k), heads_first(v), 0, Hq, Hkv))
    G = Hq // Hkv

    def lax_body(q, k, v, window):
        def group(xs):                  # a KV head and its G query heads
            qg, kg, vg = xs             # (T, G, D), (T, D), (T, D)
            o, m, l = att._blockwise_attention_partial_lax(
                qg[None], jnp.repeat(kg[None, :, None], G, 2),
                jnp.repeat(vg[None, :, None], G, 2), True, 512, 0,
                window=window)
            return att.normalize_attention_state(o, m, l, q.dtype)[0]

        out = jax.lax.map(group, (
            q[0].reshape(T, Hkv, G, D).transpose(1, 0, 2, 3),
            k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2)))
        return out.transpose(1, 0, 2, 3).reshape(T, Hq, D)

    errs, ok = [], True
    for fn, w in ((kern, window), (full, 0)):
        got = np.asarray(fn(q, k, v).astype(jnp.float32)).transpose(1, 0, 2)
        want = np.asarray(jax.jit(lax_body, static_argnums=3)(
            q, k, v, w).astype(jnp.float32))
        errs.append(float(np.abs(got - want).max()
                          / max(np.abs(want).max(), 1e-9)))
        ok = ok and errs[-1] < TOL and bool(np.isfinite(got).all())
    err = errs[0]
    ms = _kernel_ms(lambda x: kern(x, k, v), q, n=3)
    ms.update(_kernel_ms(lambda x: full(x, k, v), q, n=3))
    print(f"{'OK ' if ok else 'FAIL'} window T={T} window={window} "
          f"H={Hq}/{Hkv} D={D}: fwd={err:.4f} global={errs[1]:.4f}"
          + "".join(f" {k}={v:.3f}ms" for k, v in ms.items()), flush=True)
    return ok


def _gqa_schedule_note(n, T, window, latent=False):
    """The schedule's own counts for a prompt of n rows in a bucket of
    T (host arithmetic, ``pk.prompt_tile_work``; ``latent``: the latent
    prompt kernel's): the share of the walked tiles that took a masked
    body, and the scores computed over the pairs the band holds."""
    from mxnet_tpu.ops import pallas_kernels as pk

    walked, _ = pk.prompt_tile_visits(n, T, window, latent)
    masked, done, needed = pk.prompt_tile_work(n, T, window, latent)
    return (f"masked={masked}/{walked} tiles "
            f"scores x{done / max(needed, 1):.3f}")


# (query heads, KV heads, T): the longdoc cell's buckets, then mixed's
GQA_SHAPES = ((48, 8, 32768), (48, 8, 8192), (48, 8, 4096),
              (28, 4, 8192), (28, 4, 2048), (28, 4, 1024))


def sweep_gqa_tiles(D=128, window=4096):
    """The kernel-alone table of PERF.md (PR 43): ``flash_mha_window``
    under every schedule (block_q, block_k, sub, inner) worth holding
    against the one ``pk._mha_window_tiles`` picks, windowed and global,
    at the longdoc and mixed cells' shapes — ms a call beside the least
    time of the real pairs.  A schedule whose sub-block is the tile
    masks its edge tiles whole; ``inner`` is the rows of an interior
    tile updated at a time.  Every schedule's output is held against
    the chosen one's."""
    from mxnet_tpu.ops import pallas_kernels as pk

    results = []
    chooser = pk._mha_window_tiles
    for Hq, Hkv, T in GQA_SHAPES:
        rng = np.random.RandomState(T + Hq)
        q, k, v = (jnp.asarray(rng.randn(n, T, D).astype(np.float32) * 0.5)
                   .astype(jnp.bfloat16) for n in (Hq, Hkv, Hkv))
        one = min(1024, T)
        cands = [None, (one, one, one, one), (one, one, 256, one),
                 (one, one, 128, one), (512, 512, 256, 512)]
        if T >= 4096:
            cands += [(1024, 2048, 1024, 512), (1024, 2048, 256, 1024),
                      (1024, 2048, 128, 512), (2048, 2048, 256, 512),
                      (512, 2048, 256, 512), (1024, 512, 256, 512)]
        for w in (window, 0):
            name = "flash_fwd_window" if w else "flash_fwd_mha"
            want = None
            for tiles in dict.fromkeys(cands):
                if tiles is not None:
                    pk._mha_window_tiles = lambda t, w_, tiles=tiles: tiles
                try:
                    fn = jax.jit(lambda q, k, v, w=w: pk.flash_mha_window(
                        q, k, v, w, Hq, Hkv))
                    got = np.asarray(fn(q, k, v).astype(jnp.float32))
                    want = got if want is None else want
                    err = float(np.abs(got - want).max()
                                / max(np.abs(want).max(), 1e-9))
                    ms = _named_ms(lambda x: fn(x, k, v), q, name)
                    note = _gqa_schedule_note(T, T, w)
                    bq, bk, sub, inner = tiles or chooser(T, w)
                except Exception as e:  # noqa: BLE001 — the compiler's no
                    print(f"FAIL gqa-tiles {name} H={Hq}/{Hkv} T={T} "
                          f"tiles={tiles}: {type(e).__name__}: "
                          f"{str(e)[:300]}", flush=True)
                    results.append(False)
                    continue
                finally:
                    pk._mha_window_tiles = chooser
                least = 4.0 * Hq * D * _band_pairs(T, w) / 197e12 * 1e3
                ok = err < TOL and bool(np.isfinite(got).all())
                results.append(ok)
                print(f"{'OK ' if ok else 'FAIL'} gqa-tiles {name} "
                      f"H={Hq}/{Hkv} T={T} tiles={bq}x{bk}/{sub}/{inner}"
                      f"{'' if tiles else ' (chosen)'}: {ms:.3f}ms "
                      f"least={least:.3f}ms "
                      f"roofline={100 * least / ms if ms else 0:.1f}% "
                      f"{note} gap={err:.4f}", flush=True)
    return results


def check_mla_flash(T, H=128, n=128, r=64, dv=128, scale=0.13523):
    """The latent prefill kernel (one prompt, ``H`` heads of qk width
    n + r and v width dv, one rotary key for all) against the lax body
    a block of queries at a time, with the kernel's ms a call."""
    from mxnet_tpu.ops import hybrid as hy
    from mxnet_tpu.ops import pallas_kernels as pk

    q, q_r, kv, k_r = _mla_inputs(T, H, n, r, dv)
    assert pk.mla_flash_enabled(H, n, r, dv)
    kern = jax.jit(lambda q, q_r, kv, k_r: pk.mla_flash(
        q, q_r, kv, k_r, H, n, dv, scale))
    lax_body = jax.jit(lambda q, q_r, kv, k_r: hy.mla_causal(
        q[..., :H * n], q_r, kv[..., :H * n], k_r, kv[..., H * n:], H,
        scale, block=128))
    got = np.asarray(kern(q, q_r, kv, k_r).astype(jnp.float32))
    want = np.asarray(lax_body(q, q_r, kv, k_r).astype(jnp.float32))
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9))
    ok = err < TOL and bool(np.isfinite(got).all())
    ms = _kernel_ms(lambda x: kern(x, q_r, kv, k_r), q, n=3)
    least = _mla_flops(T, H, n, r, dv) / 197e12 * 1e3
    print(f"{'OK ' if ok else 'FAIL'} mla_flash T={T} H={H} "
          f"qk={n}+{r} v={dv} tiles={_mla_tiles_note(T, H, n, r, dv)}: "
          f"fwd={err:.4f} least={least:.3f}ms"
          + "".join(f" {k}={v:.3f}ms roofline={100 * least / v:.1f}%"
                    for k, v in ms.items()), flush=True)
    return ok


def _mla_flops(rows, H, n, r, dv):
    """The FLOP of a prompt of ``rows`` rows' causal pairs in the latent
    prefill kernel: what its roofline is counted from."""
    return 2.0 * H * (n + r + dv) * rows * (rows + 1) / 2


def _mla_tiles_note(T, H, n, r, dv):
    from mxnet_tpu.ops import pallas_kernels as pk

    bq, bk, sub, inner, hb = pk._mla_tiles(T, H, n, r, dv)
    return f"{bq}x{bk}/{sub}/{inner}/h{hb}"


def _mla_inputs(T, H, n, r, dv):
    rng = np.random.RandomState(T)

    def arr(lanes, s=0.5):
        return jnp.asarray(rng.randn(1, T, lanes).astype(np.float32)
                           * s).astype(jnp.bfloat16)

    return arr(H * (n + r)), arr(H * r), arr(H * (n + dv)), arr(r, 1.5)


# the longctx cell's four prefill buckets
MLA_BUCKETS = (8192, 4096, 2048, 1024)
# (block_q, block_k, sub, inner, heads a step) held against the chosen
# one; the first is the parent's walk (square tiles of 512 masked whole)
MLA_TILE_CANDIDATES = (
    (512, 512, 512, 512, 4), (512, 512, 256, 512, 4),
    (512, 1024, 256, 512, 4), (512, 2048, 256, 512, 4),
    (512, 2048, 256, 256, 4), (1024, 512, 256, 512, 4),
    (1024, 1024, 256, 512, 4), (1024, 1024, 512, 512, 4),
    (1024, 1024, 256, 256, 4), (1024, 1024, 256, 1024, 4),
    (1024, 1024, 256, 512, 2), (1024, 2048, 256, 512, 4),
    (1024, 2048, 512, 512, 4), (1024, 2048, 256, 256, 4),
    (1024, 2048, 256, 1024, 4), (1024, 2048, 256, 256, 2),
    (1024, 2048, 256, 256, 8), (2048, 2048, 256, 256, 4),
    (2048, 2048, 256, 512, 4), (1024, 4096, 256, 256, 4))


def sweep_mla_tiles(fills, H=128, n=128, r=64, dv=128, scale=0.13523):
    """The kernel-alone table of PERF.md (PR 47): ``mla_flash`` under
    every schedule (block_q, block_k, sub, inner, heads a step) worth
    holding against the one ``pk._mla_tiles`` picks, at the longctx
    cell's four buckets and a prompt of F x T rows for each F of
    ``--fill`` (1: the whole bucket) — ms a call beside the least time
    of the prompt's real pairs.  A schedule whose sub-block is the tile
    masks its diagonal tiles whole (the parent's walk at 512 x 512).
    Every schedule's output is held against the chosen one's."""
    from mxnet_tpu.ops import pallas_kernels as pk

    results = []
    chooser = pk._mla_tiles
    name = "mla_flash_fwd"
    for T in MLA_BUCKETS:
        q, q_r, kv, k_r = _mla_inputs(T, H, n, r, dv)
        cands = [None] + [
            tiles for tiles in MLA_TILE_CANDIDATES
            if max(tiles[:2]) <= T and tiles != chooser(T, H, n, r, dv)]
        want = None
        for tiles in cands:
            if tiles is not None:
                pk._mla_tiles = lambda *a, tiles=tiles: tiles
            try:
                fn = jax.jit(lambda q, q_r, kv, k_r, lens: pk.mla_flash(
                    q, q_r, kv, k_r, H, n, dv, scale, lengths=lens))
                cells = []
                for fill in fills:
                    rows = max(1, min(T, int(round(fill * T))))
                    lens = jnp.asarray([rows], jnp.int32)
                    got = np.asarray(fn(q, q_r, kv, k_r, lens)
                                     .astype(jnp.float32))
                    if fill == fills[0]:
                        want = got if want is None else want
                        err = float(np.abs(got - want).max()
                                    / max(np.abs(want).max(), 1e-9))
                        good = err < TOL and bool(np.isfinite(got).all())
                    ms = _named_ms(lambda x: fn(x, q_r, kv, k_r, lens), q,
                                   name)
                    least = _mla_flops(rows, H, n, r, dv) / 197e12 * 1e3
                    cells.append(
                        f"fill={fill:g}: {ms:.3f}ms "
                        f"{100 * least / ms if ms else 0:.1f}% "
                        + _gqa_schedule_note(rows, T, 0, latent=True))
                note = _mla_tiles_note(T, H, n, r, dv)
            except Exception as e:  # noqa: BLE001 — the compiler's no
                print(f"FAIL mla-tiles T={T} tiles={tiles}: "
                      f"{type(e).__name__}: {str(e)[:300]}", flush=True)
                results.append(False)
                continue
            finally:
                pk._mla_tiles = chooser
            results.append(good)
            print(f"{'OK ' if good else 'FAIL'} mla-tiles T={T} "
                  f"tiles={note}{'' if tiles else ' (chosen)'} "
                  f"gap={err:.4f} | " + " | ".join(cells), flush=True)
    return results


def _fill_report(what, T, n, runs, need_flops, notes=None):
    """One line a kernel of the ``--fill`` checks: ``runs`` = {name: (ms
    given the length, without it, of a bucket cut to the live tiles —
    what is left is the dead tiles' grid steps — and ok)};
    ``need_flops`` = {name: the FLOP of the prompt's REAL pairs}, the
    least time from it at the v5e's peak; ``notes`` = {name: what the
    schedule says of itself}."""
    ok = True
    for name, (ms, ms_full, ms_live, good) in runs.items():
        ok = ok and good
        least = need_flops[name] / 197e12 * 1e3
        print(f"{'OK ' if good else 'FAIL'} fill {what} {name} T={T} "
              f"prompt={n} ({n / T:.3f}): lengths={ms:.3f}ms "
              f"lengths=None {ms_full:.3f}ms live_tiles_alone="
              f"{ms_live:.3f}ms least={least:.3f}ms "
              f"roofline={100 * least / ms if ms else 0:.1f}%"
              f"{' ' + notes[name] if notes else ''}", flush=True)
    return ok


def _named_ms(fn, x, name):
    """Device ms a call of the one kernel ``name`` that ``fn(x)`` runs."""
    return _kernel_ms(fn, x, n=3).get(name, 0.0)


def _live_rows(n, T, latent=False, window=0):
    """The rows of a bucket of T that a prompt of n rows' live query
    tiles hold: a bucket cut there walks what the kernel given the
    length walks, WITHOUT the dead tiles' grid steps."""
    from mxnet_tpu.ops import pallas_kernels as pk

    blk = pk._prompt_schedule(T, window, latent)[0]
    return min(T, -(-n // blk) * blk)


def _band_pairs(n, window):
    w = min(n, window) if window else n
    return w * (w + 1) / 2 + (n - w) * w


def check_fill_window(T, window, fill, Hq=28, Hkv=4, D=128):
    """The prompt kernels of a grouped-query family alone at a prompt
    of ``fill x T`` rows in a bucket of T: ms a call given the length,
    the same call without it (the whole bucket walked: the parent's
    walk), and the least time of the prompt's real pairs; rows below
    the length must EQUAL the other call's, rows past it be 0."""
    from mxnet_tpu.ops import pallas_kernels as pk

    n = max(1, min(T, int(round(fill * T))))
    rng = np.random.RandomState(T + window)
    q, k, v = (jnp.asarray(rng.randn(n_, T, D).astype(np.float32) * 0.5)
               .astype(jnp.bfloat16) for n_ in (Hq, Hkv, Hkv))
    lens = jnp.asarray([n], jnp.int32)
    runs, need, notes = {}, {}, {}
    for w in (window, 0):
        cut = jax.jit(lambda q, k, v, lens, w=w: pk.flash_mha_window(
            q, k, v, w, Hq, Hkv, lengths=lens))
        whole = jax.jit(lambda q, k, v, w=w: pk.flash_mha_window(
            q, k, v, w, Hq, Hkv))
        got, want = np.asarray(cut(q, k, v, lens).astype(jnp.float32)), \
            np.asarray(whole(q, k, v).astype(jnp.float32))
        good = bool((got[:, :n] == want[:, :n]).all()
                    and (got[:, n:] == 0).all())
        name = "flash_fwd_window" if w else "flash_fwd_mha"
        live = _live_rows(n, T, window=w)
        runs[name] = (
            _named_ms(lambda x: cut(x, k, v, lens), q, name),
            _named_ms(lambda x: whole(x, k, v), q, name),
            _named_ms(lambda x: whole(x, k[:, :live], v[:, :live]),
                      q[:, :live], name),
            good)
        need[name] = 4.0 * Hq * D * _band_pairs(n, w)
        notes[name] = _gqa_schedule_note(n, T, w)
    return _fill_report(f"H={Hq}/{Hkv} D={D} window={window}", T, n, runs,
                        need, notes)


def check_fill_mla(T, fill, H=128, n=128, r=64, dv=128, scale=0.13523):
    """:func:`check_fill_window` for the latent prefill kernel."""
    from mxnet_tpu.ops import pallas_kernels as pk

    rows = max(1, min(T, int(round(fill * T))))
    q, q_r, kv, k_r = _mla_inputs(T, H, n, r, dv)
    lens = jnp.asarray([rows], jnp.int32)
    cut = jax.jit(lambda q, q_r, kv, k_r, lens: pk.mla_flash(
        q, q_r, kv, k_r, H, n, dv, scale, lengths=lens))
    whole = jax.jit(lambda q, q_r, kv, k_r: pk.mla_flash(
        q, q_r, kv, k_r, H, n, dv, scale))
    got = np.asarray(cut(q, q_r, kv, k_r, lens).astype(jnp.float32))
    want = np.asarray(whole(q, q_r, kv, k_r).astype(jnp.float32))
    good = bool((got[:, :rows] == want[:, :rows]).all()
                and (got[:, rows:] == 0).all())
    name = "mla_flash_fwd"
    live = _live_rows(rows, T, latent=True)
    runs = {name: (
        _named_ms(lambda x: cut(x, q_r, kv, k_r, lens), q, name),
        _named_ms(lambda x: whole(x, q_r, kv, k_r), q, name),
        _named_ms(lambda x: whole(x, q_r[:, :live], kv[:, :live],
                                  k_r[:, :live]), q[:, :live], name),
        good)}
    return _fill_report(
        f"H={H} qk={n}+{r} v={dv}", T, rows, runs,
        {name: _mla_flops(rows, H, n, r, dv)},
        {name: f"tiles={_mla_tiles_note(T, H, n, r, dv)} "
               + _gqa_schedule_note(rows, T, 0, latent=True)})


def check_mla_paged(B, MB, lengths, H=128, R=512, r=64, KVB=16,
                    scale=0.13523):
    """The latent paged decode kernel over a pool of [latent | rotary
    key | zeros] rows against a gather in jax.numpy, and the page write
    against the row scatter, with the kernels' ms a call."""
    from mxnet_tpu.kv_cache import latent_pool_shape
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(B + MB)
    shape = latent_pool_shape(1 + B * MB, KVB, R, r)
    lanes = shape[2]
    assert pk.mla_paged_enabled(H, lanes, R)
    pool = np.zeros(shape, np.float32)
    pool[..., :R + r] = rng.randn(*shape[:2], R + r) * 0.7
    pool = jnp.asarray(pool).astype(jnp.bfloat16)
    q = np.zeros((B, H, lanes), np.float32)
    q[..., :R + r] = rng.randn(B, H, R + r) * 0.5
    q = jnp.asarray(q).astype(jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(B * MB).reshape(B, MB), jnp.int32)
    n = jnp.asarray(np.resize(np.asarray(lengths, np.int32), B))
    kern = jax.jit(lambda q, pool: pk.mla_paged_decode(
        q, pool, table, n - 1, R, scale))

    def lax_body(q, pool):
        rows = pool[table].reshape(B, MB * KVB, lanes)
        s = jnp.einsum("bhw,btw->bht", q, rows,
                       preferred_element_type=jnp.float32) * scale
        seen = jnp.arange(MB * KVB)[None, None, :] < n[:, None, None]
        p = jnp.where(seen, jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1), 0.0)
        return jnp.einsum("bht,btr->bhr", p.astype(rows.dtype),
                          rows[..., :R], preferred_element_type=jnp.float32)

    got = np.asarray(kern(q, pool).astype(jnp.float32))
    want = np.asarray(jax.jit(lax_body)(q, pool))
    err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9))
    ok = err < TOL and bool(np.isfinite(got).all())
    ms = _kernel_ms(lambda x: kern(x, pool), q)
    ctx = float(np.asarray(n).sum())
    least = max(2.0 * H * (2 * R + r) * ctx / 197e12,
                ctx * (R + r) * 2 / 819e9) * 1e3
    # the page write: a whole prompt's rows, against the row scatter
    T = min(4096, MB * KVB)
    rows = jnp.asarray(rng.randn(1, T, lanes).astype(np.float32)
                       ).astype(jnp.bfloat16)
    tab = table[:1, :T // KVB]
    live = jnp.asarray([T - 37], jnp.int32)
    write = jax.jit(lambda rows, pool: att.latent_prefill_write(
        rows, pool, tab, live))
    page, slot, _ = att._paged_write_coords(tab, live, T, KVB)
    scattered = np.array(jax.jit(
        lambda rows, pool: pool.at[page, slot].set(rows))(rows, pool))
    written = np.array(write(rows, pool))
    # the last live page's slots past the length hold the padding's rows
    # (the page write) or what was there (the scatter): no reader's
    last = int(np.asarray(tab)[0, (T - 37 - 1) // KVB])
    scattered[last, (T - 37) % KVB:] = written[last, (T - 37) % KVB:]
    # (and the scratch page 0, which the scatter sends padding to)
    same = bool((written[1:] == scattered[1:]).all())
    ms.update(_kernel_ms(lambda x: write(x, pool), rows))
    print(f"{'OK ' if ok and same else 'FAIL'} mla_paged B={B} MB={MB} "
          f"H={H} row={R}+{r} in {lanes}: fwd={err:.4f} "
          f"write_equal={same} least={least:.3f}ms"
          + "".join(f" {k}={v:.3f}ms" for k, v in ms.items()), flush=True)
    return ok and same


def _time_ms(fn, *args, n=5):
    import time

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / n


def check_mamba2(T, n, B=1, H=128, P=64, N=128):
    """``Mamba2Step`` (T = 1: B rows against their slots) or
    ``Mamba2Chunk`` (a prompt of n live tokens padded to T) through the
    registered op, the Mosaic kernel against the op's lax body on the
    same bfloat16 inputs: the outputs' and the slots' largest gaps over
    the lax body's largest value, and both bodies' time."""
    from mxnet_tpu.kv_cache import state_pool_shape
    from mxnet_tpu.ops import hybrid  # noqa: F401 — registers the ops
    from mxnet_tpu.ops.registry import OpContext, get_op

    rng = np.random.RandomState(T + n)
    bf = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32)) \
        .astype(jnp.bfloat16)
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))
    step = T == 1
    xbc, dt = bf(B, T, H * P + 2 * N), bf(B, T, H)
    a_log = f32(np.log(rng.uniform(1, 16, H)))
    dt_bias = f32(np.log(np.expm1(np.exp(rng.uniform(
        np.log(1e-3), np.log(1e-1), H)))))
    pool = f32(rng.randn(*state_pool_shape(B + 1, (H, P, N))) * 0.1)
    slots = jnp.asarray(1 + rng.permutation(B).astype(np.int32))
    lengths = jnp.full((B,), n, jnp.int32)
    op = get_op("Mamba2Step" if step else "Mamba2Chunk")
    attrs = {"num_heads": str(H), "d_state": str(N)}

    def run(flag):
        os.environ["MXNET_PALLAS"] = flag
        fn = jax.jit(lambda *a: op.compute(
            OpContext(is_train=False, rng=None), attrs, list(a), []))
        args = (xbc, dt, a_log, dt_bias, jnp.ones((H,), jnp.float32), pool,
                slots, lengths)
        out = [np.asarray(x.astype(jnp.float32)) for x in fn(*args)]
        return out, _time_ms(fn, *args)

    try:
        (y_k, s_k), ms_k = run("1")
        (y_l, s_l), ms_l = run("0")
    finally:
        os.environ.pop("MXNET_PALLAS", None)
    live = slice(0, n)
    e_y = float(np.abs(y_k[:, live] - y_l[:, live]).max()
                / max(np.abs(y_l[:, live]).max(), 1e-9))
    rows = np.asarray(slots)
    e_s = float(np.abs(s_k[rows] - s_l[rows]).max()
                / max(np.abs(s_l[rows]).max(), 1e-9))
    ok = e_y < TOL and e_s < TOL and bool(np.isfinite(y_k).all()) \
        and np.array_equal(s_k[0], s_l[0])
    print(f"{'OK ' if ok else 'FAIL'} mamba2 "
          f"{'step' if step else 'chunk'} B={B} T={T} n={n} "
          f"state=({H},{P},{N}): y={e_y:.4f} state={e_s:.4f} "
          f"kernel={ms_k:.3f}ms lax={ms_l:.3f}ms", flush=True)
    return ok


def check_retention(T, n, B=1, H=40, J=8, D=128):
    """``RetentionStep`` (T = 1: B rows against their slots) or
    ``RetentionChunk`` (a prompt of n live tokens padded to T) through
    the registered op, the Mosaic kernel against the op's lax body on
    the same bfloat16 inputs: the outputs', the slots' and the
    normalisers' largest gaps over the lax body's largest value, and both
    bodies' time."""
    from mxnet_tpu.ops import hybrid
    from mxnet_tpu.ops.registry import OpContext, get_op

    rng = np.random.RandomState(T + n)
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))
    step = T == 1

    def unit(*s):     # rows of norm sqrt(D): what the q/k norms leave
        x = rng.randn(*s, D).astype(np.float32)
        x *= np.sqrt(D) / np.linalg.norm(x, axis=-1, keepdims=True)
        return jnp.asarray(x.reshape(s[0], s[1], -1)).astype(jnp.bfloat16)

    q, k = unit(B, T, H), unit(B, T, J)
    v = jnp.asarray(rng.randn(B, T, J * D).astype(np.float32)) \
        .astype(jnp.bfloat16)
    g = jnp.asarray(rng.randn(B, T, J).astype(np.float32)) \
        .astype(jnp.bfloat16)
    bias = f32(np.log(1 / np.exp(rng.uniform(
        np.log(5e-4), np.log(2e-2), J)) - 1))
    R = hybrid.retention_rows(D)
    pool = f32(rng.randn(B + 1, J, R, D) * 0.1)
    norm = f32(np.abs(rng.randn(B + 1, J, D, D)) * 0.1
               + 10 * np.eye(D, dtype=np.float32))
    slots = jnp.asarray(1 + rng.permutation(B).astype(np.int32))
    lengths = jnp.full((B,), n, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None] + (
        900 if step else 0), (B, T))
    op = get_op("RetentionStep" if step else "RetentionChunk")
    attrs = {"num_heads": str(H), "kv_heads": str(J),
             "rope_theta": "1000000.0"}

    def run(flag):
        os.environ["MXNET_PALLAS"] = flag
        fn = jax.jit(lambda *a: op.compute(
            OpContext(is_train=False, rng=None), attrs, list(a), []))
        args = (q, k, v, g, bias, pool, norm, slots, lengths, pos)
        out = [np.asarray(x.astype(jnp.float32)) for x in fn(*args)]
        return out, _time_ms(fn, *args)

    try:
        (y_k, s_k, z_k), ms_k = run("1")
        (y_l, s_l, z_l), ms_l = run("0")
    finally:
        os.environ.pop("MXNET_PALLAS", None)
    live = slice(0, n)
    rows = np.asarray(slots)
    gap = lambda a, b: float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-9))
    e_y = gap(y_k[:, live], y_l[:, live])
    e_s, e_z = gap(s_k[rows], s_l[rows]), gap(z_k[rows], z_l[rows])
    ok = max(e_y, e_s, e_z) < TOL and bool(np.isfinite(y_k).all()) \
        and np.array_equal(s_k[0], s_l[0])
    alone = _retention_step_alone(B, H // J, J, D, pool, norm, slots) \
        if step else ""
    print(f"{'OK ' if ok else 'FAIL'} retention "
          f"{'step' if step else 'chunk'} B={B} T={T} n={n} "
          f"heads={H}/{J}x{D}: y={e_y:.4f} state={e_s:.4f} z={e_z:.4f} "
          f"kernel={ms_k:.3f}ms lax={ms_l:.3f}ms{alone}", flush=True)
    return ok


def _retention_step_alone(B, G, J, D, pool, norm, slots, n=40):
    """The step kernel ALONE (no rotation, no gate) in a program that
    donates both pools, so that no copy of them is in the reading: us a
    grid step (a row and KV head) and the share of the least its two
    copies of a head's state can take at 819 GB/s."""
    import time

    from mxnet_tpu.ops import pallas_hybrid

    rng = np.random.RandomState(B)
    f32 = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q, k, v = f32(B, J, G, D), f32(B, J, D), f32(B, J, D)
    a = jnp.full((B, J), 0.99, jnp.float32)
    fn = jax.jit(pallas_hybrid.retention_step, donate_argnums=(4, 5))
    y, pool, norm = fn(q, k, v, a, pool, norm, slots)       # compiles
    jax.block_until_ready(pool)
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            y, pool, norm = fn(q, k, v, a, pool, norm, slots)
        jax.block_until_ready((y, pool, norm))
        best = min(best, (time.perf_counter() - t) / n)
    us = 1e6 * best / (B * J)
    least = 1e6 * 2 * pool.nbytes / (pool.shape[0] * J) / 819e9
    return (f" alone(donated)={1e3 * best:.3f}ms = {us:.2f}us a grid step, "
            f"{100 * least / us:.1f}% of its copies' least {least:.2f}us")


def _grid_pages_write(k, v, k_pool, v_pool, pages):
    """The other form of the page write (ISSUE 37): a grid step a page
    through VMEM, the out BlockSpec's index map reading the page id, as
    ``pallas_hybrid.slot_rows_write`` writes its rows; a dead block goes
    to the scratch page.  Kept here for the comparison alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops import pallas_kernels as pk

    B, T, W = k.shape
    kvb = k_pool.shape[1]

    def kernel(pages_ref, k_ref, v_ref, kp, vp, k_out, v_out):
        del pages_ref, kp, vp
        k_out[...] = k_ref[...]
        v_out[...] = v_ref[...]

    rows = pk._vmem_spec((1, kvb, W), lambda b, j, pg: (b, j, 0))
    page = pk._vmem_spec((1, kvb, W), lambda b, j, pg: (pg[b, j], 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, T // kvb),
            in_specs=[rows, rows, pool, pool], out_specs=[page, page]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pk._compiler_params("arbitrary", "arbitrary"),
        interpret=pk._interpret(), name="kv_pages_write_grid",
    )(pages, k, v, k_pool, v_pool)


def _write_ms(fn, k, v, pools, table, lengths, n=10):
    """Device ms a call of ``fn`` (every op of its program, summed) over
    n calls that hand the donated pools on; (ms, the pools after)."""
    import shutil
    import tempfile

    from benchmark.trace_reduce import Trace

    pools = fn(k, v, *pools, table, lengths)
    jax.block_until_ready(pools)
    d = tempfile.mkdtemp(prefix="verify_kernels_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(n):
            pools = fn(k, v, *pools, table, lengths)
        jax.block_until_ready(pools)
        jax.profiler.stop_trace()
        return 1e3 * sum(Trace.from_dir(d).op_seconds().values()) / n, pools
    finally:
        shutil.rmtree(d, ignore_errors=True)


def check_pages_write(T, W, P, live, behind=0, KVB=16):
    """A (1, T, W) prompt of ``live`` tokens into bfloat16 pools of P
    pages, its first ``behind`` blocks behind a window (table 0): the
    page kernel ``paged_prefill_write`` takes, bit for bit against the
    row scatter on every live slot and every page the table does not
    name, and the device ms a call of the row scatter, the kernel and
    the grid form."""
    from mxnet_tpu.ops import attention as att

    rng = np.random.RandomState(T + W)
    k, v = (jnp.asarray(rng.randn(1, T, W).astype(np.float32))
            .astype(jnp.bfloat16) for _ in range(2))
    blocks = T // KVB
    table = np.zeros((1, blocks), np.int32)
    table[0, behind:] = 1 + rng.permutation(P - 1)[:blocks - behind]
    table, lengths = jnp.asarray(table), jnp.asarray([live], jnp.int32)

    def fresh():
        return [jnp.full((P, KVB, W), 7, jnp.bfloat16) for _ in range(2)]

    def pages_of(lengths):
        return att._live_pages(table, lengths, blocks, KVB)

    forms = {
        "row_scatter": lambda k, v, kp, vp, t, n: att.paged_prefill_write(
            k, v, kp, vp, t, n, start=jnp.zeros_like(n)),
        "kv_pages_write": att.paged_prefill_write,
        "grid_form": lambda k, v, kp, vp, t, n: tuple(_grid_pages_write(
            k, v, kp, vp, pages_of(n))),
    }
    ms, pools = {}, {}
    for name, fn in forms.items():
        ms[name], pools[name] = _write_ms(
            jax.jit(fn, donate_argnums=(2, 3)), k, v, fresh(), table,
            lengths)
    named = np.zeros((P,), bool)
    named[np.asarray(pages_of(lengths))[0]] = True
    named[0] = True                     # the scratch page is nobody's
    ok = True
    for rows, want, got in zip((k, v), pools["row_scatter"],
                               pools["kv_pages_write"]):
        there = got[table[0]].reshape(T, W)[behind * KVB:live]
        ok &= bool(jnp.array_equal(there, rows[0, behind * KVB:live]))
        ok &= bool(jnp.array_equal(
            there, want[table[0]].reshape(T, W)[behind * KVB:live]))
        ok &= bool(jnp.all(jnp.where(jnp.asarray(named)[:, None, None],
                                     True, got == 7)))
    moved = 2 * 2 * (live - behind * KVB) * W * 2
    print(f"{'OK ' if ok else 'FAIL'} pages  T={T} W={W} P={P} live={live} "
          f"behind={behind}: "
          + " ".join(f"{k}={v:.4f}ms" for k, v in ms.items())
          + f" ({moved / 1e6:.1f} MB read + written: "
          f"{moved / 819e9 * 1e3:.4f}ms at 819 GB/s)", flush=True)
    return ok


def _program_ms(fn, *args, n=3):
    """Device ms a call of jitted ``fn(*args)``: every op of its program
    summed, from a trace of n calls (the benchmark's reader)."""
    import shutil
    import tempfile

    from benchmark.trace_reduce import Trace

    jax.block_until_ready(fn(*args))
    d = tempfile.mkdtemp(prefix="verify_kernels_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        return 1e3 * sum(Trace.from_dir(d).op_seconds().values()) / n
    finally:
        shutil.rmtree(d, ignore_errors=True)


# the four expert cells: experts scored, held here, top_k, d, the experts'
# width, the gate; tokens of the largest prefill bucket, rows of a decode
# step; what moe_load_max_over_mean.* reads in the ledger (PR 38)
MOE_CELLS = {
    "rag": (72, 36, 10, 4096, 768, "silu", 2048, 64, 1.84),
    "mixed": (64, 64, 6, 2560, 768, "relu", 8192, 48, 2.26),
    "reason": (320, 40, 8, 4096, 1280, "silu", 2048, 128, 3.99),
    "longctx": (256, 8, 8, 7168, 2048, "silu", 8192, 32, 3.24),
}


def _scatter_dispatch(topi, valid, first, held, tm):
    """``ops/hybrid.py moe_dispatch`` as it stood before PR 39 — a
    scatter-add for the sizes, single-number gathers, three scatters:
    the form the table holds the sorts against."""
    N, k = topi.shape
    local = topi - first
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    P = N * k
    M = -(-N * min(k, held) // tm) * tm + held * tm
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
    padded = -(-sizes[:held] // tm) * tm
    ends = jnp.cumsum(padded)
    starts = jnp.concatenate([ends - padded, jnp.zeros((1,), jnp.int32)])
    plain = jnp.cumsum(sizes) - sizes
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    row = jnp.where(skey < held,
                    starts[skey] + jnp.arange(P) - plain[skey], M)
    pair_row = jnp.zeros((P,), jnp.int32).at[order].set(row)
    row_token = jnp.zeros((M,), jnp.int32).at[row].set(
        (order // k).astype(jnp.int32), mode="drop")
    first_row = jnp.arange(M // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.sum((ends[None, :] <= first_row[:, None]).astype(jnp.int32),
                axis=1), held - 1)
    n_used = (ends[-1:] // tm).astype(jnp.int32)
    return (here, pair_row.reshape(N, k), row_token, tile_expert, n_used,
            sizes[:held])


def check_moe(cell, tokens):
    """One expert layer's dispatch round at ``cell``'s widths over
    ``tokens`` rows — a prefill bucket or a decode step — piece by
    piece, device ms a call.  As it stood: the dispatch by scatters,
    the ``xs`` gather, ``gate_up``, ``down``, the ``got`` gather +
    reshape + weighted sum.  Beside them: the dispatch by sorts,
    ``gate_up`` with its own row copies, ``down`` into slabs, the
    combine as a kernel and as k gathers; the two dispatches must agree
    number for number, and the largest float32 gap between the two
    outputs is printed."""
    from mxnet_tpu.ops import hybrid
    from mxnet_tpu.ops import pallas_hybrid as ph

    experts, held, k, d, w, act, _, _, _ = MOE_CELLS[cell]
    n = tokens
    rng = np.random.RandomState(n + d)
    bf = jnp.bfloat16
    x2 = jnp.asarray(rng.randn(n, d).astype(np.float32)).astype(bf)
    wg, wu = (jnp.asarray(0.02 * rng.randn(held, d, w).astype(np.float32))
              .astype(bf) for _ in range(2))
    wd = jnp.asarray(0.02 * rng.randn(held, w, d).astype(np.float32)
                     ).astype(bf)
    # a token's k experts: the largest of popularity + noise
    scores = 0.5 * rng.randn(experts) + rng.gumbel(size=(n, experts))
    topi = jnp.asarray(np.argsort(-scores, axis=1)[:, :k].astype(np.int32))
    wts = jax.nn.softmax(jnp.asarray(rng.randn(n, k).astype(np.float32)))
    valid = jnp.ones((n,), bool)
    tm = hybrid._tile_rows(n * min(k, held))
    ms, out = {}, {}

    def piece(name, fn, *args):
        fn = jax.jit(fn)
        ms[name] = _program_ms(fn, *args)
        out[name] = fn(*args)
        return out[name]

    was = piece("scatter_dispatch", functools.partial(
        _scatter_dispatch, first=0, held=held, tm=tm), topi, valid)
    now = piece("dispatch", functools.partial(
        hybrid.moe_dispatch, first=0, held=held, tm=tm), topi, valid)
    here, pair_row, row_token, tile_expert, n_used, sizes = now
    used = int(n_used[0]) * tm           # rows past them hold anything
    ok = all(bool(jnp.array_equal(a, b)) for a, b in (
        (was[0], here), (jnp.where(here, was[1], 0),
                         jnp.where(here, pair_row, 0)),
        (was[2][:used], row_token[:used]), (was[3], tile_expert),
        (was[4], n_used), (was[5], sizes)))
    pairs = int(jnp.sum(sizes))
    load = float(jnp.max(sizes)) * held / max(pairs, 1)

    def old_combine(ys, pair_row, here, wts):
        got = ys[jnp.minimum(pair_row, ys.shape[0] - 1)]
        return jnp.sum(jnp.where(here[..., None], got * wts[..., None],
                                 0.0), axis=1).astype(bf)

    tiles = (tile_expert, n_used)
    xs = piece("xs_gather", lambda x, rt: x[rt], x2, row_token)
    h = piece("gate_up", lambda xs, wg, wu, te, nu: ph.moe_gmm_gate_up(
        xs, wg, wu, te, nu, tm, act), xs, wg, wu, *tiles)
    ys = piece("down", lambda h, wd, te, nu: ph.moe_gmm_down(
        h, wd, te, nu, tm), h, wd, *tiles)
    y_old = piece("got_sum", old_combine, ys, pair_row, here, wts)
    h2 = piece("gate_up_rows", lambda x, wg, wu, te, nu, rt:
               ph.moe_gmm_gate_up(x, wg, wu, te, nu, tm, act, row_token=rt),
               x2, wg, wu, *tiles, row_token)
    ys3 = piece("down_slabs", lambda h, wd, te, nu: ph.moe_gmm_down(
        h, wd, te, nu, tm, slabs=True), h2, wd, *tiles)
    y_new = piece("combine", lambda ys, pr, here, wts: ph.moe_gmm_combine(
        ys, pr, here, wts, d, bf), ys3, pair_row, here, wts)
    y_alt = piece("k_gathers", lambda ys, pr, here, wts: hybrid.moe_combine(
        ys, pr, here, wts, d, bf), ys, pair_row, here, wts)
    f32 = jnp.float32
    top = float(jnp.max(jnp.abs(y_old.astype(f32))))
    gap = float(jnp.max(jnp.abs(y_new.astype(f32) - y_old.astype(f32))))
    gap_alt = float(jnp.max(jnp.abs(y_alt.astype(f32) - y_old.astype(f32))))
    ok &= bool(jnp.array_equal(h[:used], h2[:used])) \
        and max(gap, gap_alt) <= 2.0 ** -8 * top
    old = sum(ms[p] for p in ("scatter_dispatch", "xs_gather", "gate_up",
                              "down", "got_sum"))
    rows = ms["dispatch"] + ms["down_slabs"] + ms["combine"]
    print(f"{'OK ' if ok else 'FAIL'} moe {cell} rows={n} tm={tm} "
          f"pairs_here={pairs} of {n * k} tiles={int(n_used[0])} of "
          f"{row_token.shape[0] // tm} load_max_over_mean={load:.2f}: "
          + " ".join(f"{p}={v:.4f}ms" for p, v in ms.items())
          + f" | as it stood {old:.4f}ms; rows by index "
          f"{rows + ms['gate_up_rows']:.4f}ms, gathered "
          f"{rows + ms['xs_gather'] + ms['gate_up']:.4f}ms; (M, d) rows and "
          f"k gathers {old - ms['scatter_dispatch'] + ms['dispatch'] - ms['got_sum'] + ms['k_gathers']:.4f}ms; "
          f"gap {gap:.3g} (k gathers {gap_alt:.3g}) of {top:.3g}", flush=True)
    return ok


def _paged_matrix(quick):
    results = []
    # the cells' widths (20 and 16 heads of 64), a head a quarter of a
    # lane tile, and H·D not a multiple of 128
    for H, D in ([(20, 64)] if quick else
                 [(20, 64), (16, 64), (12, 64), (4, 32), (3, 48)]):
        for W, kv in ((1, "bf16"), (5, "bf16"), (1, "int8")):
            results.append(check_paged(H, D, W, kv))
    # grouped queries: 64 query heads over 8 KV heads of 128, the
    # hybrid family's attention layers (decode and a 2-row window)
    for W in (1, 2):
        results.append(check_paged(8, 128, W, "bf16", Hq=64))
    # the serving cells' own decode steps: 48 rows over 64-page tables,
    # and 160-page tables of 64 / 8 x 128 (16 rows of the cell's 128:
    # the reference gathers every row's table at 64 heads, 84 MB a row)
    results.append(check_paged(20, 64, 1, "bf16", B=48, MB=64))
    results.append(check_paged(8, 128, 1, "bf16", B=16, MB=160, Hq=64))
    return results


def main():
    quick = "--quick" in sys.argv
    results = []
    # --fill F with --longdoc / --window / --mla: the prompt kernels of
    # that cell alone at a prompt of F x T rows in each bucket of T
    fills = [float(f) for f in
             sys.argv[sys.argv.index("--fill") + 1].split(",")] \
        if "--fill" in sys.argv else []
    if "--tiles" in sys.argv:
        return _report(sweep_tiles())
    if "--gqa-tiles" in sys.argv:
        return _report(sweep_gqa_tiles())
    if "--mla-tiles" in sys.argv:
        return _report(sweep_mla_tiles(fills or [1.0]))
    if "--pages" in sys.argv:
        # a prompt's K/V write at the serving cells' shapes: doc's one
        # bucket (a whole and a typical prompt), reason's two, mixed's
        # four over its ordinary pools and, past the window, over its
        # windowed pools (the blocks behind the window go nowhere)
        for T, W, P, live, behind in (
                (1024, 1280, 3073, 1024, 0), (1024, 1280, 3073, 760, 0),
                (1024, 1024, 20481, 900, 0), (2048, 1024, 20481, 1531, 0),
                (1024, 512, 26113, 700, 0), (2048, 512, 26113, 1531, 0),
                (4096, 512, 26113, 3000, 0), (8192, 512, 26113, 8192, 0),
                (8192, 512, 12385, 8000, 244)):
            results.append(check_pages_write(T, W, P, live, behind))
        return _report(results)
    if "--moe" in sys.argv:
        # each expert cell's largest prefill bucket, then its decode step
        for cell, shape in MOE_CELLS.items():
            results.append(check_moe(cell, shape[6]))
        for cell, shape in MOE_CELLS.items():
            results.append(check_moe(cell, shape[7]))
        return _report(results)
    if "--mamba2" in sys.argv:
        # the granite cell's own shapes: a 64-row decode step, prompts
        # in the 1024 and 2048 buckets (whole and ending inside a chunk)
        results.append(check_mamba2(1, 1, B=64))
        for T, n in ((1024, 1024), (1024, 700), (2048, 2048), (2048, 1531)):
            results.append(check_mamba2(T, n))
        return _report(results)
    if "--retention" in sys.argv:
        # the gen cell's own shapes: a 12-row decode step, prompts in
        # the 1024 and 2048 buckets (whole, ending inside a chunk, and
        # one that leaves three chunks unwalked)
        results.append(check_retention(1, 1, B=12))
        for T, n in ((1024, 1024), (1024, 700), (2048, 2048), (2048, 1290)):
            results.append(check_retention(T, n))
        return _report(results)
    for fill in fills:
        if "--mla" in sys.argv:
            results += [check_fill_mla(T, fill) for T in MLA_BUCKETS]
        if "--longdoc" in sys.argv:
            results += [check_fill_window(T, 4096, fill, Hq=48, Hkv=8)
                        for T in (32768, 16384, 8192)]
        if "--window" in sys.argv:
            results.append(check_fill_window(8192, 4096, fill))
    if fills:
        return _report(results)
    if "--mla" in sys.argv:
        # the longctx cell's shapes: 32 rows of 128 heads over 544-page
        # tables of 640-lane rows at its mean context, then lengths
        # that end inside a page and a chunk, an empty row among them;
        # prompts in every prefill bucket, and one that fills no tile
        results.append(check_mla_paged(32, 544, [3900]))
        results.append(check_mla_paged(
            32, 544, [8704, 513, 0, 1, 4095, 16, 0, 7000]))
        for T in (1024, 2048, 4096, 8192, 1000):
            results.append(check_mla_flash(T))
        return _report(results)
    if "--longdoc" in sys.argv:
        # the longdoc cell's shapes: 24 rows of 48 / 8 heads x 128 over
        # 2,080-page tables (rows of every length up to 33,280 keys: the
        # lax body on four of them), windowed and global; prompts in
        # its four prefill buckets, windowed and global
        rows = [0, 2, 12, 23]
        results.append(check_paged(8, 128, 1, "bf16", B=24, MB=2080, Hq=48,
                                   window=4096, rows=rows))
        results.append(check_paged(8, 128, 1, "bf16", B=24, MB=2080, Hq=48,
                                   rows=rows))
        for T in (32768, 16384, 8192, 4096):
            results.append(check_window_flash(T, 4096, Hq=48, Hkv=8))
        return _report(results)
    if "--window" in sys.argv:
        # the mixed cell's shapes: 48 rows of 28 / 4 heads x 128 over
        # 544-page tables and a window of 4,096 keys, prompts in every
        # prefill bucket; then windows and lengths that end inside tiles
        # and chunks
        results.append(check_paged(4, 128, 1, "bf16", B=48, MB=544, Hq=28,
                                   window=4096))
        results.append(check_paged(4, 128, 1, "bf16", B=48, MB=544, Hq=28))
        results.append(check_paged(4, 128, 1, "bf16", B=8, MB=64, Hq=28,
                                   window=300))
        results.append(check_paged(8, 128, 1, "bf16", B=8, MB=64, Hq=64,
                                   window=256))
        for T, w in ((8192, 4096), (4096, 4096), (2048, 4096), (1024, 4096),
                     (3000, 1000), (1024, 100)):
            results.append(check_window_flash(T, w))
        return _report(results)
    if "--packed" not in sys.argv:
        results += _paged_matrix(quick)
    if "--paged" in sys.argv:
        return _report(results)
    # packed: the cells' own shapes under the chosen tiles, then revisit
    # counts, tile schedules (block_q, block_k, sub), head counts,
    # causality
    for (B, T, H), grad in CELL_SHAPES[:1] if quick else CELL_SHAPES:
        results.append(check_packed(T, None, True, H, B=B, grad=grad))
    matrix = [(1024, None, True, 12), (4096, None, True, 12)] if quick else [
        (1024, None, True, 12), (1024, None, False, 12),
        (2048, None, True, 12), (3072, None, True, 12),
        (4096, None, True, 12), (4096, None, False, 12),
        (4096, (512, 512, 128), True, 12), (4096, (1024, 1024, 1024), True, 4),
        (1536, (512, 512, 256), True, 8), (1000, (512, 256, 128), True, 8),
        (1000, None, False, 8),
    ]
    for T, tiles, causal, H in matrix:
        results.append(check_packed(T, tiles, causal, H))
    if "--packed" in sys.argv:
        return _report(results)
    for T, block, causal in ([(4096, 0, True)] if quick else
                             [(1024, 0, True), (4096, 0, True),
                              (4096, 1024, False), (2048, 512, True)]):
        results.append(check_mha(T, block, causal))
    _report(results)


def _report(results):
    n_fail = results.count(False)
    print(f"\n{len(results) - n_fail}/{len(results)} kernel parity checks "
          f"passed")
    if n_fail:
        raise SystemExit(f"{n_fail} kernel parity checks FAILED")


if __name__ == "__main__":
    main()
