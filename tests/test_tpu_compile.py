"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached: libtpu's compiler is installed here and lowers
for a topology that is only described (on-chip-measurement guide §2,
third rehearsal).  What Mosaic refuses — an illegal contraction, more
VMEM than a kernel may hold — fails here at no chip time, at the
widths the engine and the benchmarks really run (H=12, D=64, page 16,
8,732 SSD anchors).  Interpret mode, which every other test uses,
cannot see any of it.  (The hybrid family's kernels — KDA, Mamba-2,
retention, CCA, the experts' grouped matmuls — are a second file's,
``test_tpu_compile_hybrid.py``, with these fixtures and helpers.)

The topology is described inside a module-scoped fixture: only the
xdist worker that is handed this file loads libtpu.  Nothing here runs
at import, in a ``skipif`` or in a ``parametrize`` argument.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from mxnet_tpu import hlo, parallel
from mxnet_tpu.kv_cache import value_pool_shape
from mxnet_tpu.models import transformer
# pallas_hybrid is imported HERE, before a fixture patches
# ``pk._interpret``: it binds that name at import, and a first import
# under the patch would keep the patched one for the worker's life
# (every later interpreted hybrid kernel test on it would fail)
from mxnet_tpu.ops import attention, pallas_hybrid, pallas_kernels as pk  # noqa: F401

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip — keep the cache out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Steer the kernels' own CPU detection: compiled, not interpreted."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "enabled", lambda: True)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


# LM widths: batch 8, T 1024, 12 heads x 64 — fused QKV is 3*768 wide
_QKV = ((8, 1024, 2304), bf16)


def test_flash_mha_packed_fwd(on_chip, one_chip):
    _compile(lambda x: pk.flash_mha_packed(x, 12, causal=True),
             one_chip, _QKV)


def test_flash_mha_packed_grad(on_chip, one_chip):
    def loss(x):
        return pk.flash_mha_packed(x, 12, causal=True).astype(f32).sum()

    _compile(jax.grad(loss), one_chip, _QKV)


@pytest.mark.parametrize("cell", ["doc_prefill", "train_step"])
def test_flash_mha_packed_at_the_cells_shapes(on_chip, one_chip, cell):
    """gpt2-large's 1,024 prefill (forward) and gpt2-medium's training
    step (forward + gradient) under the tiles the chooser picks."""
    shape, heads = {"doc_prefill": ((1, 1024, 3840), 20),
                    "train_step": ((8, 1024, 3072), 16)}[cell]
    bq, bk, sub, lanes = pk._mhap_tiles(shape[1], shape[2] // 3, 64)
    assert bq >= 512 and bq % sub == 0, "wide q rows, whatever a page holds"

    def fwd(x):
        return pk.flash_mha_packed(x, heads, causal=True)

    fn = fwd if cell == "doc_prefill" else jax.grad(
        lambda x: fwd(x).astype(f32).sum())
    text = _compile(fn, one_chip, (shape, bf16)).as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) \
        == (1 if cell == "doc_prefill" else 3)


def test_page_size_does_not_reach_the_prefill_kernel(on_chip, one_chip):
    """``block_size`` — the engine passes its page size, 16 in the doc
    cell — governs the op's lax body; the Mosaic call is the same
    program with it or without."""
    from mxnet_tpu.ops.registry import OpContext, get_op

    op = get_op("QKVSelfAttentionPrefill")
    x = jax.ShapeDtypeStruct((1, 1024, 3840), bf16, sharding=one_chip)

    def compiled(block):
        attrs = {"num_heads": "20", "block_size": str(block)}
        return jax.jit(lambda a: op.compute(
            OpContext(is_train=False, rng=None), attrs, [a], [])).lower(
                x).compile().as_text()

    # one call site: the executable's text carries source lines
    with_page, without = (compiled(block) for block in (16, 0))
    assert "tpu_custom_call" in with_page and with_page == without


def test_flash_mha(on_chip, one_chip):
    s = ((8 * 12, 1024, 64), bf16)
    _compile(lambda q, k, v: pk.flash_mha(q, k, v, causal=True),
             one_chip, s, s, s)


def test_flash_attention_partial(on_chip, one_chip):
    s = ((2, 1024, 12, 64), bf16)
    _compile(lambda q, k, v: pk.flash_attention_partial(
        q, k, v, True, 512, 0), one_chip, s, s, s)


# the engine's decode step: 8 streams, 12 heads x 64, page 16, a
# 1024-token table (64 pages a stream) over a 640-page pool of
# lane-dense (P, KVB, H*D) pages
_B, _H, _D, _KVB, _MB, _P = 8, 12, 64, 16, 64, 640


def _pool(dt, pages=_P, heads=_H):
    return (value_pool_shape(pages, _KVB, heads, _D), dt)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_attention_decode(on_chip, one_chip, dtype):
    dt = jnp.dtype(dtype)
    _compile(functools.partial(pk.paged_attention_decode, num_heads=_H),
             one_chip, ((_B, _H * _D), dt), _pool(dt), _pool(dt),
             ((_B, _MB), i32), ((_B,), i32))


def test_paged_attention_decode_quant_int8(on_chip, one_chip):
    scale = ((_P, _KVB, _H), f32)
    _compile(functools.partial(pk.paged_attention_decode_quant,
                               num_heads=_H),
             one_chip, ((_B, _H * _D), bf16), _pool(i8), _pool(i8), scale,
             scale, ((_B, _MB), i32), ((_B,), i32))


def test_paged_attention_verify_w5(on_chip, one_chip):
    _compile(functools.partial(pk.paged_attention_verify, num_heads=_H),
             one_chip, ((_B, 5, _H * _D), bf16), _pool(bf16), _pool(bf16),
             ((_B, _MB), i32), ((_B,), i32))


def _verify_shapes(heads, d_head, w):
    pool = (value_pool_shape(_P, _KVB, heads, d_head), bf16)
    return [((_B, w, heads * d_head), bf16), pool, pool,
            ((_B, _MB), i32), ((_B,), i32)]


def _pages_a_chunk(heads, d_head, w):
    return pk._paged_pages_per_chunk(w, heads, heads, d_head, _KVB, _MB, 2,
                                     2)[1:]


def test_paged_attention_widest_admitted(on_chip, one_chip):
    """The all-heads kernel's VMEM grows as W*H^2*D, and the chunk
    gives way to it: 16 pages (256 keys) a chunk at the engine's and
    the doc cell's widths, 2 at the widest shape the guard admits (32
    heads x 128, an 8-row window: 10 MB of spread query, accumulator
    and product, 11.4 of the budget's 12.6 MB with two pages a chunk;
    a ninth row is over at one page), which really compiles ..."""
    assert _pages_a_chunk(_H, _D, 1)[0] == 16
    assert _pages_a_chunk(20, _D, 5)[0] == 16
    pages, vmem = _pages_a_chunk(32, 128, 8)
    assert pages == 2 and vmem <= pk._PAGED_VMEM_BUDGET
    assert _pages_a_chunk(32, 128, 9) > (1, pk._PAGED_VMEM_BUDGET)
    _compile(functools.partial(pk.paged_attention_verify, num_heads=32),
             one_chip, *_verify_shapes(32, 128, 8))


def test_paged_attention_refuses_what_vmem_cannot_hold(on_chip, one_chip):
    """... and one Mosaic has no VMEM for (64 heads x 128, W = 5: 26 MB
    of the 16 MB a kernel is given before a single page; unguarded,
    "Ran out of memory in memory space vmem") is refused by name, with
    its sizes."""
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError, match="64 heads x 128.*VMEM"):
        _compile(functools.partial(pk.paged_attention_verify,
                                   num_heads=64),
                 one_chip, *_verify_shapes(64, 128, 5))


def test_paged_attention_takes_whole_lane_tiles(on_chip, one_chip):
    """Compiled, the kernel copies page rows by hand and Mosaic slices
    HBM in whole lane tiles: 5 heads x 64 (gpt2-large's 20 over tp = 4)
    is refused by name, and the decode step at that width compiles all
    the same — ``paged_enabled`` gives it the lax body, no kernel."""
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError, match="320 lanes.*whole lane tiles"):
        _compile(functools.partial(pk.paged_attention_verify, num_heads=5),
                 one_chip, *_verify_shapes(5, _D, 1))
    assert pk.paged_enabled(640) and not pk.paged_enabled(320)
    fn, donated, shapes = _decode_pool_ops(5, _pool(bf16, heads=5))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn, donate_argnums=donated).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text


# The serving cells' programs touch the pools in two places: the decode
# step (one token's K/V scattered in, then the paged kernel) and the
# prefill's write of a whole prompt.  At the cells' sizes — 48 streams,
# 3073 pages, gpt2-large's 20 heads and gpt2-medium's 16 — neither may
# re-lay-out a pool: a (P, KVB, H, D) pool cost every program four
# pool-sized copies a layer and 3.5 pools of temporaries (PERF.md §6,
# PR 26).  The pools are donated, as the engine donates them.
_CB, _CP = 48, 3073


def _decode_pool_ops(heads, pool):
    def step(qkv, k_pool, v_pool, table, lengths):
        q, k, v = attention._split_qkv(qkv, heads)
        k_pool, v_pool = attention.paged_cache_update(
            k_pool, v_pool, k, v, table, lengths)
        out = attention.paged_decode_attention(q, k_pool, v_pool, table,
                                               lengths, heads)
        return out, k_pool, v_pool

    return step, (1, 2), [((_CB, 1, 3 * heads * _D), bf16), pool, pool,
                          ((_CB, _MB), i32), ((_CB,), i32)]


def _prefill_pool_ops(heads, pool, T=1024):
    rows = ((1, T, heads * _D), bf16)
    return attention.paged_prefill_write, (2, 3), [
        rows, rows, pool, pool, ((1, T // _KVB), i32), ((1,), i32)]


@pytest.mark.parametrize("ops,heads,pages,T", [
    (_decode_pool_ops, 20, _CP, 1), (_decode_pool_ops, 16, _CP, 1),
    (_prefill_pool_ops, 20, _CP, 1024), (_prefill_pool_ops, 16, _CP, 1024),
    # the mixed cell's longest bucket over its ordinary pools (4 KV
    # heads x 128 = 512 lanes) and the reason cell's (8 x 128)
    (_prefill_pool_ops, 8, 26113, 8192), (_prefill_pool_ops, 16, 20481, 2048),
], ids=lambda v: getattr(v, "__name__", str(v)).strip("_"))
def test_pool_ops_update_the_pools_in_place(on_chip, one_chip, ops, heads,
                                            pages, T):
    pool = _pool(bf16, pages, heads)
    fn, donated, shapes = ops(heads, pool) if ops is _decode_pool_ops \
        else ops(heads, pool, T)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    if ops is _prefill_pool_ops:
        # a prompt goes in a page a copy (kv_pages_write), K and V in
        # one kernel: no row-wise scatter is left
        (kernel,) = _kernel_names(text)
        assert "kv_pages_write" in kernel
        assert not re.findall(r"\bscatter\(", text)
    dims = ",".join(str(n) for n in pool[0])
    # no instruction COPIES something pool-shaped ...
    copies = re.findall(rf"= bf16\[{dims}\]\S* copy\(.*", text)
    assert not copies, copies
    # ... the pools come in row-major (pages major, lanes minor) ...
    entry = next(ln for ln in text.splitlines()
                 if "entry_computation_layout" in ln)
    assert entry.count(f"bf16[{dims}]{{2,1,0") == 2 * len(donated), entry
    # ... and what the program needs beside them is less than one pool
    pool_bytes = 2 * pages * _KVB * heads * _D
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


# The hybrid family (models/hybrid_lm.py) at the benchmark's widths:
# 128 streams, 64 query heads over 8 KV heads of 128, 64 KDA heads of
# 128, 40 held experts of 4096 x 1280, a 2048-token prompt.

# the paged kernel of the two serving cells' decode steps: doc's 48
# rows over 64-page tables of 20 heads x 64 (3073 pages), reason's 128
# rows over 160-page tables, 64 query heads over 8 KV heads of 128
_CELLS = {"doc": (48, 64, 3073, 20, 20, 64),
          "reason": (128, 160, 20481, 64, 8, 128)}


def _cell_shapes(cell, w=1):
    B, MB, pages, Hq, Hkv, D = _CELLS[cell]
    pool = ((pages, _KVB, Hkv * D), bf16)
    return (Hq, Hkv), [((B, w, Hq * D), bf16), pool, pool, ((B, MB), i32),
                       ((B,), i32)]


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_paged_attention_at_the_cells_shapes(on_chip, one_chip, cell):
    (Hq, Hkv), shapes = _cell_shapes(cell)
    _compile(lambda q, kp, vp, t, s: pk._paged_attention(
        q, kp, vp, (), t, s, Hq, kv_heads=Hkv), one_chip, *shapes)


# The layer-list family's windowed layers at the mixed cell's widths:
# 48 rows over 544-page tables, 28 query heads (no whole sublane tiles)
# over 4 KV heads of 128, a window of 4,096 keys; the windowed pools
# hold 258 pages a stream
_MIXED = (48, 544, 28, 4, 128, 4096)


def _kernel_names(text):
    """The Mosaic kernels of a compiled program, by their HLO names."""
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _kernel_short_names(text):
    """The same, as the benchmark's readers shorten an event's name:
    ``ROOT %moe_gmm_down.3`` is ``moe_gmm_down``."""
    return [re.sub(r"^(ROOT )?%|\.\d+$", "", name)
            for name in _kernel_names(text)]


@pytest.mark.parametrize("window", [0, _MIXED[-1]])
def test_paged_attention_at_the_mixed_cells_shapes(on_chip, one_chip,
                                                   window):
    B, MB, Hq, Hkv, D, W = _MIXED
    pages = 1 + B * (W // _KVB + 2 if window else MB)
    pool = ((pages, _KVB, Hkv * D), bf16)
    text = _compile(lambda q, kp, vp, t, s: pk._paged_attention(
        q, kp, vp, (), t, s, Hq, kv_heads=Hkv, window=window), one_chip,
        ((B, 1, Hq * D), bf16), pool, pool, ((B, MB), i32),
        ((B,), i32)).as_text()
    kernels = _kernel_names(text)
    # the windowed walk carries a name the full walk's readers (a
    # substring match on ``paged_attention``) do not find
    assert len(kernels) == 1 and ("paged_window" in kernels[0]) == bool(
        window) and ("paged_attention" in kernels[0]) != bool(window)


@pytest.mark.parametrize("T", [8192, 2048, 1024])
def test_flash_mha_window_at_the_mixed_cells_shapes(on_chip, one_chip, T):
    _, _, Hq, Hkv, D, W = _MIXED
    # query tiles of 1,024 rows over key tiles of 2,048 (one square tile
    # at T = 1,024), an edge tile walked in sub-blocks of 256
    assert pk._mha_window_tiles(T, W) == (1024, min(T, 2048), 256, 512)
    text = _compile(lambda q, k, v: pk.flash_mha_window(
        q, k, v, W, Hq, Hkv), one_chip, ((Hq, T, D), bf16),
        ((Hkv, T, D), bf16), ((Hkv, T, D), bf16)).as_text()
    kernels = _kernel_names(text)
    assert len(kernels) == 1 and "flash_fwd_window" in kernels[0]
    assert "flash_fwd_mha" not in kernels[0]


# The longdoc cell (trinity-large-ep16): 24 rows over 2,080-page tables
# (33,280 keys), 48 query heads — whole sublane tiles — over 8 KV heads
# of 128 (page rows of 1,024 lanes), a window of 4,096 keys; prompts of
# up to 32,768 rows
_LONGDOC = (24, 2080, 48, 8, 128, 4096)


@pytest.mark.parametrize("window", [0, _LONGDOC[-1]])
def test_paged_attention_at_the_longdoc_cells_shapes(on_chip, one_chip,
                                                     window):
    B, MB, Hq, Hkv, D, W = _LONGDOC
    # what the chunk is derived from (``_paged_pages_per_chunk``): 48
    # rows x 1,024 lanes held three times, 256 keys a chunk in two
    # buffers each of K and V; the whole table (24 x 2,080 int32 =
    # 199,680 B) is one SMEM operand
    hp, pages, vmem = pk._paged_pages_per_chunk(1, Hq, Hkv, D, _KVB, MB, 2, 2)
    assert (hp, pages, vmem) == (48, 16, 2736128)
    n_pages = 1 + B * (W // _KVB + 2 if window else MB)
    pool = ((n_pages, _KVB, Hkv * D), bf16)
    text = _compile(lambda q, kp, vp, t, s: pk._paged_attention(
        q, kp, vp, (), t, s, Hq, kv_heads=Hkv, window=window), one_chip,
        ((B, 1, Hq * D), bf16), pool, pool, ((B, MB), i32),
        ((B,), i32)).as_text()
    kernels = _kernel_names(text)
    assert len(kernels) == 1 and ("paged_window" in kernels[0]) == bool(
        window) and ("paged_attention" in kernels[0]) != bool(window)


@pytest.mark.parametrize("T, window", [(32768, _LONGDOC[-1]), (32768, 0),
                                       (4096, _LONGDOC[-1]), (4096, 0)])
def test_flash_kernels_at_the_longdoc_cells_shapes(on_chip, one_chip, T,
                                                   window):
    """The windowed layers' band and — ``window`` 0 — the global layer's
    every key, K and V at their 8 heads (no copy of them at 48)."""
    _, _, Hq, Hkv, D, _ = _LONGDOC
    compiled = _compile(lambda q, k, v: pk.flash_mha_window(
        q, k, v, window, Hq, Hkv), one_chip, ((Hq, T, D), bf16),
        ((Hkv, T, D), bf16), ((Hkv, T, D), bf16))
    kernels = _kernel_names(compiled.as_text())
    want, other = ("flash_fwd_window", "flash_fwd_mha") if window \
        else ("flash_fwd_mha", "flash_fwd_window")
    assert len(kernels) == 1 and want in kernels[0] \
        and other not in kernels[0]
    # nothing beside q, k, v and the output: no repeat, no lse
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_paged_attention_same_kernel_at_equal_heads(on_chip):
    """The guard on shared code: grouped queries are a parameter of the
    one kernel, not a second one — asked for with as many KV heads as
    query heads, ``_paged_attention`` traces what it traces unasked, at
    the doc cell's decode step and a 5-row verify window, by the text
    of the jaxpr; and the kernel keeps the name the benchmark's readers
    find it by."""
    texts = {}
    for w in (1, 5):
        _, shapes = _cell_shapes("doc", w)
        args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
        for kv_heads in (None, 20):
            text = str(jax.make_jaxpr(
                lambda q, kp, vp, t, s: pk._paged_attention(
                    q, kp, vp, (), t, s, 20, kv_heads=kv_heads))(*args))
            texts[w, kv_heads] = re.sub(r"at 0x[0-9a-f]+", "", text)
        assert texts[w, None] == texts[w, 20], w
        assert "name=paged_attention" in texts[w, None]
    assert texts[1, None] != texts[5, None]


@pytest.mark.parametrize("cell, T, window", [
    (_LONGDOC, 32768, 0), (_LONGDOC, 32768, _LONGDOC[-1]),
    (_LONGDOC, 8192, 0), (_MIXED, 8192, 0), (_MIXED, 8192, _MIXED[-1])])
def test_flash_kernels_take_the_prompts_length_at_the_cells_shapes(
        on_chip, one_chip, cell, T, window):
    """The prompt's length as a traced (1,) operand — the kernel's
    scalar prefetch — at the longdoc and mixed cells' heads and buckets:
    Mosaic takes the index maps that read it, the kernels keep the names
    the accepted readers match, nothing is copied beside q, k, v."""
    _, _, Hq, Hkv, D, _ = cell
    compiled = _compile(lambda q, k, v, n: pk.flash_mha_window(
        q, k, v, window, Hq, Hkv, lengths=n), one_chip, ((Hq, T, D), bf16),
        ((Hkv, T, D), bf16), ((Hkv, T, D), bf16), ((1,), i32))
    kernels = _kernel_short_names(compiled.as_text())
    assert kernels == ["flash_fwd_window" if window else "flash_fwd_mha"]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# the longctx cell (deepseek-v3-ep32): 32 rows, a table of 544 pages of
# 16, 128 heads of (128 + 64 | 128) over a latent row of 512 + 64 values
# held as 640 lanes
_LONGCTX = (32, 544, 128, 128, 64, 128, 512)
_MLA_ATTRS = {"num_heads": "128", "nope_dim": "128", "rope_dim": "64",
              "v_dim": "128", "kv_rank": "512", "scale": "0.13523",
              "rope_theta": "10000", "rope_factor": "40",
              "rope_orig_len": "4096", "rope_beta_fast": "32",
              "rope_beta_slow": "1"}


def _latent_pool(pages):
    from mxnet_tpu.kv_cache import latent_pool_shape

    return latent_pool_shape(pages, _KVB, 512, 64), bf16


@pytest.mark.parametrize("T", [8192, 1024])
def test_mla_flash_at_the_longctx_cells_shapes(on_chip, one_chip, T):
    _, _, H, n, r, dv, _ = _LONGCTX
    assert pk.mla_flash_enabled(H, n, r, dv)
    assert pk._mla_heads_per_step(H, r) == 4
    text = _compile(lambda q, qr, kv, kr: pk.mla_flash(
        q, qr, kv, kr, H, n, dv, 0.13523), one_chip,
        ((1, T, H * (n + r)), bf16), ((1, T, H * r), bf16),
        ((1, T, H * (n + dv)), bf16), ((1, T, r), bf16)).as_text()
    kernels = _kernel_names(text)
    # a name no accepted reader's substring finds
    assert len(kernels) == 1 and "mla_flash_fwd" in kernels[0]
    assert "flash_fwd_mha" not in kernels[0]


# the cell's four prefill buckets under the tiles `_mla_tiles` picks at
# the published widths: a step's blocks, double buffered, and its three
# states must fit the VMEM the kernel asks for — here, not on the chip
@pytest.mark.parametrize("T, tiles", [
    (8192, (1024, 2048, 256, 512, 4)), (4096, (1024, 2048, 256, 512, 4)),
    (2048, (1024, 2048, 256, 512, 4)), (1024, (1024, 1024, 256, 512, 4))])
def test_mla_flash_takes_the_prompts_length_at_the_longctx_cells_shapes(
        on_chip, one_chip, T, tiles):
    _, _, H, n, r, dv, _ = _LONGCTX
    assert pk._mla_tiles(T, H, n, r, dv) == tiles
    compiled = _compile(lambda q, qr, kv, kr, m: pk.mla_flash(
        q, qr, kv, kr, H, n, dv, 0.13523, lengths=m), one_chip,
        ((1, T, H * (n + r)), bf16), ((1, T, H * r), bf16),
        ((1, T, H * (n + dv)), bf16), ((1, T, r), bf16), ((1,), i32))
    assert _kernel_short_names(compiled.as_text()) == ["mla_flash_fwd"]
    # nothing is copied beside the operands but the rotary key spread
    # to its two lane tiles: T rows of 256 bfloat16 lanes
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (1 << 20) + T * 256 * 2


def test_mla_paged_decode_at_the_longctx_cells_shapes(on_chip, one_chip):
    B, MB, H, _, _, _, R = _LONGCTX
    pool = _latent_pool(1 + B * MB)
    lanes = pool[0][2]
    assert lanes == 640 and pk.mla_paged_enabled(H, lanes, R)
    text = _compile(lambda q, p, t, s: pk.mla_paged_decode(
        q, p, t, s, R, 0.13523), one_chip, ((B, H, lanes), bf16), pool,
        ((B, MB), i32), ((B,), i32)).as_text()
    kernels = _kernel_names(text)
    assert len(kernels) == 1 and "mla_paged_decode" in kernels[0]
    assert "paged_attention" not in kernels[0]


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_mla_ops_update_the_latent_pool_in_place(on_chip, one_chip, phase):
    """The two attention paths over the longctx cell's pool, donated: the
    page write (``mla_latent_write``) and the decode step's row scatter
    leave no pool-shaped copy."""
    from mxnet_tpu.ops.registry import OpContext, get_op

    B, MB, H, n, r, dv, R = _LONGCTX
    pool = _latent_pool(1 + B * MB)
    T = 4096
    ctx = OpContext(is_train=False, rng=None)
    if phase == "prefill":
        def run(q, kv, c, kr, pool, table, lengths, positions):
            return get_op("MLAPrefillAttention").compute(
                ctx, _MLA_ATTRS, [q, kv, c, kr, pool, table, lengths,
                                  positions], [])

        shapes = [((1, T, H * (n + r)), bf16), ((1, T, H * (n + dv)), bf16),
                  ((1, T, R), bf16), ((1, T, r), bf16), pool,
                  ((1, T // _KVB), i32), ((1,), i32), ((1, T), i32)]
        want = {"mla_flash_fwd", "mla_latent_write"}
    else:
        def run(qa, qr, c, kr, pool, table, lengths, positions):
            return get_op("MLAPagedDecode").compute(
                ctx, _MLA_ATTRS, [qa, qr, c, kr, pool, table, lengths,
                                  positions], [])

        shapes = [((B, 1, H * R), bf16), ((B, 1, H * r), bf16),
                  ((B, 1, R), bf16), ((B, 1, r), bf16), pool,
                  ((B, MB), i32), ((B,), i32), ((B, 1), i32)]
        want = {"mla_paged_decode"}
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(run, donate_argnums=(4,)).lower(*args).compile()
    text = compiled.as_text()
    names = _kernel_names(text)
    assert {k for k in want if any(k in nm for nm in names)} == want, names
    dims = ",".join(str(v) for v in pool[0])
    copies = re.findall(rf"= bf16\[{dims}\]\S* copy\(.*", text)
    assert not copies, copies


def test_lstm_scan_ptb(on_chip, one_chip):
    # the PTB LSTM's shape: T=32, batch 32, hidden 200
    T, B, H = 32, 32, 200
    _compile(pk.lstm_scan, one_chip, ((T, B, 4 * H), f32), ((B, H), f32),
             ((B, H), f32), ((H, 4 * H), f32))


def test_nms_ssd300_anchors(on_chip, one_chip):
    _compile(functools.partial(pk.nms, nms_threshold=0.45,
                               force_suppress=False),
             one_chip, ((1, 8732, 6), f32))


# ---------------------------------------------------------------------------
# four chips: the dp=2 x tp=2 mesh of `chip_smoke.py --chips 4`
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def plan(topo):
    return parallel.MeshPlan(topo.devices, dp=2, tp=2,
                             rules=transformer.lm_partition_rules())


def _qkv_on(plan):
    # the fused QKV projection's output under the LM rules table
    return jax.ShapeDtypeStruct(
        *_QKV, sharding=NamedSharding(plan.mesh, P("dp", None, "tp")))


def test_bare_kernel_cannot_be_partitioned(on_chip, plan):
    """Why the packed kernel shard_maps itself under a plan: the
    compiler refuses to partition a Mosaic kernel."""
    with pytest.raises(NotImplementedError, match="automatically"):
        jax.jit(lambda x: pk.flash_mha_packed(x, 12, causal=True)).lower(
            _qkv_on(plan)).compile()


def test_flash_mha_packed_grad_on_dp2_tp2_mesh(on_chip, plan):
    def loss(x):
        with parallel.tracing_for(plan):
            out = attention._flash_mha_packed_on_plan(x, 12, True)
        return out.astype(f32).sum()

    text = jax.jit(jax.grad(loss)).lower(_qkv_on(plan)).compile().as_text()
    assert "tpu_custom_call" in text
    # each device gathers the tp peer's half of qkv before its kernel:
    # the collectives are in the program and the report reads them
    report = hlo.overlap_report(text)
    assert any(k.startswith("all-gather") or k.startswith("all-to-all")
               or k.startswith("collective-permute")
               for k in report["collectives"]), report
