"""``tests/test_tpu_compile.py``'s second half: the hybrid family's
kernels (``ops/pallas_hybrid.py`` — KDA, Mamba-2, retention, CCA, the
experts' grouped matmuls) compiled for the same DESCRIBED TPU v5e, with
that file's fixtures and helpers.  A file of its own so that no file of
the suite is a worker's whole run."""
import re

import jax
import jax.numpy as jnp
import pytest

from test_tpu_compile import (_KVB, _compile, _kernel_names,  # noqa: F401
                              _kernel_short_names, bf16, f32, i32, on_chip,
                              one_chip, topo)


def _hybrid(monkeypatch):
    from mxnet_tpu.ops import pallas_hybrid as ph

    monkeypatch.setattr(ph, "_interpret", lambda: False)
    return ph


def test_kda_step(on_chip, one_chip, monkeypatch):
    ph = _hybrid(monkeypatch)
    row = ((128, 64, 128), f32)
    _compile(ph.kda_step, one_chip, row, row, row, row, row,
             ((129, 64, 128, 128), f32), ((128,), i32))


@pytest.mark.parametrize("T", [2048, 1024])
def test_kda_chunk(on_chip, one_chip, monkeypatch, T):
    """The chunk form at both prefill buckets of the reason cell: three
    Mosaic kernels, each named so that ``kda_chunk_roofline.serve``
    (a substring match) reads it."""
    ph = _hybrid(monkeypatch)
    text = _compile(ph.kda_chunk, one_chip, ((1, T, 3 * 64 * 128), bf16),
                    ((1, T, 64 * 128), f32), ((1, T, 64), f32)).as_text()
    kernels = _kernel_names(text)
    assert len(kernels) == 3, kernels
    assert all("kda_chunk" in name for name in kernels), kernels


@pytest.mark.parametrize("rows, tm", [(1024 + 40 * 16, 16),
                                      (16384 + 40 * 128, 128)])
def test_moe_gmm(on_chip, one_chip, monkeypatch, rows, tm):
    ph = _hybrid(monkeypatch)
    tiles = [((rows // tm,), i32), ((1,), i32)]
    _compile(lambda x, g, u, te, nu: ph.moe_gmm_gate_up(x, g, u, te, nu,
                                                        tm),
             one_chip, ((rows, 4096), bf16), ((40, 4096, 1280), bf16),
             ((40, 4096, 1280), bf16), *tiles)
    _compile(lambda x, w, te, nu: ph.moe_gmm_down(x, w, te, nu, tm),
             one_chip, ((rows, 1280), bf16), ((40, 1280, 4096), bf16),
             *tiles)


@pytest.mark.parametrize("rows, tm", [(288 + 64 * 16, 16),
                                      (49152 + 64 * 128, 128)])
def test_moe_gmm_relu_at_the_mixed_cells_shapes(on_chip, one_chip,
                                                monkeypatch, rows, tm):
    """64 held experts of 2560 x 768, a 48-row decode step's and an
    8,192-token prompt's pairs; the ReLU gate is its own kernel, under a
    name the ``moe_gmm`` readers find."""
    ph = _hybrid(monkeypatch)
    text = _compile(
        lambda x, g, u, te, nu: ph.moe_gmm_gate_up(x, g, u, te, nu, tm,
                                                   "relu"),
        one_chip, ((rows, 2560), bf16), ((64, 2560, 768), bf16),
        ((64, 2560, 768), bf16), ((rows // tm,), i32),
        ((1,), i32)).as_text()
    assert "moe_gmm_gate_up_relu" in text


# the four expert cells' largest prefill: tokens, top_k, d, the experts'
# width, experts held, the gate
_MOE_PREFILLS = {
    "rag": (2048, 10, 4096, 768, 36, "silu"),
    "mixed": (8192, 6, 2560, 768, 64, "relu"),
    "reason": (2048, 8, 4096, 1280, 40, "silu"),
    "longctx": (8192, 8, 7168, 2048, 8, "silu"),
}


@pytest.mark.parametrize("cell", list(_MOE_PREFILLS))
def test_moe_gmm_by_index_at_the_cells_prefill_shapes(on_chip, one_chip,
                                                      monkeypatch, cell):
    """The kernels that take a prompt's rows by index — ``gate_up`` that
    copies the token rows of a tile itself, ``down`` that leaves
    slabs, the combine that copies a token's slabs, weighs and adds them — at
    each cell's worst-case rows (every pair here + a tile of padding an
    expert), each under the name the readers match."""
    ph = _hybrid(monkeypatch)
    n, k, d, w, held, act = _MOE_PREFILLS[cell]
    tm = 128
    rows = n * min(k, held) + held * tm
    tiles = [((rows // tm,), i32), ((1,), i32)]
    text = _compile(
        lambda x, g, u, te, nu, rt: ph.moe_gmm_gate_up(
            x, g, u, te, nu, tm, act, row_token=rt),
        one_chip, ((n, d), bf16), ((held, d, w), bf16), ((held, d, w), bf16),
        *tiles, ((rows,), i32)).as_text()
    assert _kernel_short_names(text) == [
        "moe_gmm_gate_up" + ("" if act == "silu" else "_" + act)]
    text = _compile(
        lambda x, wd, te, nu: ph.moe_gmm_down(x, wd, te, nu, tm, slabs=True),
        one_chip, ((rows, w), bf16), ((held, w, d), bf16), *tiles).as_text()
    assert _kernel_short_names(text) == ["moe_gmm_down"]
    text = _compile(
        lambda ys, pr, here, wts: ph.moe_gmm_combine(ys, pr, here, wts, d,
                                                     bf16),
        one_chip, ((rows, ph.slab_rows(d), 128), f32), ((n, k), i32),
        ((n, k), jnp.bool_), ((n, k), f32)).as_text()
    assert _kernel_short_names(text) == ["moe_gmm_combine"]


def test_moe_ffn_prefill_program_by_index(on_chip, one_chip, monkeypatch):
    """The rag cell's 2,048-token ``MoEFFN`` node as the chip's compiler
    leaves it: the three ``moe_gmm`` kernels, no (M, d) array of
    dispatched rows and no (N, k, d) array of gathered outputs in any
    type — the worst case is 25,088 rows of 4,096."""
    from mxnet_tpu.ops.registry import OpContext, get_op

    _hybrid(monkeypatch)
    n, k, d, w, held, _ = _MOE_PREFILLS["rag"]
    rows = n * min(k, held) + held * 128

    def ffn(*inputs):
        return get_op("MoEFFN").compute(
            OpContext(is_train=False, rng=None),
            {"top_k": str(k), "score": "softmax_topk", "count": "1"},
            list(inputs), [])

    text = _compile(
        ffn, one_chip, ((1, n, d), bf16), ((2 * held, d), f32),
        ((held, d, w), bf16), ((held, d, w), bf16), ((held, w, d), bf16),
        ((1,), i32), ((4,), i32)).as_text()
    assert sorted(_kernel_short_names(text)) == [
        "moe_gmm_combine", "moe_gmm_down", "moe_gmm_gate_up"]
    for shape in (f"[{rows},{d}]", f"[{n},{k},{d}]", f"[{n * k},{d}]"):
        assert shape not in text, shape


def test_hybrid_decode_slots_update_in_place(on_chip, one_chip,
                                             monkeypatch):
    """A decode step's slot pools — the conv tail, lane-dense, and the
    KDA state through the kernel's aliased operand — are written where
    they lie: no pool-shaped copy, next to nothing beside them."""
    from mxnet_tpu.kv_cache import conv_tail_shape, state_pool_shape
    from mxnet_tpu.ops.registry import OpContext, get_op

    _hybrid(monkeypatch)
    H, D, K, B = 64, 128, 4, 128
    state = (state_pool_shape(129, (H, D, D)), f32)
    tail = (conv_tail_shape(129, K, 3 * H * D), f32)

    def step(x, w, decay, beta, a_log, dt, tail_pool, state_pool, slots,
             lengths):
        ctx = OpContext(is_train=False, rng=None)
        c, tail_pool = get_op("ShortConv").compute(
            ctx, {"step": "True"}, [x, w, tail_pool, slots, lengths], [])
        o, state_pool = get_op("KDAStep").compute(
            ctx, {"num_heads": str(H), "neg_eigval": "True"},
            [c, decay, beta, a_log, dt, state_pool, slots, lengths], [])
        return o, tail_pool, state_pool

    shapes = [((B, 1, 3 * H * D), bf16), ((3 * H * D, K), bf16),
              ((B, 1, H * D), bf16), ((B, 1, H), bf16), ((H,), f32),
              ((H * D,), f32), tail, state, ((B,), i32), ((B,), i32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(step, donate_argnums=(6, 7)).lower(*args).compile()
    text = compiled.as_text()
    for shape, _ in (state, tail):
        dims = ",".join(str(n) for n in shape)
        copies = re.findall(rf"= f32\[{dims}\]\S* copy\(.*", text)
        assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_mamba2_kernels_granite_cell(on_chip, one_chip, monkeypatch):
    # granite-4.0-h-small: 128 heads, a state of (64, 128) a head, one
    # group; a 64-row decode step over 65 slots and a 2048-token prompt
    from mxnet_tpu.kv_cache import state_pool_shape

    ph = _hybrid(monkeypatch)
    H, P, N, B, T = 128, 64, 128, 64, 2048
    step = jax.jit(ph.mamba2_step, donate_argnums=(4,)).lower(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((B, H, P), f32), ((B, H), f32), ((B, N), f32), ((B, N), f32),
            (state_pool_shape(B + 1, (H, P, N)), f32), ((B,), i32))]
    ).compile()
    text = step.as_text()
    assert "tpu_custom_call" in text
    dims = ",".join(str(n) for n in state_pool_shape(B + 1, (H, P, N)))
    assert not re.findall(rf"= f32\[{dims}\]\S* copy\(.*", text)
    _compile(ph.mamba2_chunk, one_chip, ((1, T, H * P), bf16),
             ((1, T, H * P + 2 * N), bf16), ((1, T, H), f32))


def test_retention_kernels_gen_cell(on_chip, one_chip, monkeypatch):
    # brumby-14b-pp4: 40 query heads over 8 KV heads of 128, a packed
    # state of 8,320 rows of 128 lanes a KV head; a 12-row decode step
    # over 13 slots and a 2048-token prompt in chunks of 256
    from mxnet_tpu.kv_cache import state_pool_shape
    from mxnet_tpu.ops.hybrid import retention_rows

    ph = _hybrid(monkeypatch)
    H, J, D, B, T = 40, 8, 128, 12, 2048
    R = retention_rows(D)
    assert R == 8320
    pool = state_pool_shape(B + 1, (J, R, D))
    step = jax.jit(
        lambda q, k, v, a, s, z, sl: ph.retention_step(q, k, v, a, s, z,
                                                       sl),
        donate_argnums=(4, 5)).lower(*[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
                ((B, J, H // J, D), f32), ((B, J, D), f32), ((B, J, D), f32),
                ((B, J), f32), (pool, f32),
                (state_pool_shape(B + 1, (J, D, D)), f32), ((B,), i32))]
    ).compile()
    text = step.as_text()
    assert _kernel_short_names(text) == ["retention_step"]
    # both pools are updated in place: no copy of either beside them
    dims = ",".join(str(n) for n in pool)
    assert not re.findall(rf"= f32\[{dims}\]\S* copy\(.*", text)
    compiled = _compile(
        ph.retention_chunk,
        one_chip, ((1, T, H * D), bf16), ((1, T, J * D), bf16),
        ((1, T, J * D), bf16), ((1, T, J), f32), ((1,), i32))
    assert _kernel_short_names(compiled.as_text()) == ["retention_chunk"]


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_cca_layer_rollout_cell(on_chip, one_chip, monkeypatch, phase):
    """One whole layer of the rollout cell's programs at its widths (8
    query heads over 2 KV heads of 128 inside the latent, 16 experts of
    2,048 chosen top-1 by the MLP router; 128 rows over 96-page tables,
    129 slots, a prompt of 1,024): the attention is the grouped kernels
    the other cells run, the latent's mixing is XLA's, and the three
    pools a layer — K and V pages of 256 lanes, the tail — are written
    where they lie."""
    from mxnet_tpu.executor import build_graph_fn
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    _hybrid(monkeypatch)
    B, MB, T, V, d = 128, 96, 1024, 1024, 2048
    layer = {"mixer": {"kind": "cca", "heads": 8, "kv_heads": 2,
                       "head_dim": 128, "conv": [2, 2], "rope_theta": 5e6,
                       "rotary_dim": 64},
             "ffn": {"kind": "moe", "experts": 16, "top_k": 1,
                     "width": 2048, "score": "softmax", "select_bias": True,
                     "router": {"kind": "mlp", "hidden": 256,
                                "carry": True}}}
    spec = HybridSpec(V, d, [layer] * 2, tied_head=True,
                      learned_residual=True)
    pools = spec.pools(1 + B * MB, _KVB, B + 1, bf16)
    assert [(n, s) for n, s, _, _ in pools[:3]] == [
        ("layer0_kpool", (1 + B * MB, 16, 256)),
        ("layer0_vpool", (1 + B * MB, 16, 256)),
        ("layer0_tail", (B + 1, 8, 384))]
    fn = build_graph_fn(spec.symbol(phase))
    rows, cols = (B, 1) if phase == "decode" else (1, T)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    feeds = dict(data=sds((rows, cols), i32), positions=sds((rows, cols), i32),
                 lengths=sds((rows,), i32), slots=sds((rows,), i32),
                 block_table=sds((rows, MB if phase == "decode"
                                  else T // _KVB), i32))
    hd, kd, n = 1024, 256, 256
    shapes = dict(
        norm1_gamma=(d,), norm2_gamma=(d,), q_weight=(hd, d),
        k_weight=(kd, d), v1_weight=(128, d), v2_weight=(128, d),
        o_weight=(d, hd), mix_conv0_weight=(hd + kd, 2),
        mix_conv1_weight=(10, 2, 128, 128), res1_scales=(4, d),
        res2_scales=(4, d), experts_gate_weight=(16, d, 2048),
        experts_up_weight=(16, d, 2048), experts_down_weight=(16, 2048, d))
    router = dict(
        qk_norm_temperature=(2,), router_down_weight=(n, d),
        router_carry_gamma=(n,), router_norm_gamma=(n,),
        router_1_weight=(n, n), router_2_weight=(n, n),
        router_3_weight=(16, n), router_bias=(16,))
    params = {"tok_embed_weight": sds((V, d), bf16),
              "final_norm_gamma": sds((d,), bf16)}
    for i in range(2):
        params.update({f"layer{i}_{k}": sds(v, bf16)
                       for k, v in shapes.items()})
        params.update({f"layer{i}_{k}": sds(v, f32)
                       for k, v in router.items()
                       if i or k != "router_carry_gamma"})
    names = [n for n, _, _, _ in pools]
    key = jax.random.PRNGKey(0)

    def run(args, state):
        outs, _ = fn(dict(args, **dict(zip(names, state))), {}, key, False)
        return outs

    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        dict(params, **feeds),
        tuple(sds(s, dt) for _, s, dt, _ in pools)).compile()
    text = compiled.as_text()
    want = {"decode": {"paged_attention", "moe_gmm_gate_up", "moe_gmm_down",
                       "slot_rows_write"},
            # a prompt of 1,024 at one expert a token: 64 rows an expert,
            # the MXU's tiles and the slabs' combine (hybrid._tile_rows)
            "prefill": {"flash_fwd_mha", "kv_pages_write", "moe_gmm_gate_up",
                        "moe_gmm_down", "moe_gmm_combine",
                        "slot_rows_write"}}[phase]
    assert {re.sub(r"_(silu|relu)$", "", k)
            for k in _kernel_short_names(text)} == want
    for _, shape, dt, _ in pools[:3]:
        dims = ",".join(str(x) for x in shape)
        ty = "f32" if dt == "float32" else "bf16"
        copies = re.findall(rf"= {ty}\[{dims}\]\S* copy\(.*", text)
        assert not copies, copies[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
