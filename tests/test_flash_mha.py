import numpy as np
import jax, jax.numpy as jnp
import pytest
import mxnet_tpu as mx
from mxnet_tpu.ops import attention as att, pallas_kernels as pk

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 384, 3, 64), (1, 256, 2, 128)])
def test_flash_mha_parity(monkeypatch, causal, shape):
    monkeypatch.setenv("MXNET_PALLAS", "1")
    assert pk.enabled()
    B, T, H, D = shape
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B,T,H,D).astype(np.float32))
    k = jnp.asarray(rng.randn(B,T,H,D).astype(np.float32))
    v = jnp.asarray(rng.randn(B,T,H,D).astype(np.float32))
    def f_kern(q,k,v): return att.blockwise_attention(q,k,v,causal=causal,block_size=256)
    def f_lax(q,k,v):
        o,m,l = att._blockwise_attention_partial_lax(q,k,v,causal,256,0)
        return att.normalize_attention_state(o,m,l,q.dtype)
    ok, ol = f_kern(q,k,v), f_lax(q,k,v)
    assert float(jnp.abs(ok-ol).max()) < 1e-5
    gk = jax.grad(lambda q,k,v: jnp.sum(jnp.sin(f_kern(q,k,v))), argnums=(0,1,2))(q,k,v)
    gl = jax.grad(lambda q,k,v: jnp.sum(jnp.sin(f_lax(q,k,v))), argnums=(0,1,2))(q,k,v)
    for a, b in zip(gk, gl):
        assert float(jnp.abs(a-b).max()) < 1e-5

def _lax_packed(qkv, H, causal):
    B, T, HD3 = qkv.shape
    D = HD3 // (3 * H)
    q, k, v = (jnp.reshape(x, (B, T, H, D)) for x in jnp.split(qkv, 3, -1))
    o, m, l = att._blockwise_attention_partial_lax(q, k, v, causal, 256, 0)
    return jnp.reshape(att.normalize_attention_state(o, m, l, qkv.dtype),
                       (B, T, H * D))


def _packed_parity(monkeypatch, shape, tiles, causal):
    """Interpreted parity of the packed forward and its three gradients
    against the lax body, under the tile schedule ``tiles`` = (block_q,
    block_k, sub, lanes of a head group) — the private override: the
    chooser patched."""
    monkeypatch.setenv("MXNET_PALLAS", "1")
    monkeypatch.setattr(pk, "_mhap_tiles", lambda t, hd, d: tiles)
    B, T, H, D = shape
    qkv = jnp.asarray(np.random.RandomState(1).randn(B, T, 3 * H * D)
                      .astype(np.float32))
    f_kern = lambda x: pk.flash_mha_packed(x, H, causal=causal)
    f_lax = lambda x: _lax_packed(x, H, causal)
    assert float(jnp.abs(f_kern(qkv) - f_lax(qkv)).max()) < 1e-5
    gk = jax.grad(lambda x: jnp.sum(jnp.sin(f_kern(x))))(qkv)
    gl = jax.grad(lambda x: jnp.sum(jnp.sin(f_lax(x))))(qkv)
    for part, (a, b) in enumerate(zip(jnp.split(gk, 3, -1),
                                      jnp.split(gl, 3, -1))):
        assert float(jnp.abs(a - b).max()) < 1e-5, "dq dk dv".split()[part]


@pytest.mark.parametrize("causal", [False, True])
def test_packed_qkv_parity(monkeypatch, causal):
    # tile 256 over T = 384: a padded edge, one sub-block a tile
    _packed_parity(monkeypatch, (2, 384, 3, 64), (256, 256, 256, 192), causal)


@pytest.mark.parametrize("sub", [128, 256])
@pytest.mark.parametrize("T", [384, 1024, 1536])
def test_packed_subtile_walk_parity(monkeypatch, T, sub):
    """A tile the diagonal crosses is walked in sub-blocks, each against
    the columns it sees: T = 384 is one padded tile, 1024 two rows of
    tiles with one under the diagonal, 1536 three."""
    _packed_parity(monkeypatch, (1, T, 2, 64), (512, 512, sub, 128), True)


@pytest.mark.parametrize("tiles", [(512, 256, 128, 128),
                                   (256, 512, 128, 128)])
def test_packed_subtile_walk_parity_rectangular(monkeypatch, tiles):
    """block_q != block_k: the diagonal crosses a tile at more than one
    offset, and the walk is unrolled for each."""
    assert len(pk._crossing_offsets(*tiles[:2])) == 2
    _packed_parity(monkeypatch, (1, 1000, 2, 64), tiles, True)


@pytest.mark.parametrize("case", ["one_tile", "pairs_of_four", "d128",
                                  "d32_not_lane_tiles"])
def test_packed_head_groups_parity(monkeypatch, case):
    """A grid step holds a lane tile's heads (a pair at D = 64), H·D
    where heads do not fill lane tiles; T in one tile carries no
    state."""
    shape, tiles = {
        "one_tile": ((1, 1024, 2, 64), (1024, 1024, 256, 128)),
        "pairs_of_four": ((2, 640, 4, 64), (256, 256, 128, 128)),
        "d128": ((1, 640, 3, 128), (256, 256, 128, 128)),
        "d32_not_lane_tiles": ((1, 384, 2, 32), (256, 256, 128, 64)),
    }[case]
    assert pk._mhap_tiles(shape[1], shape[2] * shape[3], shape[3])[3] \
        == tiles[3]
    _packed_parity(monkeypatch, shape, tiles, True)


def test_packed_one_padded_tile_without_the_causal_mask(monkeypatch):
    """T = 1000 under the chosen schedule: one tile, no state carried,
    the padded edge under its own mask."""
    shape = (1, 1000, 2, 64)
    tiles = pk._mhap_tiles(1000, 128, 64)
    assert tiles[:2] == (1024, 1024)
    _packed_parity(monkeypatch, shape, tiles, False)


def test_packed_tiles_come_from_the_shape():
    """The chooser sees (T, H·D, D) and nothing else; what it picks
    divides, fits and pads least."""
    for t, hd, d in [(1024, 1280, 64), (1024, 1024, 64), (4096, 768, 64),
                     (1536, 768, 64), (384, 192, 64), (100, 128, 128),
                     (2048, 4096, 128), (1024, 640, 64), (512, 96, 32)]:
        bq, bk, sub, lanes = pk._mhap_tiles(t, hd, d)
        assert bq == bk and bq % sub == 0 and sub in (128, 256), (t, hd)
        assert hd % lanes == 0 and lanes % d == 0, (t, hd, d, lanes)
        assert lanes % 128 == 0 or lanes == hd, (t, hd, d, lanes)
        assert bq * lanes * 48 <= pk._VMEM_LIMIT, (t, hd)
        assert (-t) % bq < 128, (t, hd, bq)
    # one tile up to 1,024 positions, whatever the width; 1,024s beyond,
    # 512s where those pad less
    assert pk._mhap_tiles(1024, 1280, 64) == pk._mhap_tiles(1024, 1024, 64) \
        == (1024, 1024, 256, 128)
    assert pk._mhap_tiles(4096, 768, 64)[0] == 1024
    assert pk._mhap_tiles(1536, 768, 64)[0] == 512


def test_packed_scores_counter(monkeypatch):
    """Each build records its schedule and the scores it computes over
    those the mask keeps: 1.50 for the masked 512 tile at T = 1024,
    1.125 walked in 128s."""
    assert pk._mhap_scores(1024, 512, 512, 512, True) == (786432, 524800)
    assert pk._mhap_scores(1024, 512, 512, 128, True)[0] == 589824
    assert pk._mhap_scores(1024, 1024, 1024, 256, True)[0] == 655360
    assert pk._mhap_scores(1024, 128, 128, 128, True)[0] == 589824
    assert pk._mhap_scores(1000, 512, 512, 512, False) == (1 << 20, 10 ** 6)
    monkeypatch.setenv("MXNET_PALLAS", "1")
    monkeypatch.setattr(pk, "_mhap_tiles",
                        lambda t, hd, d: (256, 256, 128, 64))
    mx.profiler.reset_metrics()
    pk.flash_mha_packed(jnp.zeros((1, 512, 3 * 64), jnp.float32), 1,
                        causal=True)
    g = mx.profiler.metrics_summary()["gauges"]
    assert (g["flash.tile_q"], g["flash.tile_k"], g["flash.subtile"],
            g["flash.head_group_lanes"]) == (256, 256, 128, 64)
    done, needed = pk._mhap_scores(512, 256, 256, 128, True)
    assert g["flash.scores_computed_over_needed"] == done / needed
    # the next program that holds the kernel says so again, though the
    # jitted body is not traced again
    mx.profiler.reset_metrics()
    pk.flash_mha_packed(jnp.zeros((1, 512, 3 * 64), jnp.float32), 1,
                        causal=True)
    assert mx.profiler.metrics_summary()["gauges"]["flash.subtile"] == 128


def test_packed_trace_for_the_chip_does_not_answer_an_interpreted_call(
        monkeypatch):
    """The custom_vjp is jitted and jit keeps its trace: one made with
    the Mosaic call (as ``tests/test_tpu_compile.py`` makes them, in the
    same worker) must not be handed to an interpreted call at the same
    shape."""
    monkeypatch.setattr(pk, "_mhap_tiles",
                        lambda t, hd, d: (128, 128, 128, 128))
    x = jnp.ones((1, 128, 3 * 128), jnp.float32)
    f = lambda x: pk.flash_mha_packed(x, 2, causal=True)
    with monkeypatch.context() as m:
        m.setattr(pk, "_interpret", lambda: False)
        assert "interpret=False" in str(jax.make_jaxpr(f)(x))
    monkeypatch.setenv("MXNET_PALLAS", "1")
    assert float(jnp.abs(f(x) - _lax_packed(x, 2, True)).max()) < 1e-5


def test_packed_tiles_refused_by_name(monkeypatch):
    monkeypatch.setattr(pk, "_mhap_tiles",
                        lambda t, hd, d: (512, 384, 128, 64))
    with pytest.raises(ValueError, match="must divide"):
        pk.flash_mha_packed(jnp.zeros((1, 512, 192), jnp.float32), 1)


def test_softmax_ce_loss_head():
    """SoftmaxCELoss: forward loss parity with SoftmaxOutput-derived CE
    and the (p - onehot) backward, without materializing probs."""
    rng = np.random.RandomState(3)
    B, T, V = 2, 8, 32
    logits = rng.randn(B, T, V).astype(np.float32)
    label = rng.randint(0, V, size=(B, T)).astype(np.float32)
    sym = mx.sym.SoftmaxCELoss(mx.sym.Variable("data"),
                               mx.sym.Variable("label"))
    ld = mx.nd.array(logits)
    gd = mx.nd.zeros(logits.shape)
    ex = sym.bind(mx.cpu(), {"data": ld, "label": mx.nd.array(label)},
                  args_grad={"data": gd})
    out = ex.forward(is_train=True)[0].asnumpy()
    # reference CE
    x = logits - logits.max(-1, keepdims=True)
    lse = np.log(np.exp(x).sum(-1)) + logits.max(-1)
    ll = np.take_along_axis(logits, label[..., None].astype(int), -1)[..., 0]
    np.testing.assert_allclose(out, lse - ll, rtol=1e-5, atol=1e-5)
    ex.backward(out_grads=[mx.nd.ones(out.shape)])
    p = np.exp(logits - lse[..., None])
    onehot = np.eye(V)[label.astype(int)]
    np.testing.assert_allclose(gd.asnumpy(), p - onehot, rtol=1e-4,
                               atol=1e-5)


def test_transformer_ce_head_trains():
    import mxnet_tpu.models as models
    sym = models.transformer_lm(vocab_size=64, seq_len=16, num_layers=1,
                                num_heads=2, d_model=32, head="ce")
    rng = np.random.RandomState(0)
    X = rng.randint(1, 64, size=(4, 16)).astype(np.float32)
    Y = rng.randint(1, 64, size=(4, 16)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=4, label_name="softmax_label")

    class MeanLoss(mx.metric.EvalMetric):
        def __init__(self):
            super().__init__("mean_loss")

        def update(self, labels, preds):
            self.sum_metric += float(preds[0].asnumpy().mean())
            self.num_inst += 1

    mod = mx.mod.Module(sym, context=mx.cpu())
    mx.random.seed(0)
    losses = []
    mod.fit(it, num_epoch=30, optimizer="adam",
            optimizer_params={"learning_rate": 1e-2},
            initializer=mx.initializer.Xavier(), eval_metric=MeanLoss(),
            batch_end_callback=lambda p: losses.append(
                p.eval_metric.get()[1]))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_softmax_ce_loss_ignore_label():
    rng = np.random.RandomState(4)
    logits = rng.randn(2, 6, 16).astype(np.float32)
    label = rng.randint(1, 16, size=(2, 6)).astype(np.float32)
    label[0, 2] = 0  # padding
    sym = mx.sym.SoftmaxCELoss(mx.sym.Variable("data"),
                               mx.sym.Variable("label"),
                               use_ignore=True, ignore_label=0)
    ld, gd = mx.nd.array(logits), mx.nd.zeros(logits.shape)
    ex = sym.bind(mx.cpu(), {"data": ld, "label": mx.nd.array(label)},
                  args_grad={"data": gd})
    out = ex.forward(is_train=True)[0].asnumpy()
    assert out[0, 2] == 0.0 and out[0, 3] > 0.0
    ex.backward(out_grads=[mx.nd.ones(out.shape)])
    g = gd.asnumpy()
    np.testing.assert_allclose(g[0, 2], 0.0, atol=1e-8)
    assert np.abs(g[0, 3]).max() > 0


def test_qkv_packing_validation():
    """_qkv_infer rejects a last dim that is a multiple of 3 but not of
    3*num_heads (the weaker % 3 check waved these through), and a
    zero-width qkv; the message names the expected packing."""
    sym = mx.sym.QKVSelfAttention(mx.sym.Variable("qkv"), num_heads=4)
    with pytest.raises(mx.base.MXNetError, match=r"3\*num_heads\*d_head"):
        sym.infer_shape(qkv=(2, 8, 6))  # 6 % 3 == 0 but 6 % 12 != 0
    with pytest.raises(mx.base.MXNetError, match="positive multiple"):
        sym.infer_shape(qkv=(2, 8, 0))  # d_head = 0
    _, out, _ = sym.infer_shape(qkv=(2, 8, 24))
    assert tuple(out[0]) == (2, 8, 8)


@pytest.mark.parametrize("mesh", ["dp2_tp2", "dp4", "tp_indivisible"])
def test_packed_qkv_on_a_mesh_plan_matches_one_device(monkeypatch, mesh):
    """Under a MeshPlan the packed kernel shard_maps itself (a Mosaic
    kernel cannot be partitioned by the compiler): batch over 'dp',
    heads over 'tp' when tp divides them — each device cutting ITS
    heads' q/k/v spans out of the packed dim — and the result and the
    qkv gradient equal the one-device kernel's."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu import parallel
    from mxnet_tpu.models import transformer

    monkeypatch.setenv("MXNET_PALLAS", "1")
    B, T, H, D = 4, 256, 4, 64
    dp, tp = {"dp2_tp2": (2, 2), "dp4": (4, 1), "tp_indivisible": (1, 3)}[mesh]
    plan = parallel.MeshPlan(jax.devices()[:dp * tp], dp=dp, tp=tp,
                             rules=transformer.lm_partition_rules())
    rng = np.random.RandomState(2)
    qkv = jnp.asarray(rng.randn(B, T, 3 * H * D).astype(np.float32))

    monkeypatch.setattr(pk, "_mhap_tiles",
                        lambda t, hd, d: (128, 128, 128, 128))

    def one(x):
        return pk.flash_mha_packed(x, H, causal=True)

    def meshed(x):
        with parallel.tracing_for(plan):
            return att._flash_mha_packed_on_plan(x, H, True)

    x_mesh = jax.device_put(qkv, NamedSharding(plan.mesh, P("dp", None, None)))
    got = jax.jit(meshed)(x_mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(one(qkv)),
                               rtol=1e-6, atol=1e-6)
    if mesh == "dp2_tp2":  # heads really are split over tp
        assert got.sharding.spec == P("dp", None, "tp")
    g_one = jax.grad(lambda x: jnp.sum(jnp.sin(one(x))))(qkv)
    g_mesh = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(meshed(x)))))(x_mesh)
    np.testing.assert_allclose(np.asarray(g_mesh), np.asarray(g_one),
                               rtol=1e-5, atol=1e-5)
    # outside a traced plan: the plain kernel
    assert parallel.traced_plan() is None
