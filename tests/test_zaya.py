"""The seventh spec of the layer-list family (``models/hybrid_lm.py``):
compressed convolutional attention — grouped-query attention inside a
latent whose q | k rows are mixed along the sequence by two causal
convolutions and a q-k mean, L2-normalised under a learned temperature,
rotated over HALF of each head, half of the value taken from the
previous token: K/V pages AND a per-stream tail in every layer — over
experts chosen top-1 by an MLP router that carries its hidden row from
layer to layer, the residual stream under learned scales, a tied head;
against its plain reference (``benchmark/reference/zaya.py``) at a small
size, seeded weights: prefill + decode through the pages and the tails
on logits, each mechanism left out failing that comparison, the mix's
prompt form against its step form, the tail at a padded prompt's TRUE
last rows, streams that share nothing, the new refusals by name, and the
accepted specs building the symbols they built."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.executor import build_graph_fn  # noqa: E402
from mxnet_tpu.models.hybrid_lm import HybridSpec, mixer_state  # noqa: E402
from mxnet_tpu.ops import hybrid as hy  # noqa: E402

from benchmark.reference import zaya as ref  # noqa: E402
from _engines import WAIT, Family  # noqa: E402

# the published shape at a size a test can hold: 4 query heads over 2 KV
# heads of 16 (8 lanes rotated), 4 experts of 32 chosen top-1 by a router
# of hidden width 16, three layers alike
KVB = 16
CFG = {
    "family": "zaya", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000,
                                   "rope_type": "default"}},
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 1,
    "router_hidden_size": 16, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "vocab_size": 96,
    # a wider draw than 0.02: at d 64 the token rows must weigh against
    # three blocks' outputs
    "initializer_range": 0.1, "selection_bias_std": 0.05,
}
MIXER = {"kind": "cca", "heads": 4, "kv_heads": 2, "head_dim": 16,
         "conv": [2, 2], "rope_theta": 5e6, "rotary_dim": 8}
FFN = {"kind": "moe", "experts": 4, "top_k": 1, "width": 32,
       "score": "softmax", "select_bias": True,
       "router": {"kind": "mlp", "hidden": 16, "carry": True}}
TOL = 2e-4      # float32 logits, prefill + decode against the full forward


def draw(seed=7, dtype="float32", cfg=CFG):
    return ref.draw(cfg, seed, embed_dtype=dtype, dtype=dtype)


def sequence(n, seed=0):
    return np.random.RandomState(seed).randint(0, CFG["vocab_size"], n) \
        .astype(np.int32)


class Programs:
    """The spec's prefill and decode symbols over hand-kept pools, tables
    and slots: what the engine's programs compute, with the logits
    kept.  Stream ``row`` holds slot ``row + 1`` and its own pages."""

    def __init__(self, drawn, max_len=64, rows=1, cfg=CFG):
        self.spec = ref.spec(cfg)
        self.params = {k: jnp.asarray(v)
                       for k, v in ref.program_names(drawn).items()}
        self.mb = max_len // KVB
        layout = self.spec.pools(1 + rows * self.mb, KVB, 1 + rows,
                                 np.float32)
        self.names = [n for n, _, _, _ in layout]
        self.pools = [jnp.zeros(shape, dt) for _, shape, dt, _ in layout]
        self.fn = {ph: jax.jit(build_graph_fn(self.spec.symbol(ph)),
                               static_argnums=(3,))
                   for ph in ("prefill", "decode")}
        self.key = jax.random.PRNGKey(0)

    def pool(self, name):
        return np.asarray(self.pools[self.names.index(name)])

    def run(self, phase, tokens, positions, lengths, rows=(0,)):
        table = np.zeros((len(rows), self.mb), np.int32)
        for i, (r, n) in enumerate(zip(rows, lengths)):
            used = -(-int(n) // KVB)
            table[i, :used] = 1 + r * self.mb + np.arange(used)
        args = dict(self.params, data=jnp.asarray(tokens),
                    positions=jnp.asarray(positions),
                    lengths=jnp.asarray(lengths, jnp.int32),
                    block_table=jnp.asarray(table),
                    slots=jnp.asarray([r + 1 for r in rows], jnp.int32))
        args.update(zip(self.names, self.pools))
        outs, _ = self.fn[phase](args, {}, self.key, False)
        self.pools = list(outs[1:])
        return np.asarray(outs[0])[:, 0]

    def prefill(self, seq, n_prompt, bucket, row=0):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n_prompt] = seq[:n_prompt]
        return self.run("prefill", toks, np.arange(bucket)[None],
                        [n_prompt], rows=(row,))[0]

    def step(self, tokens, lengths, rows):
        """One decode step: stream rows[i] is fed tokens[i] at position
        lengths[i] - 1."""
        return self.run("decode", np.asarray(tokens, np.int32)[:, None],
                        np.asarray(lengths)[:, None] - 1, lengths, rows)

    def serve(self, seq, n_prompt, bucket, row=0):
        """Logits at positions n_prompt - 1 .. len(seq) - 1: a prefill of
        ``seq[:n_prompt]`` padded to ``bucket``, then a step a token."""
        out = [self.prefill(seq, n_prompt, bucket, row)]
        for t in range(n_prompt, len(seq)):
            out.append(self.step([seq[t]], [t + 1], (row,))[0])
        return np.stack(out)


# -- the spec ------------------------------------------------------------

def test_spec_holds_pages_and_a_tail_in_every_layer():
    spec = ref.spec(CFG)
    assert spec.mixer_kinds() == ("cca",) * 3
    assert spec.cache_kinds() == ("pages+slots",) * 3
    assert spec.pool_kinds() == ("pages", "pages", "slots_aux") * 3 \
        + ("counters",)
    assert (spec.kv_heads, spec.head_dim) == (2, 16)
    assert spec.feeds == ("data", "lengths", "block_table", "slots",
                          "positions")
    # u | c | the shifted value half: 2 x 96 + 16 numbers in whole tiles
    assert mixer_state(MIXER) == (None, (8, 128))
    full = dict(MIXER, heads=8, head_dim=128, rotary_dim=64)
    assert mixer_state(full) == (None, (8, 384))       # 2,688 of 3,072
    pools = spec.pools(9, KVB, 3, np.float32)
    assert [(n, s, d) for n, s, d, _ in pools[:3]] == [
        ("layer0_kpool", (9, 16, 32), np.float32),
        ("layer0_vpool", (9, 16, 32), np.float32),
        ("layer0_tail", (3, 8, 128), "float32")]
    assert spec.prompt_attention() == ((0, False),) * 3
    assert HybridSpec.from_dict(spec.to_dict()).to_dict() == spec.to_dict()


def test_node_names_and_an_absent_carry_in_the_first_layer():
    sym = ref.spec(CFG).symbol("decode")
    names = set(sym.list_arguments()) | {
        n["name"] for n in json.loads(sym.tojson())["nodes"]}
    for node in ("q", "k", "v1", "v2", "mix", "qk_norm", "v", "attn", "o",
                 "res1", "res2", "router_down", "router_norm", "router_1",
                 "router_2", "router_3", "moe"):
        assert f"layer1_{node}" in names, node
    assert "layer1_router_carry" in names
    assert "layer0_router_carry" not in names       # r_(-1) = 0
    assert "layer0_router_carry_gamma" not in names
    assert "head_weight" not in names               # the table is the head
    # a router that carries nothing hands nothing on
    alone = HybridSpec(96, 64, [{"mixer": MIXER, "ffn": dict(
        FFN, router={"kind": "mlp", "hidden": 16, "carry": False})}] * 2)
    assert not [n for n in alone.symbol("decode").list_arguments()
                if "router_carry" in n]


@pytest.mark.parametrize("bad, match", [
    (dict(mixer=dict(MIXER, conv=[4, 4])), "two taps"),
    (dict(mixer=dict(MIXER, conv=[2])), "two taps"),
    (dict(mixer=dict(MIXER, rotary_dim=7)), "even"),
    (dict(mixer=dict(MIXER, rotary_dim=32)), "at most head_dim"),
    (dict(mixer={k: v for k, v in MIXER.items() if k != "rope_theta"}),
     "rope_theta required"),
    (dict(mixer=dict(MIXER, kv_heads=3)), "do not divide"),
    (dict(mixer=dict(MIXER, window=8)), "no key"),
    (dict(ffn=dict(FFN, top_k=2)), "top_k 1 is built"),
    (dict(ffn=dict(FFN, router={"kind": "linear"})), "kind 'mlp'"),
    (dict(ffn=dict(FFN, router={"kind": "mlp", "hidden": 16,
                                "depth": 5})), "kind 'mlp'"),
    (dict(ffn=dict(FFN, router={"kind": "mlp"})), "positive hidden"),
    (dict(ffn=dict(FFN, routers=1)), "no key"),
])
def test_refusals_by_name(bad, match):
    layer = {"mixer": bad.get("mixer", MIXER), "ffn": bad.get("ffn", FFN)}
    with pytest.raises(MXNetError, match=match):
        HybridSpec(96, 64, [layer])


# the reference at one length, and the one engine the engine tests share
FAMILY = Family(ref, CFG, pad=64, max_len=64, kv_block=KVB, max_streams=2,
                decode_buckets=(2,), prefill_buckets=(16, 32),
                temperature=0.0)


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


def test_engine_refuses_what_a_slot_spec_cannot_carry(engines):
    for extra, match in ((dict(prefix_cache=1), "prefix"),
                         (dict(prefill_chunk=16), "chunk"),
                         (dict(spec_tokens=2), "verify|spec"),
                         (dict(kv_dtype="int8"), "int8"),
                         (dict(tp=2), "tp|mesh|partition")):
        with pytest.raises(MXNetError, match=match):
            FAMILY.engine(**extra)
    eng, _ = engines(FAMILY.engine)
    with pytest.raises(MXNetError, match="prefill_only"):
        eng.submit(sequence(5), max_new_tokens=2, prefill_only=True)
    with pytest.raises(MXNetError, match="import"):
        eng.import_stream({}, [])


# -- logits: prefill + decode through pages and tails --------------------

def test_prefill_then_decode_equals_the_reference_forward():
    drawn = draw()
    seq = sequence(40)
    want = FAMILY.logits(drawn, seq)
    # a prompt of 21 in a bucket of 32: the tail is read at row 20
    got = Programs(drawn).serve(seq, 21, 32)
    np.testing.assert_allclose(got, want[20:], atol=TOL, rtol=0)
    # and the whole prompt at once
    got = Programs(drawn).serve(seq, 40, 48)
    np.testing.assert_allclose(got, want[39:], atol=TOL, rtol=0)


@pytest.mark.parametrize("wrong", ref.MECHANISMS)
def test_each_mechanism_left_out_fails_the_comparison(wrong):
    drawn = draw()
    seq = sequence(40)
    want = FAMILY.logits(drawn, seq)
    other = FAMILY.logits(drawn, seq, wrong)
    assert np.abs(other[20:] - want[20:]).max() > 50 * TOL, wrong


def test_a_tail_at_the_buckets_last_rows_fails():
    """The planted fault: a prefill that keeps the rows at the END of
    the bucket (padding) serves another next token."""
    drawn = draw()
    seq = sequence(12)
    want = FAMILY.logits(drawn, seq)
    p = Programs(drawn)
    p.prefill(seq, 5, 8)
    sound = p.step([seq[5]], [6], (0,))[0]
    np.testing.assert_allclose(sound, want[5], atol=TOL, rtol=0)
    q = Programs(drawn)
    q.prefill(seq, 5, 8)
    # what the fault would have written: the bucket's last row's tail,
    # which a prefill of the same 8 rows as a prompt of 8 leaves
    r = Programs(drawn)
    padded = np.zeros(8, np.int32)
    padded[:5] = seq[:5]
    r.prefill(padded, 8, 8)
    for i, n in enumerate(q.names):
        if n.endswith("_tail"):
            assert not np.allclose(q.pool(n)[1], r.pool(n)[1]), n
            q.pools[i] = r.pools[i]
    faulty = q.step([seq[5]], [6], (0,))[0]
    assert np.abs(faulty - want[5]).max() > 50 * TOL


def test_two_streams_share_no_tail_and_a_zeroed_tail_fails():
    drawn = draw()
    a, b = sequence(30, 1), sequence(30, 2)
    want_a = FAMILY.logits(drawn, a)
    want_b = FAMILY.logits(drawn, b)
    p = Programs(drawn, rows=2)
    p.prefill(a, 9, 16, row=0)
    p.prefill(b, 20, 32, row=1)
    for t in range(20, 26):
        got = p.step([a[t - 11], b[t]], [t - 10, t + 1], (0, 1))
        np.testing.assert_allclose(got[0], want_a[t - 11], atol=TOL, rtol=0)
        np.testing.assert_allclose(got[1], want_b[t], atol=TOL, rtol=0)
    tail = p.pool("layer1_tail")
    assert np.abs(tail[1]).max() > 0 and np.abs(tail[2]).max() > 0
    assert not np.allclose(tail[1], tail[2])
    # a stream whose tail was lost serves another token
    at = p.names.index("layer0_tail")
    p.pools[at] = p.pools[at].at[1].set(0.0)
    got = p.step([a[15], b[26]], [16, 27], (0, 1))
    assert np.abs(got[0] - want_a[15]).max() > 50 * TOL
    np.testing.assert_allclose(got[1], want_b[26], atol=TOL, rtol=0)


def test_the_engine_serves_it_and_a_reused_slot_shows_no_last_owner(
        engines):
    eng, drawn = engines(FAMILY.engine)
    prompts = [sequence(n, s) for n, s in ((5, 3), (17, 4), (9, 5),
                                            (12, 6), (7, 7))]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    outs = [np.asarray(f.result(timeout=WAIT)) for f in futs]
    st = eng.stats()
    # five streams through two slots: every slot was reused
    assert st["state_slots"] == 2 and st["state_slots_live"] == 0
    assert st["state_pool_bytes"] == 3 * 3 * 8 * 128 * 4
    # K and V of 2 x 16 float32 lanes a layer; three tails of (8, 128)
    gauges = mx.profiler.metrics_summary()["gauges"]
    assert gauges["cca.cache_bytes_per_token"] == 2 * 32 * 4
    assert gauges["cca.tail_bytes_per_stream"] == 3 * 8 * 128 * 4
    assert ref.spec(CFG).tail_row == (3 * 208, 3 * 1024)
    assert st["moe_pairs_here"] > 0 and st["moe_pairs_elsewhere"] == 0
    assert 0 < st["moe_experts_hit"] and st["moe_load_max"] > 0
    for p, out in zip(prompts, outs):
        # greedy against the reference, teacher-forced: every served
        # token lies within TOL of the reference's best logit
        seq = np.concatenate([p, out])
        z = FAMILY.logits(drawn, seq)[len(p) - 1:-1]
        gap = z.max(-1) - z[np.arange(len(out)), out]
        assert gap.max() < TOL, (len(p), gap)


# -- the ops --------------------------------------------------------------

def _mix_inputs(B=2, S=9, H=4, J=2, D=16, seed=0):
    r = np.random.RandomState(seed)
    C = (H + J) * D
    return dict(
        q=jnp.asarray(r.randn(B, S, H * D), jnp.float32),
        k=jnp.asarray(r.randn(B, S, J * D), jnp.float32),
        v2=jnp.asarray(r.randn(B, S, J * D // 2), jnp.float32),
        w0=jnp.asarray(r.uniform(-.7, .7, (C, 2)), jnp.float32),
        w1=jnp.asarray(r.randn(H + J, 2, D, D) * (2 * D) ** -.5,
                       jnp.float32))


def _mix(x, pool, slots, lengths, step):
    from mxnet_tpu.ops.registry import get_op

    return get_op("CCAMix").compute(
        None, dict(num_heads=4, kv_heads=2, step=step),
        [x["q"], x["k"], x["v2"], x["w0"], x["w1"], pool,
         jnp.asarray(slots, jnp.int32), jnp.asarray(lengths, jnp.int32)],
        [])


def test_mix_prompt_form_equals_step_form_token_by_token():
    x = _mix_inputs()
    B, S = 2, 9
    pool = jnp.zeros((4, 8, 128), jnp.float32)
    q, k, v, after = _mix(x, pool, [1, 3], [9, 6], step=False)
    # the reference's mix, a sequence at a time
    for b in range(B):
        p = {"mix_conv0_weight": x["w0"], "mix_conv1_weight": x["w1"]}
        rq, rk = ref.mix(p, x["q"][b].reshape(S, 4, 16),
                         x["k"][b].reshape(S, 2, 16), "float32")
        np.testing.assert_allclose(q[b], rq.reshape(S, -1), atol=1e-5)
        np.testing.assert_allclose(k[b], rk.reshape(S, -1), atol=1e-5)
        np.testing.assert_allclose(v[b], ref.shift(x["v2"][b]), atol=0)
    # token by token from nothing: the same rows, and the same tail
    tail = jnp.zeros((4, 8, 128), jnp.float32)
    for t in range(S):
        one = {n: (a[:, t:t + 1] if n in ("q", "k", "v2") else a)
               for n, a in x.items()}
        sq, sk, sv, tail = _mix(one, tail, [1, 3], [t + 1] * 2, step=True)
        np.testing.assert_allclose(sq[:, 0], q[:, t], atol=1e-5)
        np.testing.assert_allclose(sk[:, 0], k[:, t], atol=1e-5)
        np.testing.assert_allclose(sv[:, 0], v[:, t], atol=0)
        if t == 5:      # stream 1's prompt ended here: its TRUE last row
            np.testing.assert_allclose(tail[3], after[3], atol=1e-6)
    np.testing.assert_allclose(tail[1], after[1], atol=1e-6)
    assert not np.allclose(after[3], tail[3])       # not the bucket's
    assert np.abs(np.asarray(after)[[0, 2]]).max() == 0     # untouched
    # a slot's numbers: u | c | v2, then padding
    run = np.asarray(after[1]).reshape(-1)
    np.testing.assert_allclose(run[:96], np.concatenate(
        [x["q"][0, -1], x["k"][0, -1]]), atol=0)
    np.testing.assert_allclose(run[192:208], x["v2"][0, -1], atol=0)
    assert np.abs(run[208:]).max() == 0


def test_partial_rotation_and_the_whole_head():
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 5, 3 * 16), jnp.float32)
    pos = jnp.asarray(r.randint(0, 100, (2, 5)), jnp.int32)
    whole = hy.rotate_half(x, pos, 1e4, 3)
    np.testing.assert_array_equal(
        np.asarray(hy.rotate_half(x, pos, 1e4, 3, rotary_dim=16)),
        np.asarray(whole))
    half = np.asarray(hy.rotate_half(x, pos, 5e6, 3, rotary_dim=8))
    x4 = np.asarray(x).reshape(2, 5, 3, 16)
    np.testing.assert_array_equal(half.reshape(2, 5, 3, 16)[..., 8:],
                                  x4[..., 8:])
    # against the reference's rotation, row t at position t
    seq = jnp.asarray(r.randn(7, 3, 16), jnp.float32)
    got = hy.rotate_half(seq.reshape(1, 7, 48), jnp.arange(7)[None], 5e6, 3,
                         rotary_dim=8)
    np.testing.assert_allclose(
        np.asarray(got).reshape(7, 3, 16),
        np.asarray(ref.rotate_span(seq, 5e6, 8)), atol=1e-6)
    with pytest.raises(MXNetError, match="rotated in pairs"):
        hy.rotate_half(x, pos, 1e4, 3, rotary_dim=5)


def test_route_softmax_against_the_reference_weight_not_one():
    r = np.random.RandomState(1)
    s = jnp.asarray(r.randn(64, 8) * 1.5, jnp.float32)
    bias = jnp.asarray(r.randn(8) * 0.1, jnp.float32)
    topi, wts = hy.moe_route(None, None, 1, "softmax", select_bias=bias,
                             logits=s)
    e, wt = ref.route({"router_bias": bias}, s)
    np.testing.assert_array_equal(np.asarray(topi)[:, 0], np.asarray(e))
    np.testing.assert_allclose(np.asarray(wts)[:, 0], np.asarray(wt),
                               atol=1e-7)
    assert np.asarray(wts).max() < 0.999            # a top-1 weight is p
    # the bias moves choices, never a weight
    plain, _ = hy.moe_route(None, None, 1, "softmax", logits=s)
    assert (np.asarray(plain) != np.asarray(topi)).any()
    p = np.asarray(jax.nn.softmax(s, -1))
    np.testing.assert_allclose(
        np.asarray(wts)[:, 0], p[np.arange(64), np.asarray(topi)[:, 0]],
        atol=1e-7)
    # two of them: the two probabilities, un-normalised
    top2, w2 = hy.moe_route(None, None, 2, "softmax", logits=s)
    np.testing.assert_allclose(np.asarray(w2), -np.sort(-p, -1)[:, :2],
                               atol=1e-7)
    # from one matrix too, as the other scores
    x2 = jnp.asarray(r.randn(64, 12), jnp.float32)
    w = jnp.asarray(r.randn(8, 12), jnp.float32)
    a = hy.moe_route(x2, w, 1, "softmax")
    b = hy.moe_route(None, None, 1, "softmax",
                     logits=jnp.dot(x2, w.T, precision=hy.HI))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    with pytest.raises(MXNetError, match="takes no group limit"):
        hy.moe_route(None, None, 1, "softmax", groups=2, top_groups=1,
                     logits=s)


def test_the_routers_carry_reaches_the_next_layers_logits():
    """Layer 1's logits change when layer 0's hidden row does, and not
    under ``no_carry``."""
    drawn = draw()
    z = ref.sizes(CFG)
    r = np.random.RandomState(2)
    h2 = jnp.asarray(r.randn(6, 64), jnp.float32)
    prev = jnp.asarray(r.randn(6, 16), jnp.float32)
    p1 = drawn["layers"][1]
    s_a, row_a = ref.router(p1, h2, prev, z)
    s_b, row_b = ref.router(p1, h2, 2.0 * prev, z)
    assert np.abs(np.asarray(s_a) - np.asarray(s_b)).max() > 1e-2
    np.testing.assert_allclose(
        np.asarray(row_b - row_a),
        np.asarray(p1["router_carry_gamma"] * prev), atol=1e-5)
    s_c, _ = ref.router(p1, h2, prev, z, "no_carry")
    s_d, _ = ref.router(p1, h2, None, z)
    np.testing.assert_array_equal(np.asarray(s_c), np.asarray(s_d))
    # the program's chain of nodes gives the reference's logits
    from mxnet_tpu.models.hybrid_lm import _router_mlp
    from mxnet_tpu import symbol as sym

    logits, row = _router_mlp(sym.Variable("h"), "layer1", FFN["router"],
                              sym.Variable("prev"), 1e-5)
    fn = build_graph_fn(sym.Group([logits, row]))
    args = {f"layer1_{k}": v for k, v in p1.items() if "router" in k}
    outs, _ = fn(dict(args, h=h2[None], prev=prev[None]), {},
                 jax.random.PRNGKey(0), False)
    np.testing.assert_allclose(np.asarray(outs[0])[0], np.asarray(s_a),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1])[0], np.asarray(row_a),
                               atol=1e-5)


def test_scaled_residual_and_the_l2_norm():
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(2, 3, 8), jnp.float32)
    out = jnp.asarray(r.randn(2, 3, 8), jnp.float32)
    sc = jnp.asarray(r.randn(4, 8), jnp.float32)
    from mxnet_tpu.ops.registry import get_op

    got = get_op("ScaledResidual").compute(None, {}, [x, out, sc], [])[0]
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray((sc[0] * x + sc[1]) + (sc[2] * out + sc[3])), atol=1e-6)
    q = jnp.asarray(r.randn(2, 3, 4 * 16), jnp.float32)
    k = jnp.asarray(r.randn(2, 3, 2 * 16), jnp.float32)
    tau = jnp.asarray([0.5, 2.0], jnp.float32)
    qn, kn = get_op("QKL2Norm").compute(
        None, dict(num_heads=4, kv_heads=2), [q, k, tau], [])
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(qn).reshape(2, 3, 4, 16), axis=-1), 4.0,
        atol=1e-4)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(kn).reshape(2, 3, 2, 16), axis=-1),
        np.broadcast_to(4.0 * np.asarray(tau), (2, 3, 2)), atol=1e-4)


# -- kernels, the other specs ---------------------------------------------

KERNELS = ("flash_fwd_mha", "kv_pages_write", "paged_attention", "moe_gmm",
           "slot_rows_write")


def test_kernels_interpreted_match_the_lax_bodies(monkeypatch):
    drawn = draw()
    seq = sequence(40)
    lax_rows = Programs(drawn).serve(seq[:27], 21, 32)
    monkeypatch.setenv("MXNET_PALLAS", "1")
    from mxnet_tpu.ops import pallas_kernels as pk
    assert pk.enabled()
    progs = Programs(drawn)
    texts = {}
    for ph, shape, n in (("prefill", (1, 32), 32), ("decode", (1, 1), 5)):
        table = np.zeros((1, progs.mb), np.int32)
        args = dict(progs.params, data=jnp.zeros(shape, jnp.int32),
                    positions=jnp.zeros(shape, jnp.int32),
                    lengths=jnp.asarray([n], jnp.int32),
                    block_table=jnp.asarray(table),
                    slots=jnp.ones((1,), jnp.int32))
        args.update(zip(progs.names, progs.pools))
        graph = build_graph_fn(progs.spec.symbol(ph))
        texts[ph] = str(jax.make_jaxpr(
            lambda a: graph(a, {}, progs.key, False))(args))
    # the attention is the grouped kernels the other specs run: nothing
    # of the latent's own is a kernel
    assert {k for k in KERNELS if k in texts["prefill"]} == {
        "flash_fwd_mha", "kv_pages_write", "moe_gmm", "slot_rows_write"}
    assert {k for k in KERNELS if k in texts["decode"]} == {
        "paged_attention", "moe_gmm", "slot_rows_write"}
    got = progs.serve(seq[:27], 21, 32)
    np.testing.assert_allclose(got, lax_rows, atol=2e-4)


def test_the_held_form_holds_what_a_bfloat16_program_holds():
    """``bfloat16_held`` (the yardstick ``serve_pages_relative`` divides
    by): the stream a layer hands on is bfloat16 words, which the form
    that only multiplies in bfloat16 is not; the chosen experts and the
    logits stay the float32 reference's but for rounding."""
    import jax.numpy as jnp

    w, z, seq = draw(), ref.sizes(CFG), jnp.asarray(sequence(24))
    exact = lambda x: bool(jnp.all(
        x == x.astype(jnp.bfloat16).astype(jnp.float32)))
    held, chosen = ref.hidden(w, seq, z, "bfloat16_held")
    mult, _ = ref.hidden(w, seq, z, "bfloat16")
    full, chosen32 = ref.hidden(w, seq, z)
    assert exact(held) and not exact(mult) and not exact(full)
    assert float(jnp.mean(chosen == chosen32)) > 0.9
    want = ref.logits(w, full, z)
    for form, rows in (("bfloat16", mult), ("bfloat16_held", held)):
        gap = float(jnp.abs(ref.logits(w, rows, z, form) - want).max())
        assert 0.0 < gap < 0.1, (form, gap)


def test_the_reference_refuses_a_program_without_the_kind(monkeypatch):
    from mxnet_tpu.models import hybrid_lm
    monkeypatch.delitem(hybrid_lm.MIXERS, "cca")
    with pytest.raises(NotImplementedError, match="cca"):
        ref.spec(CFG)


@pytest.mark.parametrize("family", ["solar_open2", "granitemoehybrid",
                                    "smallthinker", "deepseek_v3", "afmoe",
                                    "brumby"])
def test_the_six_specs_there_were_build_the_parents_symbols(family):
    # tests/data/hybrid_symbols_pr47.json: ``structure`` of the parent
    # commit's symbols for the six tiny configurations — node for node,
    # names and attributes: an absent key adds no node
    import importlib

    import test_afmoe
    import test_brumby
    import test_deepseek_v3
    import test_hybrid_lm
    import test_mamba2
    import test_smallthinker

    cfg = {"solar_open2": test_hybrid_lm.CFG,
           "granitemoehybrid": test_mamba2.CFG,
           "smallthinker": test_smallthinker.CFG,
           "deepseek_v3": test_deepseek_v3.CFG, "afmoe": test_afmoe.CFG,
           "brumby": test_brumby.CFG}[family]
    spec = importlib.import_module(
        f"benchmark.reference.{family}").spec(cfg)
    with open(os.path.join(ROOT, "tests", "data",
                           "hybrid_symbols_pr47.json")) as f:
        parent = json.load(f)[family]
    for ph in ("prefill", "decode"):
        assert test_smallthinker.structure(spec.symbol(ph)) == parent[ph]
    assert not spec.learned_residual
    assert spec.to_dict()["learned_residual"] is False


@pytest.mark.parametrize("pairs, held, step, rows", [
    # the accepted cells' programs keep the tiles they had: decode steps
    # of reason, rag, mixed, longctx, longdoc; their smallest prefills
    (128 * 8, 40, True, 16), (64 * 10, 36, True, 16), (48 * 6, 64, True, 16),
    (32 * 8, 8, True, 16), (24 * 4, 16, True, 16),
    (1024 * 8, 40, False, 128), (1024 * 10, 36, False, 128),
    (1024 * 6, 64, False, 128), (1024 * 8, 8, False, 128),
    (4096 * 4, 16, False, 128),
    # the rollout cell: 128 rows a step at one expert a token stay small;
    # its prompts of 512 and 1,024 pairs over 16 held experts would
    # stream every expert 2-5 times in 16-row tiles
    (128, 16, True, 16), (512, 16, False, 128), (1024, 16, False, 128),
    (256, 16, False, 16), (4095, 0, False, 16), (4096, 0, True, 128),
])
def test_tile_rows_by_a_prompts_pairs_an_expert(pairs, held, step, rows):
    assert hy._tile_rows(pairs, held, step) == rows
