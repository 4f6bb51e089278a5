"""The hybrid language-model family (``models/hybrid_lm.py``) against
its plain reference (``benchmark/reference/solar_open2.py``) at small
sizes, seeded weights, float32: the engine through pages AND slots
against the reference's full forward, the refusals, and the seam — what
``DecodeEngine`` asks of any spec.  (Each op against its plain form:
``test_hybrid_ops.py``.)"""

import copy
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.kv_cache import SlotAllocator  # noqa: E402

from benchmark.reference import solar_open2 as ref  # noqa: E402
from _engines import WAIT, Family, build, watch_slots  # noqa: E402

# the published shape at a size a test can hold: one period (layer 0
# gated GQA, layers 1-3 KDA), 16 query heads over 2 KV heads, 4 of 32
# experts held, 4 a token, one shared
CFG = {
    "family": "solar_open2", "hidden_size": 64, "num_hidden_layers": 4,
    "num_hidden_layers_published": 48, "num_attention_heads": 16,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 96,
    "rms_norm_eps": 1e-5, "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
    "kda_allow_neg_eigval": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4},
    "n_routed_experts": 4, "n_routed_experts_published": 32,
    "first_expert": 0, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "initializer_range": 0.02,
}


# -- the engine: pages and slots against the reference's full forward ----

FAMILY = Family(ref, CFG, pad=96, max_len=96, kv_block=4, max_streams=3,
                decode_buckets=(1, 2, 4), cache_buckets=(8, 24),
                prefill_buckets=(16, 32, 96))
# the tests below that name no argument share one engine (``engines``)
# and read its counters from ``reset_stats()`` on
make_engine, served_gap, prompts = \
    FAMILY.engine, FAMILY.served_gap, FAMILY.prompts


def test_the_references_rows_do_not_see_the_padding_behind_them():
    FAMILY.padding_is_not_seen()


def test_engine_joins_and_retirements_match_the_reference(engines):
    eng, drawn = engines(make_engine)
    ps = prompts(np.random.default_rng(8), (9, 20, 13, 27, 6))
    news = (44, 41, 50, 43, 47)                      # >= 40 decode steps
    with watch_slots(eng) as (held, history):
        outs = [f.result(timeout=WAIT) for f in
                [eng.submit(p, max_new_tokens=m) for p, m in zip(ps, news)]]
        st = eng.stats()
    assert [len(o) for o in outs] == list(news)
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    # five streams through three slots: slots were reused, never shared,
    # and every reuse was overwritten (the later streams agree too)
    assert len(history) == 5 and len({s for s, _ in history}) <= 3
    assert not held and st["state_slots_live"] == 0
    assert st["state_slots"] == 3 and st["state_pool_bytes"] > 0
    assert st["moe_pairs_here"] + st["moe_pairs_elsewhere"] == \
        st["stream_steps"] * CFG["num_experts_per_tok"] * 4
    assert 0 < st["moe_experts_hit"] <= st["steps"] * 4 * 4
    assert st["moe_load_max"] >= st["moe_experts_hit"] / 4


def test_engine_preemption_recomputes_the_slot():
    # 17 pages for three streams that want ~13 each: someone is thrown
    # out, its slot freed, and its re-prefill writes a slot anew (an
    # engine of its own: the pool is sized for it)
    eng, drawn = make_engine(cache_blocks=34)
    ps = prompts(np.random.default_rng(9), (10, 12, 9))
    with eng, watch_slots(eng) as (held, history):
        outs = [f.result(timeout=WAIT) for f in
                [eng.submit(p, max_new_tokens=40) for p in ps]]
        st = eng.stats()
    assert st["preempted"] >= 1 and len(history) > 3
    for p, o in zip(ps, outs):
        assert served_gap(drawn, p, o) < 1e-4
    assert not held


def test_returned_state_is_the_reference_scans_last_state(engines):
    # five streams through three slots, so slots are reused; two ask for
    # their state: what the slot holds at retirement is the scan's state
    # after prompt + every generated token but the last, never fed
    eng, drawn = engines(make_engine)
    ps = prompts(np.random.default_rng(11), (9, 21, 14, 30, 5))
    futs = [eng.submit(p, max_new_tokens=42, return_state=(i % 2 == 1))
            for i, p in enumerate(ps)]
    outs = [f.result(timeout=WAIT) for f in futs]
    assert isinstance(outs[0], np.ndarray)
    for p, out in ((ps[1], outs[1]), (ps[3], outs[3])):
        # the reference takes a padded sequence and its length: one
        # trace for both streams
        n = len(p) + len(out["tokens"])
        seq = np.zeros(96, np.int32)
        seq[:n] = np.concatenate([p, out["tokens"]])
        assert served_gap(drawn, p, out["tokens"]) < 1e-4
        want = ref.final_states(CFG, drawn, jnp.asarray(seq), n - 1)
        assert sorted(out["state"]) == sorted(want) == [
            "layer1_state", "layer2_state", "layer3_state"]
        for name, st in out["state"].items():
            np.testing.assert_allclose(
                st, np.asarray(want[name]).transpose(0, 2, 1), atol=2e-5)
            assert np.abs(st).max() > 1e-3
        # ... and not the state one token later
        late = ref.final_states(CFG, drawn, jnp.asarray(seq), n)
        assert np.abs(np.asarray(late["layer1_state"]).transpose(0, 2, 1)
                      - out["state"]["layer1_state"]).max() > 1e-4


def test_return_state_needs_a_model_with_slots():
    from benchmark.reference import gpt2

    cfg = {"n_layer": 1, "n_embd": 16, "n_head": 2, "vocab_size": 32,
           "n_positions": 16, "initializer_range": 0.02}
    with build(
            gpt2.program_names(gpt2.draw(cfg, 3, "float32", "float32")),
            vocab_size=32, num_layers=1, num_heads=2, d_model=16,
            max_len=16, ctx=mx.cpu(), dtype="float32") as eng:
        with pytest.raises(MXNetError, match="return_state.*kda"):
            eng.submit(np.arange(1, 5, dtype=np.int32), 4,
                       return_state=True)


def test_reset_stats_zeroes_the_routing_counters(engines):
    eng, _ = engines(make_engine)
    eng.generate(prompts(np.random.default_rng(10), (8,))[0], 6)
    assert eng.stats()["moe_pairs_here"] + \
        eng.stats()["moe_pairs_elsewhere"] > 0
    eng.reset_stats()
    st = eng.stats()
    assert st["moe_pairs_here"] == st["moe_pairs_elsewhere"] == 0
    assert st["moe_experts_hit"] == st["moe_load_max"] == 0


# -- what slots cannot do yet is refused by name --------------------------

@pytest.mark.parametrize("kw, feature", [
    (dict(prefix_cache=1), "prefix_cache"),
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(spec_tokens=2), "spec_tokens"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(tp=2), "tp=2"),
    (dict(adapters=True), "adapters"),
])
def test_features_over_slots_are_refused_by_name(kw, feature):
    with pytest.raises(MXNetError) as err:
        make_engine(**kw)
    assert feature in str(err.value) and "kda" in str(err.value)


def test_page_export_and_import_are_refused_by_name(engines):
    eng, _ = engines(make_engine)
    with pytest.raises(MXNetError, match="page export.*kda"):
        eng.submit(np.arange(1, 6, dtype=np.int32), prefill_only=True)
    with pytest.raises(MXNetError, match="page import.*kda"):
        eng.import_stream({}, [])


def test_spec_or_dense_keywords_not_both():
    with pytest.raises(MXNetError, match="not both"):
        build(ref.program_names(FAMILY.draw()), model=ref.spec(CFG),
              num_heads=4, max_len=32, ctx=mx.cpu())
    with pytest.raises(MXNetError, match="model=<spec> or all of"):
        build({}, vocab_size=10, ctx=mx.cpu())


def test_spec_is_plain_data():
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    spec = ref.spec(CFG)
    again = HybridSpec.from_dict(copy.deepcopy(spec.to_dict()))
    assert again.to_dict() == spec.to_dict()
    assert spec.cache_kinds() == ("pages", "slots", "slots", "slots")
    assert (spec.kv_heads, spec.head_dim) == (2, 8)
    names = [n for n, _, _, _ in spec.pools(9, 4, 4, "float32")]
    assert names == ["layer0_kpool", "layer0_vpool", "layer1_state",
                     "layer1_tail", "layer2_state", "layer2_tail",
                     "layer3_state", "layer3_tail", "moe_counters"]
    with pytest.raises(MXNetError, match="verify"):
        spec.symbol("verify")


# -- the seam: what the engine asks of ANY spec (DecodeEngine's docstring) --

GPT2 = {"n_layer": 1, "n_embd": 16, "n_head": 2, "vocab_size": 32,
        "n_positions": 16, "initializer_range": 0.02}
PHASES = ("prefill", "decode", "prefix_prefill", "verify")


class StubSpec:
    """A third family, neither ``DenseSpec`` nor ``HybridSpec`` (and no
    subclass): pages only, a prefill and a decode symbol and nothing
    else, no mesh placement.  It borrows the dense builders for its two
    symbols, and with them what those can hold (quantized pages, LoRA)."""

    name = "a stub family"
    feeds = ("data", "positions", "lengths", "block_table")
    phases = ("prefill", "decode")
    positions = "pos_embed_weight"
    partition_rules = None

    def __init__(self, dense):
        self._dense = dense
        for k in ("vocab_size", "num_layers", "d_model", "kv_heads",
                  "head_dim", "kv_dtypes", "lora_width", "pools",
                  "pool_kinds"):
            setattr(self, k, getattr(dense, k))

    def symbol(self, which, **kw):
        if which not in self.phases:
            raise MXNetError(f"{self.name} builds no {which!r} symbol")
        return self._dense.symbol(which, **kw)


def granite_family():
    """The second spec of ``HybridSpec``'s layer list (mamba2 mixers,
    multipliers, a tied head): its reference and tiny configuration."""
    from benchmark.reference import granitemoehybrid
    from test_mamba2 import CFG as GRANITE

    return granitemoehybrid, GRANITE


FAMILIES = ["dense", "hybrid", "granite", "stub"]


def seam_family(family):
    """(spec, params, engine keywords) of one family, tiny."""
    from benchmark.reference import gpt2
    from mxnet_tpu.models.transformer import DenseSpec

    if family in ("hybrid", "granite"):
        r, cfg = (ref, CFG) if family == "hybrid" else granite_family()
        drawn = r.draw(cfg, 7, embed_dtype="float32", dtype="float32")
        return r.spec(cfg), r.program_names(drawn), dict(
            max_len=96, kv_block=4, max_streams=2, prefill_buckets=(16, 96))
    dense = DenseSpec(32, 1, 2, 16)
    return (dense if family == "dense" else StubSpec(dense)), \
        gpt2.program_names(gpt2.draw(GPT2, 3, "float32", "float32")), \
        dict(max_len=16, kv_block=4, max_streams=2)


@pytest.mark.parametrize("phase", PHASES)
@pytest.mark.parametrize("family", FAMILIES)
def test_a_spec_builds_the_phases_it_lists_and_refuses_the_rest(family,
                                                                phase):
    spec, _, _ = seam_family(family)
    assert {"prefill", "decode"} <= set(spec.phases) <= set(PHASES)
    if phase not in spec.phases:
        with pytest.raises(MXNetError, match=f"no '{phase}' symbol"):
            spec.symbol(phase, kv_block=4)
        return
    sym = spec.symbol(phase, kv_block=4)
    pools = spec.pools(9, 4, 3, "float32")
    assert len(spec.pool_kinds()) == len(pools) == len(sym.list_outputs()) - 1
    args = set(sym.list_arguments())
    assert {n for n, _, _, _ in pools} <= args
    # the feeds it lists are the arguments that are neither state nor
    # parameters: what the engine's programs bind by name
    assert args & {"data", "positions", "lengths", "block_table", "start",
                   "slots"} <= set(spec.feeds)


# feature -> (the keyword that asks for it, what the protocol says a
# spec needs to carry it)
FEATURES = {
    "none": ({}, lambda s, paged: True),
    "prefix_cache": (dict(prefix_cache=1),
                     lambda s, paged: "prefix_prefill" in s.phases and paged),
    "prefill_chunk": (dict(prefill_chunk=8),
                      lambda s, paged: "prefix_prefill" in s.phases and paged),
    "spec_tokens": (dict(spec_tokens=2),
                    lambda s, paged: "verify" in s.phases and paged),
    "kv_dtype='int8'": (dict(kv_dtype="int8"),
                        lambda s, paged: "int8" in s.kv_dtypes),
    "tp=2": (dict(tp=2), lambda s, paged: s.partition_rules is not None),
    "adapters": (dict(adapters=True), lambda s, paged: bool(s.lora_width)),
}


@pytest.mark.parametrize("feature", list(FEATURES))
@pytest.mark.parametrize("family", FAMILIES)
def test_a_feature_is_taken_or_refused_from_the_protocol_alone(family,
                                                               feature):
    """No engine edit for a third family: ``StubSpec`` is served, and
    each feature is built exactly where the spec lists what it needs."""
    spec, params, kw = seam_family(family)
    asks, needs = FEATURES[feature]
    paged = "slots" not in spec.pool_kinds()

    def served(**more):
        return build(params, model=spec, ctx=mx.cpu(), dtype="float32",
                     **kw, **more)

    if not needs(spec, paged):
        with pytest.raises(MXNetError) as err:
            served(**asks)
        assert feature in str(err.value) and spec.name in str(err.value)
        return
    prompt = np.arange(1, 10, dtype=np.int32)
    with served(**asks) as eng:
        out = eng.generate(prompt, 3)
        # the catalog's default (on) is taken only where it is carried
        if feature == "none":
            assert (eng._prefix is not None) == (
                "prefix_prefill" in spec.phases and paged)
    assert out.shape == (3,)
    if family == "stub" and feature == "none":
        dense, _, _ = seam_family("dense")
        with build(params, model=dense, ctx=mx.cpu(), dtype="float32",
                   **kw) as eng:
            np.testing.assert_array_equal(out, eng.generate(prompt, 3))


def test_slot_allocator():
    a = SlotAllocator(2)
    s1, s2 = a.alloc("x"), a.alloc("y")
    assert {s1, s2} == {1, 2} and a.alloc() is None and a.live == 2
    a.free(s1)
    assert a.owner(s2) == "y" and a.alloc("z") == s1
    with pytest.raises(MXNetError):
        a.free(0)
