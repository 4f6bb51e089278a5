"""Every device operation under the name the program gave it:
``hlo.scope_table`` on compiled text (a symbol's graph function, a
``Module`` fused step, a hand-written module with what the CPU's
compiler does not emit), and the lazy accessors over the executables an
engine and a module already hold — which read no text until asked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import hlo, profiler
from mxnet_tpu.executor import build_graph_fn

from _engines import WAIT, build, dense_engine as _engine, tiny_lm_params

RECORD_KEYS = {"scope", "group", "opcodes", "scopes", "klass", "optimizer"}


# ---------------------------------------------------------------------
# (a) a two-layer symbol through build_graph_fn
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def graph_table():
    data = mx.sym.Variable("data")   # (24, 8) float16: features first
    h = mx.sym.transpose(data, name="layer0_swap")
    h = mx.sym.Cast(h, dtype="float32", name="layer0_widen")
    h = mx.sym.FullyConnected(h, num_hidden=32, name="layer0_ff1")
    h = mx.sym.Activation(h, act_type="relu", name="layer0_act")
    out = mx.sym.FullyConnected(h, num_hidden=16, name="layer1_ff1")
    fn = build_graph_fn(out)
    rng = np.random.RandomState(0)
    args = {"layer0_ff1_weight": rng.randn(32, 24),
            "layer0_ff1_bias": rng.randn(32),
            "layer1_ff1_weight": rng.randn(16, 32),
            "layer1_ff1_bias": rng.randn(16)}
    args = {k: jnp.asarray(v, jnp.float32) for k, v in args.items()}
    args["data"] = jnp.asarray(rng.randn(24, 8), jnp.float16)

    def forward(args):
        return fn(args, {}, jax.random.PRNGKey(0), False)[0]

    text = jax.jit(forward).lower(args).compile().as_text()
    return hlo.scope_table(text)


def test_table_names_its_program_and_every_record_has_the_fields(
        graph_table):
    assert graph_table.program == "jit_forward"
    assert graph_table.text_bytes > 0 and len(graph_table) > 0
    for name, rec in graph_table.items():
        assert not name.startswith("%")
        assert set(rec) == RECORD_KEYS
        assert rec["klass"] in ("kernel", "collective", "matmul",
                                "relayout", "other")
        assert rec["opcodes"] == sorted(set(rec["opcodes"]))


def test_every_dot_carries_its_nodes_scope(graph_table):
    dots = {n: r for n, r in graph_table.items() if "dot" in r["opcodes"]}
    assert sorted(r["scope"] for r in dots.values()) == \
        ["layer0_ff1", "layer1_ff1"]
    for rec in dots.values():
        assert rec["klass"] == "matmul"
        assert rec["group"] == "layer*_ff1"   # the layer number is gone
        assert rec["scope"] in rec["scopes"]
        assert rec["optimizer"] is False


def test_transpose_and_convert_compute_nothing(graph_table):
    moved = [r for r in graph_table.values()
             if {"layer0_swap", "layer0_widen"} & set(r["scopes"])]
    assert moved
    for rec in moved:
        assert rec["klass"] == "relayout"
        assert set(rec["opcodes"]) <= hlo.RELAYOUT_OPS


def test_parameters_have_no_scope_and_are_relayout(graph_table):
    params = [r for r in graph_table.values()
              if r["opcodes"] == ["parameter"]]
    assert len(params) == 5
    for rec in params:
        assert rec["scope"] == "" and rec["group"] == ""
        assert rec["klass"] == "relayout"


# ---------------------------------------------------------------------
# (b) a tiny Module's fused step
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def fused_module():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=32, name="layer0_ff1")
    h = mx.sym.Activation(h, act_type="relu", name="layer0_act")
    h = mx.sym.FullyConnected(h, num_hidden=16, name="layer1_ff1")
    out = mx.sym.SoftmaxOutput(h, name="softmax")
    mod = mx.mod.Module(out, context=mx.cpu())
    rng = np.random.RandomState(1)
    it = mx.io.NDArrayIter(rng.randn(32, 8).astype("float32"),
                           rng.randint(0, 16, (32,)).astype("float32"),
                           batch_size=16)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="adam", kvstore=None)
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
    return mod


def test_fused_step_updates_are_optimizer_records(fused_module):
    tables = fused_module.fused_program_scopes()
    assert list(tables) == ["jit_step_train"]
    table = tables["jit_step_train"]
    updates = [r for r in table.values() if r["optimizer"]]
    assert updates
    groups = {r["group"] for r in updates}
    assert "optimizer_update/layer*_ff1_weight" in groups
    assert "optimizer_update/layer*_ff1_bias" in groups
    # forward and backward of a node are one scope: the transforms'
    # brackets (jvp, transpose) are taken off
    dots = [r for r in table.values() if "dot" in r["opcodes"]]
    assert len(dots) >= 5
    assert {r["scope"] for r in dots} == {"layer0_ff1", "layer1_ff1"}
    assert not any("(" in r["scope"] for r in table.values())


def test_a_fusion_lists_every_scope_inside_it(fused_module):
    table = fused_module.fused_program_scopes()["jit_step_train"]
    mixed = [r for r in table.values() if len(r["scopes"]) > 1]
    assert mixed  # XLA fuses across the symbol's nodes
    for rec in mixed:
        assert rec["scope"] in rec["scopes"]
        assert rec["scopes"] == sorted(set(rec["scopes"]))


def test_module_table_is_built_once_and_only_when_asked(
        fused_module, monkeypatch):
    first = fused_module.fused_program_scopes()
    assert first["jit_step_train"].seconds > 0
    # whatever else this process still holds (an engine an earlier test
    # file of the same worker closed) is asked once too, here
    profiler.program_scopes()

    def no_text(self):
        raise AssertionError("as_text() called again")

    monkeypatch.setattr(jax.stages.Compiled, "as_text", no_text)
    again = fused_module.fused_program_scopes()
    assert again["jit_step_train"] is first["jit_step_train"]
    assert profiler.program_scopes()["jit_step_train"] \
        is first["jit_step_train"]


# ---------------------------------------------------------------------
# (c) what the CPU's compiler does not emit, by hand
# ---------------------------------------------------------------------
HAND = """\
HloModule jit_prefill_t64, is_scheduled=true, entry_computation_layout={(bf16[64,128]{1,0})->bf16[64,128]{1,0}}

%fused_computation.1 (param_0: bf16[64,128], param_1: bf16[128,128], param_2: bf16[128]) -> bf16[64,128] {
  %param_0 = bf16[64,128]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = bf16[128,128]{1,0:T(8,128)(2,1)} parameter(1)
  %param_2 = bf16[128]{0:T(256)(128)(2,1)} parameter(2)
  %dot.1 = bf16[64,128]{1,0:T(8,128)(2,1)} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(prefill_t64)/layer3_ff1/dot_general" stack_frame_id=4}
  %broadcast.1 = bf16[64,128]{1,0:T(8,128)(2,1)} broadcast(%param_2), dimensions={1}, metadata={op_name="jit(prefill_t64)/layer3_ff1/add" stack_frame_id=5}
  ROOT %add.1 = bf16[64,128]{1,0:T(8,128)(2,1)} add(%dot.1, %broadcast.1), metadata={op_name="jit(prefill_t64)/layer3_ff1/add" stack_frame_id=5}
}

%fused_computation.2 (param_0.1: f32[128,128], param_1.1: bf16[64,128], param_2.1: bf16[64,128]) -> f32[128,128] {
  %param_0.1 = f32[128,128]{1,0} parameter(0)
  %param_1.1 = bf16[64,128]{1,0} parameter(1)
  %param_2.1 = bf16[64,128]{1,0} parameter(2)
  %dot.2 = f32[128,128]{1,0} dot(%param_1.1, %param_2.1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step_train)/transpose(jvp(layer12_ff2))/dot_general"}
  ROOT %subtract.1 = f32[128,128]{1,0} subtract(%param_0.1, %dot.2), metadata={op_name="jit(step_train)/optimizer_update/layer12_ff2_weight/sub"}
}

%fused_computation.3 (param_0.2: bf16[64,128]) -> f32[128,64] {
  %param_0.2 = bf16[64,128]{1,0} parameter(0)
  %transpose.1 = bf16[128,64]{1,0} transpose(%param_0.2), dimensions={1,0}
  ROOT %convert.1 = f32[128,64]{1,0} convert(%transpose.1)
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%scan_body (carry: (s32[], bf16[64,128])) -> (s32[], bf16[64,128]) {
  %carry = (s32[], bf16[64,128]{1,0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%carry), index=0
  %get-tuple-element.2 = bf16[64,128]{1,0} get-tuple-element(%carry), index=1
  %fusion.77 = bf16[64,128]{1,0} fusion(%get-tuple-element.2, %get-tuple-element.2, %get-tuple-element.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(prefill_t64)/layer3_ff1/add"}
  ROOT %tuple.1 = (s32[], bf16[64,128]{1,0}) tuple(%get-tuple-element.1, %fusion.77)
}

%scan_cond (carry.1: (s32[], bf16[64,128])) -> pred[] {
  %carry.1 = (s32[], bf16[64,128]{1,0}) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element(%carry.1), index=0
  %constant.3 = s32[] constant(4)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.3, %constant.3), direction=LT, metadata={op_name="jit(prefill_t64)/mixer0_scan/while/cond/lt"}
}

ENTRY %main.1 (tokens.1: bf16[64,128]) -> bf16[64,128] {
  %tokens.1 = bf16[64,128]{1,0} parameter(0), metadata={op_name="tokens"}
  %copy.96 = bf16[64,128]{0,1} copy(%tokens.1)
  %copy-start.2 = (bf16[64,128]{1,0:S(1)}, bf16[64,128]{1,0}, u32[]) copy-start(%tokens.1)
  %copy-done.2 = bf16[64,128]{1,0:S(1)} copy-done(%copy-start.2)
  %flash.1 = (bf16[64,128]{1,0}, /*index=1*/f32[64,8]{1,0}) custom-call(%copy.96, %copy-done.2), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[64,128]{1,0}, bf16[64,128]{1,0}}, metadata={op_name="jit(prefill_t64)/layer0_attn/jit(flash_mha_packed)/pallas_call" stack_frame_id=9}, backend_config={"custom_call_config": {"body": "AAAA"}}
  %sort.1 = bf16[64,128]{1,0} custom-call(%copy.96), custom_call_target="TopK", metadata={op_name="jit(prefill_t64)/layer0_moe/top_k"}
  %all-reduce-start.1 = bf16[64,128]{1,0} all-reduce-start(%sort.1), replica_groups={{0,1}}, to_apply=%region_add, metadata={op_name="jit(prefill_t64)/layer0_moe/psum"}
  %all-reduce-done.1 = bf16[64,128]{1,0} all-reduce-done(%all-reduce-start.1)
  %fusion.229 = f32[128,128]{1,0} fusion(%copy.96, %sort.1, %sort.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(step_train)/optimizer_update/layer12_ff2_weight/sub"}
  %convert_transpose_fusion = f32[128,64]{1,0} fusion(%all-reduce-done.1), kind=kLoop, calls=%fused_computation.3
  %constant.1 = s32[] constant(0)
  %tuple.2 = (s32[], bf16[64,128]{1,0}) tuple(%constant.1, %all-reduce-done.1)
  %while.1 = (s32[], bf16[64,128]{1,0}) while(%tuple.2), condition=%scan_cond, body=%scan_body, metadata={op_name="jit(prefill_t64)/mixer0_scan/while"}
  ROOT %get-tuple-element.9 = bf16[64,128]{1,0} get-tuple-element(%while.1), index=1
}
"""


@pytest.fixture(scope="module")
def hand_table():
    return hlo.scope_table(HAND)


@pytest.mark.parametrize("name, klass, scope, group", [
    ("flash.1", "kernel", "layer0_attn", "layer*_attn"),
    ("sort.1", "other", "layer0_moe", "layer*_moe"),
    ("all-reduce-start.1", "collective", "layer0_moe", "layer*_moe"),
    ("all-reduce-done.1", "collective", "", ""),
    ("copy.96", "relayout", "", ""),
    ("copy-start.2", "relayout", "", ""),
    ("copy-done.2", "relayout", "", ""),
    ("convert_transpose_fusion", "relayout", "", ""),
    ("fusion.229", "matmul", "optimizer_update/layer12_ff2_weight",
     "optimizer_update/layer*_ff2_weight"),
    ("while.1", "other", "mixer0_scan", "mixer*_scan"),
    ("fusion.77", "matmul", "layer3_ff1", "layer*_ff1"),
    ("compare.1", "other", "mixer0_scan/while/cond",
     "mixer*_scan/while/cond"),
])
def test_hand_written_instruction(hand_table, name, klass, scope, group):
    rec = hand_table[name]
    assert (rec["klass"], rec["scope"], rec["group"]) == \
        (klass, scope, group)


def test_hand_written_program_and_reach(hand_table):
    assert hand_table.program == "jit_prefill_t64"
    # the while's body and condition are run; a fusion's body and a
    # reducer's lambda are what an instruction is made of
    assert {"fusion.77", "tuple.1", "compare.1"} <= set(hand_table)
    assert not {"dot.1", "dot.2", "add.9", "transpose.1"} & set(hand_table)


def test_matmul_fused_with_its_update_lists_both_scopes(hand_table):
    rec = hand_table["fusion.229"]
    assert rec["scopes"] == ["layer12_ff2",
                             "optimizer_update/layer12_ff2_weight"]
    assert rec["optimizer"] is True and rec["klass"] == "matmul"
    assert rec["opcodes"] == ["dot", "parameter", "subtract"]
    bias = hand_table["fusion.77"]     # a dot fused with its bias add
    assert bias["klass"] == "matmul" and bias["optimizer"] is False
    assert bias["opcodes"] == ["add", "broadcast", "dot", "parameter"]


def test_instruction_without_metadata_takes_no_scope(hand_table):
    rec = hand_table["convert_transpose_fusion"]
    assert rec["scope"] == "" and rec["scopes"] == []
    assert rec["opcodes"] == ["convert", "parameter", "transpose"]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(prefill_t1024)/layer3_ff1/dot_general", "layer3_ff1"),
    ("jit(step_train)/transpose(jvp(layer3_ff1))/dot_general",
     "layer3_ff1"),
    ("jit(step_train)/jvp(layer0_act)/jit(relu)/max", "layer0_act"),
    ("jit(step_train)/jvp(layer0_mix/inner)/while/body/mul",
     "layer0_mix/inner/while/body"),
    ("jit(step_train)/optimizer_update/layer3_ff1_weight/mul",
     "optimizer_update/layer3_ff1_weight"),
    ("jit(step_train)/add", ""),
    ("params['layer0_ff1_bias']", ""),
    ("reduce_sum", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert hlo._scope_of(op_name) == scope


@pytest.mark.parametrize("scope, group", [
    ("layer3_ff1", "layer*_ff1"),
    ("optimizer_update/layer12_ff1_weight",
     "optimizer_update/layer*_ff1_weight"),
    ("layer3_mamba2/mamba2_chunk_scan", "layer*_mamba2/mamba2_chunk_scan"),
    ("layer7_retention/retention_step", "layer*_retention/retention_step"),
    ("layer7_g", "layer*_g"),
    ("head", "head"),
    ("", ""),
])
def test_group_drops_the_layers_number_alone(scope, group):
    assert hlo._group_of(scope) == group


# ---------------------------------------------------------------------
# (d) the engine's accessor: lazy, once, under the trace's names
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_params():
    return tiny_lm_params()


def test_engine_that_nobody_asks_reads_no_text(lm_params, monkeypatch):
    def no_text(self):
        raise AssertionError("as_text() called without being asked")

    monkeypatch.setattr(jax.stages.Compiled, "as_text", no_text)
    eng = _engine(lm_params)
    try:
        eng.warmup()
        out = eng.submit(np.arange(5, dtype=np.int32),
                         max_new_tokens=4).result(timeout=WAIT)
        assert len(out) == 4
    finally:
        eng.close()


def test_engine_program_scopes_by_trace_names(lm_params, monkeypatch):
    eng = _engine(lm_params)
    calls = []
    as_text = jax.stages.Compiled.as_text

    def counted(self):
        if any(self is exe for exe in eng._exe_cache.values()):
            calls.append(self)  # (the registry reads other holders')
        return as_text(self)

    monkeypatch.setattr(jax.stages.Compiled, "as_text", counted)
    try:
        eng.submit(np.arange(5, dtype=np.int32),
                   max_new_tokens=3).result(timeout=WAIT)
        assert not calls
        tables = eng.program_scopes()
        # one table an executable, under the name jax gave the program
        assert len(tables) == len(eng._exe_cache) == len(calls)
        assert all(name.startswith("jit_") for name in tables)
        assert any(n.startswith("jit_prefill_t") for n in tables)
        assert any(n.startswith("jit_step_decode_b") for n in tables)
        assert any(n.startswith("jit_next_tokens_b") for n in tables)
        prefill = next(t for n, t in tables.items()
                       if n.startswith("jit_prefill_t"))
        scopes = {r["group"] for r in prefill.values()}
        assert any(g.startswith("layer*_") for g in scopes)
        assert any(r["klass"] == "matmul" for r in prefill.values())
        # asked again: the same tables, no text read again
        again = eng.program_scopes()
        assert len(calls) == len(tables)
        assert all(again[n] is tables[n] for n in tables)
        # the registry needs no handle on the engine
        merged = profiler.program_scopes()
        assert all(merged[n] is tables[n] for n in tables)
    finally:
        eng.close()
    assert len(calls) == len(tables)


def test_closed_and_deleted_engine_is_still_nameable(lm_params):
    import gc

    eng = _engine(lm_params)
    eng.submit(np.arange(6, dtype=np.int32),
               max_new_tokens=2).result(timeout=WAIT)
    names_held = len(eng._exe_cache)
    eng.close()
    del eng
    gc.collect()
    tables = profiler.program_scopes()
    found = [n for n in tables if n.startswith(
        ("jit_prefill_t", "jit_step_decode_b", "jit_next_tokens_b"))]
    assert len(found) >= names_held
    # its executables are let go once the tables stand
    assert profiler._retired["programs"] == {}
    assert profiler.program_scopes().keys() >= set(found)


def test_retention_layers_programs_carry_the_mixers_node_names():
    """A power-retention spec's two programs under the names
    ``models/hybrid_lm.py`` lists for the mixer; the bare ``_g`` is a
    node of its own, which ``scope_group_share``'s whole-name suffixes
    book to no FFN's ``_gate`` (nor the FFN's to it)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.reference import brumby as ref

    cfg = {"hidden_size": 32, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 8, "intermediate_size": 48, "vocab_size": 61,
           "rms_norm_eps": 1e-6, "rope_theta": 1e4}
    w = ref.draw(cfg, 3, embed_dtype="float32", dtype="float32")
    eng = build(ref.program_names(w), model=ref.spec(cfg), max_len=32,
                kv_block=4, max_streams=2, decode_buckets=[2],
                prefill_buckets=[16], temperature=0.0, ctx=mx.cpu(),
                dtype="float32")
    try:
        eng.submit(np.arange(1, 7, dtype=np.int32),
                   max_new_tokens=3).result(timeout=WAIT)
        tables = eng.program_scopes()
    finally:
        eng.close()
    want = {f"layer*_{n}" for n in ("q", "k", "v", "q_norm", "k_norm", "g",
                                    "retention", "o")}
    for prefix in ("jit_prefill_t", "jit_step_decode_b"):
        table = next(t for n, t in tables.items() if n.startswith(prefix))
        nodes = {r["group"].split("/", 1)[0] for r in table.values()}
        nodes |= {s.split("/", 1)[0] for r in table.values()
                  for s in map(hlo._group_of, r["scopes"])}
        assert want <= nodes, sorted(want - nodes)
        assert "layer*_ffn_gate" in nodes
    suffixes = ("layer*_retention",)
    assert not "layer*_ffn_gate".endswith(suffixes)
    assert not "layer*_ffn_gate".endswith(("layer*_g",))
    assert not "layer*_g".endswith(("layer*_gate", "_gate"))


@pytest.mark.parametrize("n", [1, 3, 48])
def test_observe_with_a_count_reads_as_n_observations(n):
    one, many = profiler.MetricsRegistry(), profiler.MetricsRegistry()
    for reg in (one, many):
        reg.observe("tpt", 0.7)
    for _ in range(n):
        one.observe("tpt", 12.5)
    many.observe("tpt", 12.5, n)
    a, b = one.summary()["histograms"], many.summary()["histograms"]
    assert a == b and a["tpt"]["count"] == n + 1
    assert profiler.prometheus_text(one) == profiler.prometheus_text(many)
