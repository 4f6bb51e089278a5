"""The ``zaya`` family's cell: found by discovery, its configuration
against the catalog, its counters of operations against hand counts,
every new metric file read on a small made-up trace or stats dict, a
tiny configuration through ``serve_pages_relative`` on the CPU, and an altered
served token, a tail read as zeros and each control form driven to
``correct: false``."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import ROOT, TINY_ROOT

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import paged_attention
from benchmark.flops import zaya as flops
from benchmark.reducers import (engine_stat_ratio, moe_load_held,
                                scope_group_share)

CELL = "zaya1-8b-pp2.serve-rollout-closed"
TINY = "zaya-tiny.serve-tiny-rollout"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "zaya")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"cca_share.decode", "cca_share.prefill", "router_share.decode",
       "moe_load_max_over_mean.rollout", "moe_rows_per_expert.rollout"}
CONTROLS = ("fp8", "no_conv", "no_qk_mean", "value_current", "no_rotation",
            "rotate_all", "no_temperature", "no_carry", "weight_one",
            "no_select_bias", "no_residual_scale")


def metric_args(name):
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    return harness.plugin("reducers", spec["reducer"]), spec.get("args", {})


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 \
        and cell.workload["runner"] == "serve_pages_relative"
    # the nearest precision below the configuration's is held: the mean
    # gap as a multiple of the reference's own bfloat16 form's
    assert set(cell.workload["limits"]) == {
        "sample_requests", "logit_gap_widest",
        "logit_gap_mean_over_bfloat16", "logit_gap_mean_floor"}
    assert cell.config["family"] == "zaya"
    assert cell.traffic["arrivals"]["process"] == "closed"
    eng = cell.workload["engine"]
    assert cell.traffic["arrivals"]["clients"] == eng["max_streams"] \
        == eng["decode_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] == eng["prefill_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] == eng["max_len"] \
        == eng["cache_buckets"][-1] * eng["kv_block"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    assert OWN <= set(metrics)
    for name in metrics:
        harness.plugin("reducers", metrics[name]["reducer"])
    # beside its own five, what the closed-loop cell of grouped-query
    # attention over sigmoid experts reports, less its windows
    longdoc = {m["name"] for m, _ in harness.Cell(
        ROOT, "trinity-large-ep16.serve-longdoc-closed").per_layer()}
    assert not longdoc & OWN
    assert set(metrics) - OWN == longdoc - {
        "paged_window_roofline.serve", "flash_window_roofline.serve",
        "gqa_attn_share.decode", "gqa_attn_share.prefill",
        "window_pages_held_share.longdoc", "prefill_bucket_fill.longdoc",
        "moe_load_max_over_mean.longdoc"}
    # the configuration, the cell and the metrics keep the driver's form
    bench = cell.bench
    assert [c["name"] for c in bench["configs"]][-1] == "zaya1-8b-pp2"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [m["name"] for m in bench["per_layer"]][-5:] == [
        "cca_share.decode", "cca_share.prefill", "router_share.decode",
        "moe_load_max_over_mean.rollout", "moe_rows_per_expert.rollout"]
    for entry in (bench["configs"][-1], bench["workloads"][-1]):
        assert len(entry["why"]) <= 200


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = json.loads([ln for ln in open(CATALOG)
                      if '"name": "ZAYA1-8B"' in ln][0])
    published = row["config"]
    changed = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"],
            cfg["layers_held"]) == (20, 40, list(range(20)))
    assert (cfg["num_experts"], cfg["router_hidden_size"],
            cfg["vocab_size"]) == (16, 256, 262272)
    assert (cfg["compute_dtype"], cfg["router_dtype"], cfg["kv_dtype"],
            cfg["tail_dtype"]) == ("bfloat16", "float32", "bfloat16",
                                   "float32")
    assert "2 pipeline stages" in cfg["deployment"] \
        and "9.38 GB" in cfg["parameters"]
    for key in ("latent_widths", "convolutions", "qk_mean", "qk_norm",
                "rotation", "value_shift", "router", "residual",
                "router_dtype", "initialisation"):
        assert key in cfg["assumed"]
    # the parameters held, counted from the shapes the reference draws
    from benchmark.reference import zaya as ref

    z = ref.sizes(cfg)
    layer = sum(int(np.prod(s)) for s in ref._layer_shapes(z, False).values())
    assert round(layer / 1e6, 2) == 207.58
    held = 20 * layer - 256 + z["V"] * z["d"]   # layer 0 has no carry gain
    assert round(2 * held / 1e9, 2) == 9.38


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    assert flops.layer_counts(cfg) == (20, 0, 20)
    # a decode step of 128 rows, every expert hit: the three matrices of
    # 16 experts, a row's input, hidden pair and float32 output
    ops, nbytes = flops.moe_gmm(16, 128, cfg)
    assert ops == 128 * 3 * 2 * 2048 * 2048
    assert nbytes == 16 * 3 * 2048 * 2048 * 2 \
        + 128 * (2048 * 2 + 2 * 2048 * 2 + 2048 * 4)
    # the pages: 2 KV heads x 128 lanes of K and of V, 1,024 B a token
    # and layer in bfloat16
    _, nbytes = paged_attention.decode_step(1000, 0, 1, 2 * 128, 2)
    assert nbytes == 1000 * 1024
    # a prompt of 10 tokens, 55 causal pairs, 8 query heads over 2 KV
    stats = {"prefills": 4, "prefill_tokens": 40, "prefill_pairs": 220}
    ops, nbytes = flops.need("flash_fwd_mha", stats, cfg, 2)
    assert ops == 20 * 4 * 55 * 128 * 8
    assert nbytes == 20 * 10 * 2 * (8 + 2) * 128 * 2
    assert flops.need("flash_fwd_mha", {"prefills": 0}, cfg, 2) is None
    with pytest.raises(ValueError):
        flops.need("flash_fwd_window", stats, cfg, 2)


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} f32[8,16]]"
    ops = [(k("%paged_attention.2"), 13e3, 1e3),
           (k("%moe_gmm_gate_up_silu.4"), 16e3, 3e3),
           (k("%paged_attention.2"), 31e3, 1e3),
           (k("%kv_pages_write.9"), 51e3, 1e3),
           (k("%flash_fwd_mha.6"), 59e3, 4e3)]
    modules = [("jit_step_decode_b128x96(1)", 10e3, 10e3),
               ("jit_step_decode_b128x96(1)", 30e3, 10e3),
               ("jit_prefill_t1024(2)", 50e3, 30e3)]
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


STATS = {"steps": 10, "stream_steps": 1280, "prefills": 4,
         "prefill_tokens": 2560, "context_tokens": 10 * 128 * 800,
         "prefill_pairs": 4 * 205120, "moe_experts_hit": 10 * 20 * 16,
         "moe_pairs_here": 10 * 20 * 128, "moe_load_max": 10 * 20 * 19}


def test_every_metric_file_of_the_cell_reads_its_number(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    src = {"trace": made_up_trace(), "engine_stats": STATS, "cell": cell,
           "run": run}
    reader, args = metric_args("moe_rows_per_expert.rollout")
    assert reader is engine_stat_ratio
    assert reader.read(src, **args) == 8.0
    reader, args = metric_args("moe_load_max_over_mean.rollout")
    assert reader is moe_load_held
    assert reader.read(src, **args) == pytest.approx(19 / 8)
    # the accepted files, through this family's counts
    reader, args = metric_args("flash_mha_roofline.serve")
    ops, _ = flops.need("flash_fwd_mha", STATS, cell.config, 2)
    assert reader.read(src, **args) == pytest.approx(
        100 * (ops / 197e12) / 4e-6)
    reader, args = metric_args("paged_gqa_roofline.serve")
    _, nbytes = paged_attention.decode_step(128 * 800, 128, 20, 256, 2)
    assert reader.read(src, **args) == pytest.approx(
        100 * (nbytes / 819e9) / 1e-6)
    reader, args = metric_args("moe_gmm_roofline.serve")
    _, nbytes = flops.moe_gmm(20 * 16, 20 * 128, cell.config)
    assert reader.read(src, **args) == pytest.approx(
        100 * (nbytes / 819e9) / 1.5e-6)
    # a program without the counters (the parent of this PR): nothing to
    # read, nothing raised
    parent = dict(src, engine_stats={"steps": 10, "stream_steps": 240})
    for name in ("moe_rows_per_expert.rollout",
                 "moe_load_max_over_mean.rollout",
                 "flash_mha_roofline.serve"):
        reader, args = metric_args(name)
        assert reader.read(parent, **args) is None
    assert engine_stat_ratio.read({}, "a", "b") is None
    assert engine_stat_ratio.read({"engine_stats": {"a": 3, "b": 0}},
                                  "a", "b") is None
    capfd.readouterr()


def test_the_shares_book_whole_node_names(monkeypatch, capfd):
    """``layer*_q`` is the mixer's, ``layer*_q_norm`` nobody's here;
    ``layer*_router_1`` the router's, ``layer*_moe`` not."""
    from benchmark.reducers import scope_time

    def table(**groups):
        return {f"i{n}": {"group": g} for n, g in enumerate(groups.values())}

    nodes = dict(a="layer*_q", b="layer*_mix/dot_general", c="layer*_qk_norm",
                 d="layer*_attn/paged_attention", e="layer*_router_1",
                 f="layer*_router_carry", g="layer*_moe/gmm",
                 h="layer*_q_norm", i="layer*_res1", j="layer*_v2")
    tables = {"jit_step_decode_b128x96": table(**nodes)}
    rows = {"jit_step_decode_b128x96": {
        "seconds": 20.0, "ops": {f"i{n}": 1.0 for n in range(len(nodes))}}}
    monkeypatch.setattr(scope_time, "booked", lambda trace: (rows, tables))
    src = {"trace": made_up_trace()}
    reader, args = metric_args("cca_share.decode")
    assert reader is scope_group_share
    assert reader.read(src, **args) == pytest.approx(100 * 5 / 20)
    reader, args = metric_args("router_share.decode")
    assert reader.read(src, **args) == pytest.approx(100 * 2 / 20)
    reader, args = metric_args("cca_share.prefill")
    assert reader.read(src, **args) is None     # no such program booked
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "zaya-tiny.json"), bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    # answers of 20-28 tokens: a control is judged on two requests
    shutil.copy(os.path.join(DATA, "serve-tiny-rollout.json"),
                bdir / "traffic")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "zaya-tiny", "source": "test",
                         "file": "benchmark/configs/zaya-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "zaya-tiny",
                           "traffic": "serve-tiny-rollout", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"]:
        if "gpt2-tiny.serve-tiny-closed" in m.get("workloads", ()):
            m["workloads"].append(TINY)
    have = {m["name"]: m for m in b["per_layer"]}
    for m in real["per_layer"]:
        if CELL not in m.get("workloads", ()):
            continue
        if m["name"] in have:        # a metric the closed-loop cells share
            have[m["name"]]["workloads"].append(TINY)
        else:
            b["per_layer"].append(dict(m, workloads=[TINY]))
            shutil.copy(os.path.join(ROOT, "benchmark", "layer_metrics",
                                     m["name"] + ".json"),
                        bdir / "layer_metrics")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


def checks(lines):
    return {ln["check"]: ln for ln in lines if "check" in ln}


def test_tiny_cell_agrees_with_reference(run_cell, tiny_root):
    result, lines = run_cell(TINY, root=tiny_root)
    assert result["correct"] is True and result["failed"] == 0, [
        ln for ln in lines if "error" in ln or ln.get("ok") is False]
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    by = checks(lines)
    assert by["served_logit_gap_widest"]["ok"] is True
    assert by["served_logit_gap_mean_over_bfloat16"]["ok"] is True
    assert not [ln for ln in lines if "control" in ln]
    eng = [ln for ln in lines if "engine" in ln][0]["engine"]
    # one expert a token, every expert held: nothing left elsewhere
    assert eng["moe_pairs_here"] > 0 and eng["moe_pairs_elsewhere"] == 0
    assert eng["preempted"] == 0
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report, the rooflines and shares do not
    assert set(result["metrics"]) >= {
        "moe_load_max_over_mean.rollout", "moe_rows_per_expert.rollout",
        "decode_batch_fill.closed", "engine_ttft_p50_ms.closed",
        "programs_built.setup"}
    rows = result["metrics"]["moe_rows_per_expert.rollout"]["value"]
    assert 1.0 <= rows <= 4.0       # at most 4 rows a step, 4 experts
    assert not [m for m in result["metrics"] if "roofline" in m
                or m.startswith(("cca_share", "router_share"))]


# at three layers of 64 and four requests of some 24 tokens, a form whose
# share is small (fp8, the temperature, the carry) reads 0.01-0.15 by the
# sample that happened to finish; these read false on every sample
ROBUST = ("no_conv", "no_qk_mean", "value_current", "no_rotation",
          "rotate_all", "weight_one", "no_select_bias", "no_residual_scale")


def test_each_control_is_run_and_the_robust_ones_read_not_correct(
        tiny_root, capfd, monkeypatch):
    """``control_cca``: the run itself is correct; every form named is
    run and reported against the run's own divisor, and the reference in
    each form that a tiny size can hold fails a limit of the cell."""
    from benchmark import control_cca
    from benchmark.runners import serve_lm, serve_pages_relative

    assert control_cca.MUST_FAIL == CONTROLS
    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)
    monkeypatch.setattr(serve_pages_relative, "CONTROLS", ())
    monkeypatch.setattr(serve_pages_relative, "CONTROL_REQUESTS", None)
    monkeypatch.setattr(control_cca, "MUST_FAIL", ROBUST)
    rc = control_cca.main(
        ["--workload", TINY, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--requests", "3", "--controls",
         ",".join(CONTROLS)],
        root=tiny_root, require_tpu=False)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    told = {ln["control"]: ln for ln in lines if "control" in ln}
    assert set(told) == set(CONTROLS)
    assert {c: told[c]["correct"] for c in ROBUST} \
        == dict.fromkeys(ROBUST, False)
    # one divisor for the forms, of the requests they were read on
    assert {ln["requests"] for ln in told.values()} == {3}
    assert len({ln["own_logit_gap_mean"] for ln in told.values()}) == 1


def relative_run(served, own, limits, prefix=""):
    from benchmark.runners import serve_pages_relative

    return serve_pages_relative.held(
        prefix, limits, np.asarray(served), np.asarray(own), [])


@pytest.mark.parametrize("served,own,correct", [
    ([0.0, 0.2, 0.0, 0.2], [0.1, 0.0, 0.0, 0.1], True),     # 2 x its own
    ([0.0, 0.4, 0.0, 0.4], [0.1, 0.0, 0.0, 0.1], False),    # 4 x
    ([0.0, 0.0, 0.0, 0.9], [0.1, 0.1, 0.1, 0.1], False),    # one wide gap
    ([0.0, 0.0, 0.0, 0.1], [0.0, 0.0, 0.0, 0.0], True),     # 2.5 x the floor
    ([0.0, 0.2, 0.0, 0.0], [0.0, 0.0, 0.0, 0.0], False),    # 5 x the floor
], ids=["twice_its_own", "four_times_its_own", "one_wide_gap",
        "nothing_lost_in_bfloat16", "nothing_lost_and_a_level"])
def test_the_mean_is_held_as_a_multiple_of_the_samples_own(
        served, own, correct, capfd):
    """By hand: limit 3 x the own form's mean on the same positions, the
    divisor no less than the floor; the widest on its own."""
    limits = {"logit_gap_widest": 0.5, "logit_gap_mean_over_bfloat16": 3.0,
              "logit_gap_mean_floor": 0.01}
    assert relative_run(served, own, limits) is correct
    by = checks([json.loads(ln) for ln in
                 capfd.readouterr().out.splitlines()])
    ratio = by["served_logit_gap_mean_over_bfloat16"]["value"]
    assert ratio == pytest.approx(
        np.mean(served) / max(np.mean(own), 0.01))


def test_altered_served_token_is_not_correct(run_cell, tiny_root,
                                             monkeypatch):
    from benchmark.runners import serve_lm

    def altered(future):
        out = np.asarray(future.result()).copy()
        out[len(out) // 2] = out[len(out) // 2] % 7 + 1
        return out

    monkeypatch.setattr(serve_lm, "served_tokens", altered)
    result, lines = run_cell(TINY, root=tiny_root)
    assert result["correct"] is False
    assert checks(lines)["served_logit_gap_widest"]["ok"] is False


def test_a_tail_read_as_zeros_is_not_correct(run_cell, tiny_root,
                                             monkeypatch):
    """The engine itself with every step's tail read as zeros (a stale,
    lost or unwritten slot): the next token's q, k and v move, and the
    run reads false by the served logits."""
    from mxnet_tpu.ops import hybrid

    real = hybrid.cca_mix

    def no_tail(q, k, w0, w1, left_u, left_c, H, Hkv):
        return real(q, k, w0, w1, 0.0 * left_u, 0.0 * left_c, H, Hkv)

    monkeypatch.setattr(hybrid, "cca_mix", no_tail)
    result, lines = run_cell(TINY, root=tiny_root)
    assert result["correct"] is False
    by = checks(lines)
    assert by["served_logit_gap_widest"]["ok"] is False \
        or by["served_logit_gap_mean_over_bfloat16"]["ok"] is False


def test_a_program_without_the_kind_ends_the_run_at_once(
        run_cell, tiny_root, monkeypatch):
    """The parent commit, given this PR's benchmark files: its layer
    list knows no ``cca`` mixer, and the reference's ``spec`` says so
    before anything is drawn."""
    from mxnet_tpu.models import hybrid_lm

    monkeypatch.delitem(hybrid_lm.MIXERS, "cca")
    with pytest.raises(NotImplementedError, match="cca"):
        run_cell(TINY, root=tiny_root)
