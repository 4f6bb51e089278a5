"""The ``afmoe`` family's cell: found by discovery, its configuration
against the catalog, its counters of operations against hand counts,
every new metric file read on a small made-up trace or stats dict, a
tiny configuration through ``serve_pages`` on the CPU, and each control
driven to ``correct: false``."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import ROOT, TINY_ROOT

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import afmoe as flops
from benchmark.flops import paged_attention
from benchmark.reducers import (engine_stat, family_kernel_roofline as roof,
                                moe_load_held, scope_group_share,
                                scope_time, spec_kernel_roofline)

CELL = "trinity-large-ep16.serve-longdoc-closed"
TINY = "afmoe-tiny.serve-tiny-longdoc"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "afmoe")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"flash_mha_roofline.serve", "gqa_attn_share.decode",
       "gqa_attn_share.prefill", "window_pages_held_share.longdoc",
       "prefill_bucket_fill.longdoc", "moe_load_max_over_mean.longdoc"}
CONTROLS = ("fp8", "no_window", "no_rotation", "no_qk_norm", "no_post_norm",
            "no_gate", "no_select_bias")


def metric_args(name):
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))
    return harness.plugin("reducers", spec["reducer"]), spec.get("args", {})


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 and cell.workload["runner"] == "serve_pages"
    assert cell.config["family"] == "afmoe"
    assert cell.traffic["arrivals"]["process"] == "closed"
    eng = cell.workload["engine"]
    assert cell.traffic["arrivals"]["clients"] == eng["max_streams"] \
        == eng["decode_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] == 32768 \
        == eng["prefill_buckets"][-1]
    assert eng["prefill_buckets"] == [4096, 8192, 16384, 32768]
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] == eng["max_len"] == 33280 \
        == eng["cache_buckets"][-1] * eng["kv_block"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    for name in metrics:
        harness.plugin("reducers", metrics[name]["reducer"])
    assert OWN <= set(metrics)
    # what the mixed cell reports and this one does too: all but its own
    # held share and load metric and the dead dispatch-time share
    other = {m["name"] for m, _ in harness.Cell(
        ROOT, "smallthinker-21ba3b-l8.serve-mixed-closed").per_layer()}
    assert not other & OWN
    assert set(metrics) - OWN == other - {
        "window_pages_held_share.mixed", "moe_load_max_over_mean.mixed",
        "engine_prefill_share.closed"}
    assert not [m for m in metrics if m.startswith("mla_")]
    # only this cell reads this PR's metrics; nothing accepted was edited
    # but the lists
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] \
                and m["moves"] == "serve_out_tokens_per_s"
    assert bench["workloads"][-1]["name"] == CELL
    assert len(bench["workloads"][-1]["why"]) <= 200


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = json.loads([ln for ln in open(CATALOG)
                      if '"name": "Trinity-Large-Preview"' in ln][0])
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size"}
    assert cfg["source"] == row["source_url"]
    for k in cfg["reduced"]:
        assert cfg[k + "_published"] == row["config"][k]
        assert cfg["reduced_from"][k]
    assert cfg["layer_types"] == row["config"]["layer_types"]
    assert [cfg["layer_types"][i] for i in cfg["layers_held"]] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    for key in ("deployment", "parameters", "departures", "assumed"):
        assert cfg[key]
    assert "16 chips" in cfg["deployment"]
    entry = [c for c in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["configs"]
        if c["name"] == "trinity-large-ep16"][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    # the parameters held, by the shapes the reference draws
    from benchmark.reference import afmoe as ref
    z = ref.sizes(cfg)
    held = 2 * z["V"] * z["d"] + z["d"]
    for i in range(z["L"]):
        held += sum(int(np.prod(s)) for s in
                    ref._layer_shapes(z, i < z["dense"]).values())
    assert round(held / 1e6) == 2510        # 5.02 GB in bfloat16
    # and the published model by the same shapes: 398.6B
    attn = 3072 * (6144 + 1024 + 1024 + 6144) + 6144 * 3072
    assert attn == 62_914_560
    published = 6 * (attn + 3 * 3072 * 12288) + 54 * (
        attn + 256 * 3072 + 257 * 3 * 3072 * 3072) + 2 * 200192 * 3072
    assert round(published / 1e8) == 3986


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    assert flops.layer_counts(cfg) == (1, 4, 4)
    # one expert: 3 x 3072 x 3072 weights; a pair: 2 flops a weight
    ops, nbytes = flops.moe_gmm(experts_hit=2, pairs=5, cfg=cfg)
    assert ops == 5 * 2 * 3 * 3072 * 3072
    assert nbytes == 2 * 3 * 3072 * 3072 * 2 \
        + 5 * (3072 * 2 + 2 * 3072 * 2 + 3072 * 4)
    # a prompt of 10 positions, 55 causal pairs: 48 heads x 128 lanes,
    # q.k and p.v; q and o at 48 heads, k and v ONCE at 8
    ops, nbytes = flops.flash(10, 55, cfg)
    assert ops == 4 * 55 * 128 * 48
    assert nbytes == 10 * 2 * (48 + 8) * 128 * 2
    stats = {"steps": 10, "stream_steps": 240, "prefills": 4,
             "prefill_tokens": 40000, "context_tokens": 10 * 24 * 10000,
             "window_context_tokens": 10 * 24 * 3500,
             "prefill_pairs": 4 * 50005000,
             "window_prefill_pairs": 4 * 32000000}
    assert flops.need("flash_fwd_mha", stats, cfg, 2) == \
        flops.flash(10000, 50005000, cfg)
    assert flops.need("flash_fwd_window", stats, cfg, 2) == tuple(
        4 * x for x in flops.flash(10000, 32000000, cfg))
    assert flops.need("paged_window", stats, cfg, 2) == \
        paged_attention.decode_step(24 * 3500, 24, 4, 1024, 2)
    assert flops.need("paged_window", {"steps": 0}, cfg, 2) is None
    assert flops.need("paged_window", {"steps": 3}, cfg, 2) is None
    assert flops.need("flash_fwd_mha", {"prefills": 2}, cfg, 2) is None
    assert flops.need("flash_fwd_window", {"prefills": 2}, cfg, 2) is None
    with pytest.raises(ValueError):
        flops.need("mla_flash_fwd", stats, cfg, 2)


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} f32[8,16]]"
    ops = [(k("%paged_window.1"), 11e3, 2e3),
           (k("%paged_attention.2"), 13e3, 1e3),
           (k("%moe_gmm_gate_up_silu.4"), 16e3, 3e3),
           ("%fusion.7", 19e3, 1e3),
           (k("%paged_window.1"), 31e3, 2e3),
           (k("%kv_pages_write.9"), 51e3, 1e3),
           (k("%flash_fwd_window.5"), 53e3, 6e3),
           (k("%flash_fwd_mha.6"), 59e3, 4e3),
           ("%fusion.8", 64e3, 6e3),
           ("%fusion.9", 70e3, 8e3),
           (k("%paged_window.1"), 95e3, 1e3)]    # in no whole program
    modules = [("jit_step_decode_b24x2080(1)", 10e3, 10e3),
               ("jit_step_decode_b24x2080(1)", 30e3, 10e3),
               ("jit_prefill_t8192(2)", 50e3, 30e3),
               ("jit_step_decode_b24x2080(1)", 94e3, 10e3)]  # cut short
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


STATS = {"steps": 10, "stream_steps": 240, "prefills": 4,
         "prefill_tokens": 40000, "prefill_bucket_tokens": 57344,
         "prefill_bucket_fill": 0.6975,
         "context_tokens": 10 * 24 * 10000,
         "window_context_tokens": 10 * 24 * 3500,
         "prefill_pairs": 4 * 50005000,
         "window_prefill_pairs": 4 * 32000000, "moe_experts_hit": 200,
         "moe_pairs_here": 240, "moe_load_max": 60,
         "window_pages_held_share": 0.31}


def test_every_new_metric_file_reads_its_number(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    src = {"trace": made_up_trace(), "engine_stats": STATS, "cell": cell,
           "run": run}
    reader, args = metric_args("flash_mha_roofline.serve")
    ops, _ = flops.need("flash_fwd_mha", STATS, cell.config, 2)
    assert reader.read(src, **args) == pytest.approx(
        100 * (ops / 197e12) / 4e-6)
    # the accepted files, through this family's counts
    reader, args = metric_args("flash_window_roofline.serve")
    ops, _ = flops.need("flash_fwd_window", STATS, cell.config, 2)
    assert reader.read(src, **args) == pytest.approx(
        100 * (ops / 197e12) / 6e-6)
    reader, args = metric_args("paged_window_roofline.serve")
    _, nbytes = flops.need("paged_window", STATS, cell.config, 2)
    assert reader.read(src, **args) == pytest.approx(
        100 * (nbytes / 819e9) / 2e-6)
    reader, args = metric_args("paged_gqa_roofline.serve")
    _, nbytes = paged_attention.decode_step(24 * 10000, 24, 1, 1024, 2)
    assert reader.read(src, **args) == pytest.approx(
        100 * (nbytes / 819e9) / 0.5e-6)
    reader, args = metric_args("moe_gmm_roofline.serve")
    _, nbytes = flops.moe_gmm(20, 24, cell.config)
    assert reader.read(src, **args) == pytest.approx(
        100 * (nbytes / 819e9) / 1.5e-6)
    # the page write is nobody's kernel
    for theirs, program in (("flash_fwd_mha", "jit_step_decode"),
                            ("mla_flash_fwd", "jit_prefill"),
                            ("mla_paged_decode", "jit_step_decode")):
        assert spec_kernel_roofline.kernel_seconds_in(
            src["trace"], theirs, program)[0] == 0.0
    reader, args = metric_args("moe_load_max_over_mean.longdoc")
    assert reader is moe_load_held
    assert reader.read(src, **args) == pytest.approx(60 * 16 / 240)
    reader, args = metric_args("window_pages_held_share.longdoc")
    assert reader is engine_stat and reader.read(src, **args) == 0.31
    reader, args = metric_args("prefill_bucket_fill.longdoc")
    assert reader.read(src, **args) == 0.6975
    # a program without the kernels, the counters or the key (the
    # parent of this PR): nothing to read, nothing raised
    parent = dict(src, engine_stats={"steps": 10, "stream_steps": 240,
                                     "prefills": 4, "prefill_tokens": 4000})
    for name in ("flash_mha_roofline.serve", "prefill_bucket_fill.longdoc",
                 "window_pages_held_share.longdoc",
                 "moe_load_max_over_mean.longdoc"):
        reader, args = metric_args(name)
        assert reader.read(parent, **args) is None
    capfd.readouterr()


def table(**groups):
    return {key: {"scope": g.replace("*", "3"), "group": g,
                  "opcodes": ["fusion"], "klass": klass, "optimizer": False}
            for key, (g, klass) in groups.items()}


def test_gqa_attn_share_books_whole_node_names(monkeypatch, capfd):
    from mxnet_tpu import profiler

    tables = {
        "jit_step_decode_b24x2080": table(**{
            "paged_window.1": ("layer*_attn", "kernel"),
            "paged_attention.2": ("layer*_attn", "kernel"),
            "moe_gmm_gate_up_silu.4": ("layer*_moe", "kernel"),
            "fusion.7": ("layer*_q_norm", "other")}),
        "jit_prefill_t8192": table(**{
            "kv_pages_write.9": ("layer*_attn/write", "kernel"),
            "flash_fwd_window.5": ("layer*_attn", "kernel"),
            "flash_fwd_mha.6": ("layer*_attn", "kernel"),
            "fusion.8": ("layer*_gate", "matmul"),
            # a bare ``_gate`` would book these two as attention's
            "fusion.9": ("layer*_ffn_gate", "matmul")})}
    monkeypatch.setattr(profiler, "program_scopes", lambda: tables,
                        raising=False)
    scope_time._booked.clear()
    src = {"trace": made_up_trace()}
    reader, args = metric_args("gqa_attn_share.decode")
    assert reader is scope_group_share
    # decode: 2 whole programs of 10 us; attn 2 + 1 + 2, q_norm 1
    assert reader.read(src, **args) == pytest.approx(100 * 6 / 20)
    reader, args = metric_args("gqa_attn_share.prefill")
    # prefill: 30 us; write 1 + window 6 + mha 4 + gate 6; NOT ffn_gate
    assert reader.read(src, **args) == pytest.approx(100 * 17 / 30)
    assert set(args["suffixes"]) == {
        "layer*_attn", "layer*_q", "layer*_k", "layer*_v", "layer*_gate",
        "layer*_o", "layer*_q_norm", "layer*_k_norm", "layer*_post_norm1"}
    for wrong in ("layer*_ffn_gate", "layer*_shared_gate", "layer*_moe",
                  "layer*_post_norm2", "layer*_norm1"):
        assert not wrong.endswith(tuple(args["suffixes"]))
    # no tables (the reader's own refusal), no trace
    monkeypatch.setattr(profiler, "program_scopes", lambda: {},
                        raising=False)
    scope_time._booked.clear()
    assert reader.read(src, **args) is None
    assert reader.read({"trace": None}, **args) is None
    scope_time._booked.clear()
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "afmoe-tiny.json"), bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    # answers of 20-28 tokens: a control is judged on two requests
    shutil.copy(os.path.join(DATA, "serve-tiny-longdoc.json"),
                bdir / "traffic")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "afmoe-tiny", "source": "test",
                         "file": "benchmark/configs/afmoe-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "afmoe-tiny",
                           "traffic": "serve-tiny-longdoc", "chips": 1,
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
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    by = checks(lines)
    assert by["served_logit_gap_widest"]["value"] < 1e-3
    assert not [ln for ln in lines if "control" in ln]
    eng = [ln for ln in lines if "engine" in ln][0]["engine"]
    # prompts of up to 36 tokens through a window of 16: pages came back
    assert eng["window_pages_released"] > 0 and eng["preempted"] == 0
    assert 0 < eng["window_pages_held_share"] < 1
    assert eng["window_context_tokens"] < eng["context_tokens"]
    assert eng["moe_pairs_elsewhere"] > 0
    longest = [ln for ln in lines if "compared_lengths" in ln][0]
    assert longest["compared_lengths"][0] > 2 * 16
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report
    assert set(result["metrics"]) >= {
        "moe_load_max_over_mean.longdoc", "window_pages_held_share.longdoc",
        "prefill_bucket_fill.longdoc", "decode_batch_fill.closed",
        "engine_ttft_p50_ms.closed", "programs_built.setup"}
    assert 0 < result["metrics"]["prefill_bucket_fill.longdoc"]["value"] <= 1
    assert not [m for m in result["metrics"]
                if "roofline" in m or "gqa_attn_share" in m]


def test_each_control_reads_not_correct(tiny_root, capfd, monkeypatch):
    """``control_pages``, unedited, with ``--controls`` naming the forms
    this family's reference knows: the run itself is correct; the
    reference in each form fails a limit of the cell."""
    from benchmark import control_pages
    from benchmark.runners import serve_lm, serve_pages

    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)
    monkeypatch.setattr(serve_pages, "CONTROLS", ())
    rc = control_pages.main(
        ["--workload", TINY, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--controls", ",".join(CONTROLS)],
        root=tiny_root, require_tpu=False)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    verdict = {ln["control"]: ln["correct"] for ln in lines
               if "control" in ln}
    assert verdict == dict.fromkeys(CONTROLS, False)


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


def test_a_program_without_the_keys_ends_the_run_at_once(
        run_cell, tiny_root, monkeypatch):
    """The parent commit, given this PR's benchmark files: its layer
    list knows no q/k norm, and the reference's ``spec`` says so before
    anything is drawn."""
    from mxnet_tpu.models import hybrid_lm

    monkeypatch.setitem(hybrid_lm.MIXERS, "attention", tuple(
        k for k in hybrid_lm.MIXERS["attention"] if k != "qk_norm"))
    with pytest.raises(NotImplementedError, match="qk_norm"):
        run_cell(TINY, root=tiny_root)
