"""The ``deepseek_v3`` family's cell: found by discovery, its
configuration against the catalog, its counters of operations against
hand counts, the family-counted roofline reducer and the new scope-group
reader on small made-up traces (and the accepted readers deaf to the
latent kernels' names), a tiny configuration through ``serve_pages`` on
the CPU, and each control driven to ``correct: false``."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import ROOT, TINY_ROOT

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import deepseek_v3 as flops
from benchmark.reducers import (family_kernel_roofline as roof,
                                moe_load_held, scope_group_share,
                                scope_time, spec_kernel_roofline)

CELL = "deepseek-v3-ep32.serve-longctx-closed"
TINY = "deepseek-v3-tiny.serve-tiny-latent"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "deepseek_v3")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"mla_paged_roofline.serve", "mla_flash_roofline.serve",
       "moe_load_max_over_mean.longctx", "mla_attn_share.decode",
       "mla_attn_share.prefill"}
ACCEPTED_NAMES = ("paged_attention", "paged_window", "flash_fwd_mha",
                  "flash_fwd_window", "flash_fwd_packed", "moe_gmm",
                  "kv_pages_write", "kda_step", "kda_chunk", "mamba2_step",
                  "mamba2_chunk")


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 and cell.workload["runner"] == "serve_pages"
    assert cell.config["family"] == "deepseek_v3"
    assert cell.traffic["arrivals"]["process"] == "closed"
    eng = cell.workload["engine"]
    assert cell.traffic["arrivals"]["clients"] == eng["max_streams"] \
        == eng["decode_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] <= eng["prefill_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] <= eng["max_len"] \
        == eng["cache_buckets"][-1] * eng["kv_block"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    for name in OWN | {"moe_gmm_roofline.serve"}:
        harness.plugin("reducers", metrics[name]["reducer"])
    # what the mixed cell reports and this one does too: all but its own
    # kernels', its load metric, the grouped-query paged kernel's and
    # the dead dispatch-time share
    other = {m["name"] for m, _ in harness.Cell(
        ROOT, "smallthinker-21ba3b-l8.serve-mixed-closed").per_layer()}
    assert not other & OWN
    assert set(metrics) - OWN == other - {
        "paged_window_roofline.serve", "flash_window_roofline.serve",
        "window_pages_held_share.mixed", "moe_load_max_over_mean.mixed",
        "paged_gqa_roofline.serve", "engine_prefill_share.closed"}
    # no name of a latent kernel holds a name an accepted reader matches
    # kernels by, and none of those holds ``mla``
    for mine in ("mla_flash_fwd", "mla_paged_decode", "mla_latent_write"):
        for theirs in ACCEPTED_NAMES:
            assert theirs not in mine and "mla" not in theirs


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = json.loads([ln for ln in open(CATALOG)
                      if '"name": "DeepSeek-V3"' in ln][0])
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert cfg["source"] == row["source_url"]
    for k in cfg["reduced"]:
        assert cfg[k + "_published"] == row["config"][k]
        assert cfg["reduced_from"][k]
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]
    for key in ("deployment", "parameters", "departures", "assumed"):
        assert cfg[key]
    assert "32 chips" in cfg["deployment"] and "13" in cfg["deployment"]
    entry = [c for c in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["configs"]
        if c["name"] == "deepseek-v3-ep32"][0]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    # the parameters held, by the shapes the reference draws
    from benchmark.reference import deepseek_v3 as ref
    z = ref.sizes(cfg)
    held = 2 * z["V"] * z["d"] + z["d"]
    for i in range(z["L"]):
        held += sum(int(np.prod(s)) for s in
                    ref._layer_shapes(z, i < z["dense"]).values())
    assert round(held / 1e6) == 3156        # 6.31 GB in bfloat16


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    assert flops.layer_counts(cfg) == (5, 0, 4)
    # one expert: 3 x 7168 x 2048 weights; a pair: 2 flops a weight
    ops, nbytes = flops.moe_gmm(experts_hit=2, pairs=5, cfg=cfg)
    assert ops == 5 * 2 * 3 * 7168 * 2048
    assert nbytes == 2 * 3 * 7168 * 2048 * 2 \
        + 5 * (7168 * 2 + 2 * 2048 * 2 + 7168 * 4)
    # a step over 1,000 cached rows in 4 streams: 128 heads score 576
    # values and weigh 512; the rows once, queries in, latents out
    ops, nbytes = flops.mla_paged_decode(1000, 4, cfg)
    assert ops == 2 * 128 * (576 + 512) * 1000
    assert nbytes == (1000 * 576 + 4 * 128 * (576 + 512)) * 2
    # a prompt of 10 positions, 55 causal pairs
    ops, nbytes = flops.mla_flash_fwd(10, 55, cfg)
    assert ops == 2 * 128 * (192 + 128) * 55
    assert nbytes == 10 * (128 * 192 + 128 * 128 + 64 + 128 * 128
                           + 128 * 128) * 2
    stats = {"steps": 10, "stream_steps": 480, "prefills": 4,
             "prefill_tokens": 4000, "context_tokens": 10 * 48 * 4000,
             "prefill_pairs": 4 * 500500}
    assert flops.need("mla_paged_decode", stats, cfg, 2) == tuple(
        5 * x for x in flops.mla_paged_decode(48 * 4000, 48, cfg))
    assert flops.need("mla_flash_fwd", stats, cfg, 2) == tuple(
        5 * x for x in flops.mla_flash_fwd(1000, 500500, cfg))
    assert flops.need("mla_paged_decode", {"steps": 0}, cfg, 2) is None
    assert flops.need("mla_paged_decode", {"steps": 3}, cfg, 2) is None
    assert flops.need("mla_flash_fwd", {"prefills": 2}, cfg, 2) is None
    with pytest.raises(ValueError):
        flops.need("paged_window", stats, cfg, 2)
    # the step's kernel sits on the chip's ridge (240): 242 FLOP a byte
    # of cache, 228 with the queries in and the latents out
    ops, nbytes = flops.mla_paged_decode(48 * 4000, 48, cfg)
    assert 225 < ops / nbytes < 242


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} f32[8,16]]"
    ops = [(k("%mla_paged_decode.1"), 11e3, 2e3),
           (k("%mla_paged_decode.2"), 13e3, 2e3),
           (k("%moe_gmm_gate_up_silu.4"), 16e3, 3e3),
           ("%fusion.7", 19e3, 1e3),
           (k("%mla_paged_decode.1"), 31e3, 2e3),
           (k("%mla_latent_write.9"), 51e3, 1e3),
           (k("%mla_flash_fwd.5"), 53e3, 10e3),
           ("%fusion.8", 64e3, 6e3),
           ("%fusion.9", 70e3, 8e3),
           (k("%mla_paged_decode.1"), 95e3, 1e3)]    # in no whole program
    modules = [("jit_step_decode_b48x544(1)", 10e3, 10e3),
               ("jit_step_decode_b48x544(1)", 30e3, 10e3),
               ("jit_prefill_t8192(2)", 50e3, 30e3),
               ("jit_step_decode_b48x544(1)", 94e3, 10e3)]  # cut short
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


STATS = {"steps": 10, "stream_steps": 480, "prefills": 4,
         "prefill_tokens": 16000, "context_tokens": 10 * 48 * 4000,
         "prefill_pairs": 4 * 8002000, "moe_experts_hit": 200,
         "moe_pairs_here": 1200, "moe_load_max": 300}


def test_roofline_shares_by_the_familys_own_count(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    src = {"trace": made_up_trace(), "engine_stats": STATS, "cell": cell,
           "run": run}
    got = roof.read(src, kernel="mla_paged_decode",
                    program="jit_step_decode")
    ops, nbytes = flops.need("mla_paged_decode", STATS, cell.config, 2)
    assert got == pytest.approx(
        100 * max(ops / 197e12, nbytes / 819e9) / 3e-6)
    got = roof.read(src, kernel="mla_flash_fwd", program="jit_prefill")
    ops, _ = flops.need("mla_flash_fwd", STATS, cell.config, 2)
    assert got == pytest.approx(100 * (ops / 197e12) / 10e-6)
    # the accepted readers: the grouped matmul is a moe_gmm kernel; the
    # paged and flash readers of the other cells find nothing of theirs
    got = spec_kernel_roofline.read(src, kernel="moe_gmm",
                                    program="jit_step_decode")
    _, nbytes = flops.moe_gmm(20, 120, cell.config)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 1.5e-6)
    for theirs, program in (("paged_attention", "jit_step_decode"),
                            ("paged_window", "jit_step_decode"),
                            ("flash_fwd_window", "jit_prefill"),
                            ("kv_pages_write", "jit_prefill")):
        assert spec_kernel_roofline.kernel_seconds_in(
            src["trace"], theirs, program)[0] == 0.0
    assert moe_load_held.read(src, held_key="n_routed_experts") == \
        pytest.approx(300 * 8 / 1200)
    # a program without the kernels or the counters (the parent of this
    # PR): nothing to read, nothing raised
    parent = dict(src, engine_stats={"steps": 10, "stream_steps": 480,
                                     "prefills": 4, "prefill_tokens": 4000})
    assert roof.read(parent, kernel="mla_paged_decode",
                     program="jit_step_decode") is None
    assert roof.read(parent, kernel="mla_flash_fwd",
                     program="jit_prefill") is None
    assert moe_load_held.read(parent, held_key="n_routed_experts") is None
    capfd.readouterr()


def table(**groups):
    return {key: {"scope": g.replace("*", "3"), "group": g,
                  "opcodes": ["fusion"], "klass": klass, "optimizer": False}
            for key, (g, klass) in groups.items()}


def test_scope_group_share_on_a_hand_written_trace(monkeypatch, capfd):
    from mxnet_tpu import profiler

    tables = {
        "jit_step_decode_b48x544": table(**{
            "mla_paged_decode.1": ("layer*_attn", "kernel"),
            "mla_paged_decode.2": ("layer*_attn", "kernel"),
            "moe_gmm_gate_up_silu.4": ("layer*_moe", "kernel"),
            "fusion.7": ("layer*_absorb_k", "matmul")}),
        "jit_prefill_t8192": table(**{
            "mla_latent_write.9": ("layer*_attn/write", "kernel"),
            "mla_flash_fwd.5": ("layer*_attn", "kernel"),
            "fusion.8": ("layer*_q_up", "matmul"),
            "fusion.9": ("layer*_ffn_gate", "matmul")})}
    monkeypatch.setattr(profiler, "program_scopes", lambda: tables,
                        raising=False)
    scope_time._booked.clear()
    src = {"trace": made_up_trace()}
    # decode: 2 whole programs of 10 us; attn 2 + 2 + 2, absorb 1
    assert scope_group_share.read(src, program="jit_step_decode") == \
        pytest.approx(100 * 7 / 20)
    # prefill: 30 us; write 1 + flash 10 + q_up 6 (the FFN's 8 is not)
    assert scope_group_share.read(src, program="jit_prefill") == \
        pytest.approx(100 * 17 / 30)
    assert scope_group_share.read(src, program="jit_prefill",
                                  suffixes=("_ffn_gate",)) == \
        pytest.approx(100 * 8 / 30)
    # a program of another family (no such node), no tables, no trace
    assert scope_group_share.read(src, program="jit_prefill",
                                  suffixes=("_mamba2",)) is None
    assert scope_group_share.read({"trace": None},
                                  program="jit_prefill") is None
    monkeypatch.setattr(profiler, "program_scopes", lambda: {},
                        raising=False)
    scope_time._booked.clear()
    assert scope_group_share.read(src, program="jit_prefill") is None
    scope_time._booked.clear()
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "deepseek-v3-tiny.json"),
                bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    # answers of 20-28 tokens: a control is judged on two requests
    shutil.copy(os.path.join(DATA, "serve-tiny-latent.json"),
                bdir / "traffic")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "deepseek-v3-tiny", "source": "test",
                         "file": "benchmark/configs/deepseek-v3-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "deepseek-v3-tiny",
                           "traffic": "serve-tiny-latent", "chips": 1,
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
    assert by["served_logit_gap_widest"]["value"] < 1e-4
    assert not [ln for ln in lines if "control" in ln]
    eng = [ln for ln in lines if "engine" in ln][0]["engine"]
    assert eng["preempted"] == 0 and eng["moe_pairs_elsewhere"] > 0
    longest = [ln for ln in lines if "compared_lengths" in ln][0]
    assert longest["compared_lengths"][0] > 2 * 16
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report
    assert set(result["metrics"]) >= {
        "moe_load_max_over_mean.longctx", "decode_batch_fill.closed",
        "engine_ttft_p50_ms.closed", "programs_built.setup"}
    assert not [m for m in result["metrics"]
                if "roofline" in m or "mla_attn_share" in m]


def test_each_control_reads_not_correct(tiny_root, capfd, monkeypatch):
    """``control_latent``: the run itself is correct; the reference in
    each form the cell's limits must hold fails one of them."""
    from benchmark import control_latent
    from benchmark.runners import serve_lm, serve_pages

    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)
    monkeypatch.setattr(serve_pages, "CONTROLS", ())
    rc = control_latent.main(
        ["--workload", TINY, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0"], root=tiny_root, require_tpu=False)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    verdict = {ln["control"]: ln["correct"] for ln in lines
               if "control" in ln}
    # at this size (float32 program, limits of its own) the reported
    # form fails too; at full size it does not (PERF.md section 2)
    assert verdict == dict.fromkeys(
        control_latent.MUST_FAIL + control_latent.REPORTED, False)


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


def test_a_program_without_the_family_ends_the_run_at_once(
        run_cell, tiny_root, monkeypatch):
    """The parent commit, given this PR's benchmark files: its layer
    list knows no latent attention, and the reference's ``spec`` says so
    before anything is drawn."""
    from mxnet_tpu.models import hybrid_lm

    monkeypatch.setattr(hybrid_lm, "MIXERS", {
        k: v for k, v in hybrid_lm.MIXERS.items() if k != "mla"})
    with pytest.raises(NotImplementedError, match="mla"):
        run_cell(TINY, root=tiny_root)
