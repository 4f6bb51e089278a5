"""The ``smallthinker`` family's cell: found by discovery, its counters
of operations against hand counts, the family-counted roofline reducer
on a small made-up trace (and the accepted readers deaf to the windowed
kernels' names), a tiny configuration through ``serve_pages`` on the
CPU, and each control — fp8 products, the window left out, no rotation
— driven to ``correct: false``."""

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
from benchmark.flops import smallthinker as flops
from benchmark.reducers import (engine_stat, family_kernel_roofline as roof,
                                moe_load_held, spec_kernel_roofline)

CELL = "smallthinker-21ba3b-l8.serve-mixed-closed"
TINY = "smallthinker-tiny.serve-tiny-closed"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "smallthinker")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"paged_window_roofline.serve", "flash_window_roofline.serve",
       "window_pages_held_share.mixed", "moe_load_max_over_mean.mixed"}


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 and cell.workload["runner"] == "serve_pages"
    assert cell.config["family"] == "smallthinker"
    assert cell.traffic["arrivals"]["process"] == "closed"
    eng = cell.workload["engine"]
    assert cell.traffic["arrivals"]["clients"] == eng["max_streams"] \
        == eng["decode_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] <= eng["prefill_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] <= eng["max_len"] \
        == eng["cache_buckets"][-1] * eng["kv_block"]
    # prompts on both sides of the window, in one queue
    assert cell.traffic["prompt_tokens"]["min"] \
        < cell.config["sliding_window_size"] \
        < cell.traffic["prompt_tokens"]["max"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    shared = {"moe_gmm_roofline.serve", "paged_gqa_roofline.serve"}
    for name in OWN | shared:
        harness.plugin("reducers", metrics[name]["reducer"])
    # what the other hybrid cells report and this one does too: all but
    # their own kernels' and their own load metric
    other = {m["name"] for m, _ in harness.Cell(
        ROOT, "granite-4.0-h-small-ep2.serve-rag-closed").per_layer()}
    assert not other & OWN and shared <= other
    assert set(metrics) - OWN == other - {
        "mamba2_step_roofline.serve", "mamba2_chunk_roofline.serve",
        "moe_load_max_over_mean.rag"}
    # no name of a windowed kernel holds a name an accepted reader
    # matches kernels by
    for mine in ("paged_window", "flash_fwd_window"):
        for theirs in ("paged_attention", "flash_fwd_mha",
                       "flash_fwd_packed"):
            assert theirs not in mine


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = json.loads([ln for ln in open(CATALOG)
                      if '"SmallThinker-21BA3B-Instruct"' in ln][0])
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["source"] == row["source_url"]
    assert cfg["num_hidden_layers_published"] == 52
    # two whole periods, from the lists' start
    assert cfg["rope_layout"][:8] == cfg["sliding_window_layout"][:8] \
        == [0, 1, 1, 1, 0, 1, 1, 1]
    for key in ("deployment", "parameters", "departures", "assumed"):
        assert cfg[key]


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    assert flops.layer_counts(cfg) == (2, 6, 8)
    # one expert: 3 x 2560 x 768 weights; a pair: 2 flops a weight
    ops, nbytes = flops.moe_gmm(experts_hit=2, pairs=5, cfg=cfg)
    assert ops == 5 * 2 * 3 * 2560 * 768
    assert nbytes == 2 * 3 * 2560 * 768 * 2 \
        + 5 * (2560 * 2 + 2 * 768 * 2 + 2560 * 4)
    # a prompt of 10 positions whose band holds 40 pairs: 28 heads of
    # 128, q.k and p.v; q, o of 28 heads and k, v of 4
    ops, nbytes = flops.flash_window(tokens=10, pairs=40, cfg=cfg)
    assert ops == 40 * 4 * 128 * 28
    assert nbytes == 10 * 2 * (28 + 4) * 128 * 2
    stats = {"steps": 10, "stream_steps": 480, "prefills": 4,
             "prefill_tokens": 4000, "context_tokens": 10 * 48 * 5000,
             "window_context_tokens": 10 * 48 * 3000,
             "window_prefill_pairs": 4 * 400000}
    # a step's windows hold 48 x 3,000 keys of 512 lanes, in 6 layers
    assert flops.need("paged_window", stats, cfg, 2) == \
        paged_attention.decode_step(48 * 3000, 48, 6, 512, 2) == (
            4.0 * 144000 * 512 * 6, (2.0 * 144000 + 96) * 512 * 2 * 6)
    assert flops.need("flash_fwd_window", stats, cfg, 2) == tuple(
        6 * x for x in flops.flash_window(1000, 400000, cfg))
    assert flops.need("paged_window", {"steps": 0}, cfg, 2) is None
    assert flops.need("paged_window", {"steps": 3}, cfg, 2) is None
    assert flops.need("flash_fwd_window", {"prefills": 2}, cfg, 2) is None
    with pytest.raises(ValueError):
        flops.need("mamba2_step", stats, cfg, 2)
    # the accepted reader sizes the GLOBAL layers' kernel by the whole
    # context, through this family's layer counts
    assert spec_kernel_roofline.need(
        "paged_attention", stats, cfg, flops, 2) == \
        paged_attention.decode_step(48 * 5000, 48, 2, 512, 2)


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us, the
    kernels of both kinds of attention layer in each."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} f32[8,16]]"
    ops = [(k("%paged_attention.1"), 11e3, 1e3),
           (k("%paged_window.2"), 12e3, 2e3),
           (k("%paged_window.3"), 14e3, 2e3),
           (k("%moe_gmm_gate_up_relu.4"), 16e3, 3e3),
           (k("%paged_window.2"), 31e3, 2e3),
           (k("%flash_fwd_mha.5"), 51e3, 4e3),
           (k("%flash_fwd_window.6"), 56e3, 10e3),
           (k("%paged_window.2"), 95e3, 1e3)]      # in no whole program
    modules = [("jit_step_decode_b48x544(1)", 10e3, 10e3),
               ("jit_step_decode_b48x544(1)", 30e3, 10e3),
               ("jit_prefill_t8192(2)", 50e3, 30e3),
               ("jit_step_decode_b48x544(1)", 94e3, 10e3)]  # cut short
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


def test_roofline_shares_by_the_familys_own_count(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    stats = {"steps": 10, "stream_steps": 480, "prefills": 4,
             "prefill_tokens": 4000, "context_tokens": 10 * 48 * 5000,
             "window_context_tokens": 10 * 48 * 3000,
             "window_prefill_pairs": 4 * 400000, "moe_experts_hit": 5000,
             "moe_pairs_here": 23040, "moe_load_max": 900,
             "window_pages_held_share": 0.61}
    src = {"trace": made_up_trace(), "engine_stats": stats, "cell": cell,
           "run": run}
    got = roof.read(src, kernel="paged_window", program="jit_step_decode")
    _, nbytes = flops.need("paged_window", stats, cell.config, 2)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 3e-6)
    got = roof.read(src, kernel="flash_fwd_window", program="jit_prefill")
    ops, _ = flops.need("flash_fwd_window", stats, cell.config, 2)
    assert got == pytest.approx(100 * (ops / 197e12) / 10e-6)
    # the accepted readers: the global layers' kernel alone is spent
    # against the whole context's need; the ReLU grouped matmul is a
    # moe_gmm kernel
    got = spec_kernel_roofline.read(src, kernel="paged_attention",
                                    program="jit_step_decode")
    _, nbytes = paged_attention.decode_step(48 * 5000, 48, 2, 512, 2)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 0.5e-6)
    got = spec_kernel_roofline.read(src, kernel="moe_gmm",
                                    program="jit_step_decode")
    _, nbytes = flops.moe_gmm(500, 2304, cell.config)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 1.5e-6)
    assert moe_load_held.read(
        src, held_key="moe_num_primary_experts") == \
        pytest.approx(900 * 64 / 23040)
    assert engine_stat.read(src, key="window_pages_held_share") == 0.61
    # a program without the kernels, the counters or the key (the
    # parent of this PR): nothing to read, nothing raised
    parent = dict(src, engine_stats={"steps": 10, "stream_steps": 480,
                                     "prefills": 4, "prefill_tokens": 4000})
    assert roof.read(parent, kernel="paged_window",
                     program="jit_step_decode") is None
    assert roof.read(parent, kernel="flash_fwd_window",
                     program="jit_prefill") is None
    assert engine_stat.read(parent, key="window_pages_held_share") is None
    assert moe_load_held.read(parent,
                              held_key="moe_num_primary_experts") is None
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "smallthinker-tiny.json"),
                bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "smallthinker-tiny", "source": "test",
                         "file": "benchmark/configs/smallthinker-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "smallthinker-tiny",
                           "traffic": "serve-tiny-closed", "chips": 1,
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
    # prompts of up to 40 tokens through a window of 16: pages came back
    assert eng["window_pages_released"] > 0 and eng["preempted"] == 0
    assert 0 < eng["window_pages_held_share"] < 1
    assert eng["window_context_tokens"] < eng["context_tokens"]
    longest = [ln for ln in lines if "compared_lengths" in ln][0]
    assert longest["compared_lengths"][0] > 2 * 16
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report
    assert set(result["metrics"]) >= {
        "moe_load_max_over_mean.mixed", "window_pages_held_share.mixed",
        "decode_batch_fill.closed", "engine_prefill_share.closed",
        "engine_ttft_p50_ms.closed", "programs_built.setup"}
    assert not [m for m in result["metrics"] if "roofline" in m]


def test_each_control_reads_not_correct(tiny_root, capfd, monkeypatch):
    """``control_pages``: the run itself is correct; the reference in
    fp8, with the window left out and with no rotation each fail a limit
    of the cell."""
    from benchmark import control_pages
    from benchmark.runners import serve_lm, serve_pages

    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)
    monkeypatch.setattr(serve_pages, "CONTROLS", ())
    rc = control_pages.main(
        ["--workload", TINY, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0"], root=tiny_root, require_tpu=False)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    verdict = {ln["control"]: ln["correct"] for ln in lines
               if "control" in ln}
    assert verdict == {"fp8": False, "no_window": False,
                       "no_rotation": False}


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
    list knows no window, rotation, ReLU gate or early router, and the
    reference's ``spec`` says so before anything is drawn."""
    from mxnet_tpu.models import hybrid_lm

    monkeypatch.setattr(hybrid_lm, "MIXERS", ("attention", "kda", "mamba2"))
    with pytest.raises(NotImplementedError, match="window"):
        run_cell(TINY, root=tiny_root)
