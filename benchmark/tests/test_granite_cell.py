"""The ``granitemoehybrid`` family's cell: found by discovery, its
counters of operations against hand counts, the family-counted roofline
reducer on a small made-up trace, a tiny configuration through
``serve_spec`` on the CPU, and each control — fp8 products, a bfloat16
state, bfloat16 slots in the engine itself — driven to ``correct:
false``."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import ROOT, TINY_ROOT

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import granitemoehybrid as flops
from benchmark.reducers import (family_kernel_roofline as roof,
                                moe_load_held, spec_kernel_roofline)

CELL = "granite-4.0-h-small-ep2.serve-rag-closed"
TINY = "granite-tiny.serve-tiny-closed"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "granite")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 and cell.workload["runner"] == "serve_spec"
    assert cell.config["family"] == "granitemoehybrid"
    assert cell.traffic["arrivals"] == {"process": "closed",
                                        "clients": 64, "pool": 4096}
    assert cell.traffic["prompt_tokens"]["max"] <= \
        cell.workload["engine"]["prefill_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] <= \
        cell.workload["engine"]["max_len"]
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    own = {"mamba2_step_roofline.serve", "mamba2_chunk_roofline.serve",
           "moe_load_max_over_mean.rag"}
    shared = {"moe_gmm_roofline.serve", "paged_gqa_roofline.serve"}
    for name in own | shared:
        harness.plugin("reducers", metrics[name]["reducer"])
    # the kernels Solar brought are read for this cell by Solar's
    # reader, through this family's flops plugin; Solar's own kernels
    # and its load metric (which wants its config key) are not
    other = {m["name"] for m, _ in harness.Cell(
        ROOT, "solar-open2-250b-ep8.serve-reason-closed").per_layer()}
    assert not other & own and shared <= other
    assert set(metrics) - own == other - {
        "kda_step_roofline.serve", "kda_chunk_roofline.serve",
        "moe_load_max_over_mean.reason"}


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = json.loads([ln for ln in open(CATALOG)
                      if '"granite-4.0-h-small"' in ln][0])
    published = row["config"]
    changed = {k for k, v in published.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_local_experts", "vocab_size"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["num_hidden_layers_published"],
            cfg["num_local_experts_published"],
            cfg["vocab_size_published"]) == (40, 72, 100352)
    # one whole period, from the list's start: 5 mamba, attention, 4 mamba
    assert cfg["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    assert flops.layer_counts(cfg) == (1, 9, 10)
    # one expert: 3 x 4096 x 768 weights; a pair: 2 flops a weight
    ops, nbytes = flops.moe_gmm(experts_hit=2, pairs=5, cfg=cfg)
    assert ops == 5 * 2 * 3 * 4096 * 768
    assert nbytes == 2 * 3 * 4096 * 768 * 2 \
        + 5 * (4096 * 2 + 2 * 768 * 2 + 4096 * 4)
    # a row: 128 states of 64 x 128 float32 read and written = 8.39 MB
    ops, nbytes = flops.mamba2_step(rows=3, cfg=cfg)
    assert ops == 3 * 5 * 128 * 64 * 128
    assert nbytes == 3 * (2 * 128 * 64 * 128 + 2 * 128 * 64 + 128
                          + 2 * 128) * 4
    ops, nbytes = flops.mamba2_chunk(tokens=10, prompts=1, cfg=cfg)
    assert ops == 10 * 5 * 128 * 64 * 128
    assert nbytes == 10 * ((8192 + 256) * 2 + 128 * 4 + 8192 * 4) \
        + 128 * 64 * 128 * 4
    stats = {"steps": 10, "stream_steps": 640, "prefills": 4,
             "prefill_tokens": 4000}
    assert flops.need("mamba2_step", stats, cfg, 2) == tuple(
        9 * x for x in flops.mamba2_step(64, cfg))
    assert flops.need("mamba2_chunk", stats, cfg, 2) == tuple(
        9 * x for x in flops.mamba2_chunk(1000, 1, cfg))
    assert flops.need("mamba2_step", {"steps": 0}, cfg, 2) is None
    assert flops.need("mamba2_chunk", {"prefills": 0}, cfg, 2) is None
    with pytest.raises(ValueError):
        flops.need("kda_step", stats, cfg, 2)


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us; a
    kernel event of each kind, one outside any whole program."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} f32[8,16]]"
    ops = [(k("%mamba2_step.1"), 11e3, 2e3),
           (k("%mamba2_step.2"), 14e3, 1e3),
           (k("%moe_gmm_gate_up.3"), 16e3, 3e3),
           (k("%mamba2_step.1"), 31e3, 2e3),
           (k("%mamba2_chunk_scan.4"), 52e3, 20e3),
           (k("%mamba2_step.1"), 95e3, 1e3)]       # in no whole program
    modules = [("jit_step_decode_b64x144(1)", 10e3, 10e3),
               ("jit_step_decode_b64x144(1)", 30e3, 10e3),
               ("jit_prefill_t2048(2)", 50e3, 30e3),
               ("jit_step_decode_b64x144(1)", 94e3, 10e3)]  # cut short
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


def test_roofline_share_by_the_familys_own_count(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    stats = {"steps": 10, "stream_steps": 640, "prefills": 4,
             "prefill_tokens": 4000, "moe_experts_hit": 3600,
             "moe_pairs_here": 3200, "moe_load_max": 150}
    src = {"trace": made_up_trace(), "engine_stats": stats, "cell": cell,
           "run": run}
    got = roof.read(src, kernel="mamba2_step", program="jit_step_decode")
    _, nbytes = flops.need("mamba2_step", stats, cell.config, 2)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 2.5e-6)
    got = roof.read(src, kernel="mamba2_chunk", program="jit_prefill")
    _, nbytes = flops.need("mamba2_chunk", stats, cell.config, 2)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 20e-6)
    # Solar's reader serves this family's grouped matmul unedited
    got = spec_kernel_roofline.read(src, kernel="moe_gmm",
                                    program="jit_step_decode")
    _, nbytes = flops.moe_gmm(360, 320, cell.config)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 1.5e-6)
    # no such kernel in the program, no counters, no trace, a family
    # that counts no kernels of its own: nothing to read
    assert roof.read(src, kernel="mamba2_step", program="jit_verify") is None
    assert roof.read(dict(src, engine_stats={"steps": 10}),
                     kernel="mamba2_step", program="jit_step_decode") is None
    assert roof.read(dict(src, trace=None), kernel="mamba2_step",
                     program="jit_step_decode") is None
    solar = harness.Cell(ROOT, "solar-open2-250b-ep8.serve-reason-closed")
    assert roof.read(dict(src, cell=solar), kernel="mamba2_step",
                     program="jit_step_decode") is None
    assert moe_load_held.read(src, held_key="num_local_experts") == \
        pytest.approx(150 * 36 / 3200)
    assert moe_load_held.read({"engine_stats": {}, "cell": cell},
                              held_key="num_local_experts") is None
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "granite-tiny.json"), bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "granite-tiny", "source": "test",
                         "file": "benchmark/configs/granite-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "granite-tiny",
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
    assert by["served_logit_gap_widest"]["value"] < 0.05
    # the state of two streams was read back: float32 words, and the
    # scan's last state (bfloat16 products on the way, at this size);
    # three mamba layers x 16 heads x two streams
    assert by["kda_state_bfloat16_share"]["value"] < 0.01
    assert by["kda_state_gap_worst_head"]["ok"] is True
    assert [ln for ln in lines if "compared_head_states" in ln][0][
        "compared_head_states"] == 2 * 3 * 16
    assert not [ln for ln in lines if "control" in ln]
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report
    assert set(result["metrics"]) >= {
        "moe_load_max_over_mean.rag", "decode_batch_fill.closed",
        "engine_prefill_share.closed", "engine_ttft_p50_ms.closed",
        "programs_built.setup"}
    assert not [m for m in result["metrics"] if "roofline" in m]


def test_each_control_reads_not_correct(tiny_root, capfd, monkeypatch):
    """``control_spec``: the run itself is correct; the reference in fp8
    and with its state in bfloat16 each fail a limit of the cell."""
    from benchmark import control_spec
    from benchmark.runners import serve_lm, serve_spec

    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)
    monkeypatch.setattr(serve_spec, "CONTROLS", ())
    rc = control_spec.main(
        ["--workload", TINY, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--controls", "fp8,bf16_state,bfloat16"],
        root=tiny_root, require_tpu=False)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    by = checks(lines)
    assert rc == 0 and lines[-1]["correct"] is True
    verdict = {ln["control"]: ln["correct"] for ln in lines
               if "control" in ln}
    assert verdict == {"fp8": False, "bf16_state": False, "bfloat16": True}
    assert by["control.bf16_state.kda_state_bfloat16_share"]["value"] == 1.0
    assert by["control.fp8.kda_state_bfloat16_share"]["ok"] is True
    # (at this size fp8 hardly moves 96 logits; the state shows it)
    assert by["control.fp8.kda_state_gap_worst_head"]["ok"] is False
    assert by["control.bf16_state.kda_state_gap_worst_head"]["ok"] is True


def test_state_held_in_bfloat16_is_not_correct(run_cell, tiny_root,
                                               monkeypatch):
    """The engine itself with bfloat16 slots (what halving the state's
    traffic would do): the run reads false by the slots' words."""
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    real = HybridSpec.pools

    def narrow(self, *a, **k):
        return [(n, shape, "bfloat16" if n.endswith("_state") else dt, fill)
                for n, shape, dt, fill in real(self, *a, **k)]

    monkeypatch.setattr(HybridSpec, "pools", narrow)
    result, lines = run_cell(TINY, root=tiny_root)
    by = checks(lines)
    assert by["kda_state_bfloat16_share"]["value"] == 1.0
    assert by["kda_state_bfloat16_share"]["ok"] is False
    assert result["correct"] is False


def test_altered_served_token_is_not_correct(run_cell, tiny_root,
                                             monkeypatch):
    """The logit limits hold the served TOKENS (under this family's
    multipliers every sound token is the reference's best: gap 0.0):
    one token altered in the middle of every answer reads wide."""
    from benchmark.runners import serve_lm

    def altered(future):
        out = np.asarray(future.result()).copy()
        out[len(out) // 2] = out[len(out) // 2] % 7 + 1
        return out

    monkeypatch.setattr(serve_lm, "served_tokens", altered)
    result, lines = run_cell(TINY, root=tiny_root)
    by = checks(lines)
    assert result["correct"] is False
    assert by["served_logit_gap_widest"]["ok"] is False
    assert by["kda_state_bfloat16_share"]["ok"] is True
