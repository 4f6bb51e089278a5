"""The ``solar_open2`` family's cell: found by discovery, its counters
of operations against hand counts, its reducers on a small made-up
trace, a tiny configuration through ``serve_spec`` on the CPU with an
altered token driven to ``correct: false``, and the two runners'
accounting of one load."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import ROOT, TINY_ROOT

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import solar_open2 as flops
from benchmark.reducers import moe_load, spec_kernel_roofline as roof

CELL = "solar-open2-250b-ep8.serve-reason-closed"
TINY = "solar-tiny.serve-tiny-closed"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "solar")


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 and cell.workload["runner"] == "serve_spec"
    assert cell.config["family"] == "solar_open2"
    assert cell.traffic["arrivals"] == {"process": "closed",
                                        "clients": 128, "pool": 4096}
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    own = {"moe_gmm_roofline.serve", "kda_step_roofline.serve",
           "kda_chunk_roofline.serve", "paged_gqa_roofline.serve",
           "moe_load_max_over_mean.reason"}
    for name in own:
        harness.plugin("reducers", metrics[name]["reducer"])
    # one metric a layer: the serving front end's, the device's and the
    # runtime's are the closed-loop cells' own, this cell on their lists
    # (programs_built.setup has no list: every cell reports it)
    other = {m["name"] for m, _ in
             harness.Cell(ROOT, "gpt2-large.serve-doc-closed").per_layer()}
    assert not other & own
    assert set(metrics) - own == other - {"paged_roofline.serve",
                                         "flash_roofline.serve"}
    assert "programs_built.setup" in metrics
    listed = [m for m in cell.bench["per_layer"]
              if m["name"] == "programs_built.setup"]
    assert "workloads" not in listed[0]


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    line = [ln for ln in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "Solar-Open2-250B" in ln] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    if not line:
        pytest.skip("no catalog here")
    published = json.loads(line[0])["config"]
    changed = {k for k, v in published.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (48, 320, 196608)


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    assert flops.layer_counts(cfg) == (1, 3, 4)
    # one expert: 3 x 4096 x 1280 weights; a pair: 2 flops a weight
    ops, nbytes = flops.moe_gmm(experts_hit=2, pairs=5, cfg=cfg)
    assert ops == 5 * 2 * 3 * 4096 * 1280
    assert nbytes == 2 * 3 * 4096 * 1280 * 2 \
        + 5 * (4096 * 2 + 2 * 1280 * 2 + 4096 * 4)
    # a row: 64 states of 128 x 128 float32 read and written
    ops, nbytes = flops.kda_step(rows=3, cfg=cfg)
    assert ops == 3 * 7 * 64 * 128 * 128
    assert nbytes == 3 * (2 * 64 * 128 * 128 + 5 * 64 * 128 + 64) * 4
    ops, nbytes = flops.kda_chunk(tokens=10, prompts=1, cfg=cfg)
    assert ops == 10 * 7 * 64 * 128 * 128
    assert nbytes == (10 * (5 * 64 * 128 + 64) + 64 * 128 * 128) * 4


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us; a
    kernel event of each kind, one outside any program."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} bf16[8,16]]"
    ops = [(k("%moe_gmm_gate_up.1"), 11e3, 2e3),
           (k("%moe_gmm_down.2"), 14e3, 1e3),
           (k("%kda_step.3"), 16e3, 3e3),
           (k("%moe_gmm_gate_up.1"), 31e3, 2e3),
           (k("%kda_chunk.4"), 52e3, 20e3),
           (k("%moe_gmm_gate_up.9"), 55e3, 1e3),    # in the prefill
           (k("%moe_gmm_down.2"), 95e3, 1e3)]       # in no whole program
    modules = [("jit_step_decode_b4x8(1)", 10e3, 10e3),
               ("jit_step_decode_b4x8(1)", 30e3, 10e3),
               ("jit_prefill_t16(2)", 50e3, 30e3),
               ("jit_step_decode_b4x8(1)", 94e3, 10e3)]  # cut by the end
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


def test_kernel_time_is_booked_to_whole_programs():
    trace = made_up_trace()
    spent, runs = roof.kernel_seconds_in(trace, "moe_gmm",
                                         "jit_step_decode")
    assert runs == 2 and spent == pytest.approx(5e-6)
    spent, runs = roof.kernel_seconds_in(trace, "kda_chunk", "jit_prefill")
    assert runs == 1 and spent == pytest.approx(20e-6)
    assert roof.kernel_seconds_in(trace, "kda_step", "jit_verify") == (0.0, 0)


def test_roofline_share_from_counters_and_trace(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    stats = {"steps": 10, "stream_steps": 1000, "moe_experts_hit": 1500,
             "moe_pairs_here": 1200, "prefills": 4, "prefill_tokens": 4000,
             "context_tokens": 10 * 100000}
    src = {"trace": made_up_trace(), "engine_stats": stats, "cell": cell,
           "run": run}
    got = roof.read(src, kernel="moe_gmm", program="jit_step_decode")
    _, nbytes = flops.moe_gmm(150, 120, cell.config)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 2.5e-6)
    assert roof.read(src, kernel="kda_step", program="jit_step_decode") > 0
    assert roof.read(src, kernel="kda_chunk", program="jit_prefill") > 0
    # a program without the kernel, or without the counter: nothing
    assert roof.read(src, kernel="paged_attention",
                     program="jit_step_decode") is None
    src["engine_stats"] = {"steps": 10, "stream_steps": 1000}
    assert roof.read(src, kernel="moe_gmm",
                     program="jit_step_decode") is None
    assert moe_load.read({"engine_stats": {"moe_pairs_here": 400,
                                           "moe_load_max": 30},
                          "cell": cell}) == pytest.approx(3.0)
    assert moe_load.read({"engine_stats": {}, "cell": cell}) is None
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "solar-tiny.json"), bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "solar-tiny", "source": "test",
                         "file": "benchmark/configs/solar-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "solar-tiny",
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
    assert checks(lines)["served_logit_gap_widest"]["value"] < 0.05
    # the state of two streams was read back: float32 words, and the
    # scan's last state (bfloat16 products on the way, at this size)
    assert checks(lines)["kda_state_bfloat16_share"]["value"] < 0.01
    assert checks(lines)["kda_state_gap_worst_head"]["ok"] is True
    assert not [ln for ln in lines if "control" in ln]
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report (the tiny benchmark's
    # programs_built.setup lists no cells)
    assert set(result["metrics"]) >= {
        "moe_load_max_over_mean.reason", "decode_batch_fill.closed",
        "engine_prefill_share.closed", "engine_ttft_p50_ms.closed",
        "programs_built.setup"}
    assert not [m for m in result["metrics"] if "roofline" in m]


def test_each_control_reads_not_correct(tiny_root, capfd, monkeypatch):
    """``control_spec``: the run itself is correct, and the reference in
    fp8 and with its state in bfloat16 each fail a limit of the cell —
    the state's by its float32 words alone, which is why they are held."""
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
    assert rc == 0 and lines[-1]["correct"] is True
    verdict = {ln["control"]: ln["correct"] for ln in lines
               if "control" in ln}
    assert verdict == {"fp8": False, "bf16_state": False, "bfloat16": True}
    by = checks(lines)
    assert by["control.bf16_state.kda_state_bfloat16_share"]["value"] == 1.0
    assert by["control.bf16_state.served_logit_gap_mean"]["ok"] is True
    # (at this size fp8 hardly moves 96 logits; the state shows it)
    assert by["control.fp8.kda_state_bfloat16_share"]["ok"] is True
    assert by["control.fp8.kda_state_gap_worst_head"]["ok"] is False
    assert by["control.bf16_state.kda_state_gap_worst_head"]["ok"] is True


def test_state_held_in_bfloat16_is_not_correct(run_cell, tiny_root,
                                               monkeypatch):
    """The engine itself with bfloat16 slots (what halving the state's
    traffic would do): served tokens and state stay as close to the
    reference, and the run reads false."""
    from mxnet_tpu.models.hybrid_lm import HybridSpec

    real = HybridSpec.pools

    def narrow(self, *a, **k):
        return [(n, shape, "bfloat16" if n.endswith("_state") else dt, fill)
                for n, shape, dt, fill in real(self, *a, **k)]

    monkeypatch.setattr(HybridSpec, "pools", narrow)
    result, lines = run_cell(TINY, root=tiny_root)
    by = checks(lines)
    assert by["served_logit_gap_mean"]["ok"] is True
    assert by["kda_state_bfloat16_share"] == {
        "check": "kda_state_bfloat16_share", "value": 1.0,
        "limit": by["kda_state_bfloat16_share"]["limit"], "ok": False}
    assert result["correct"] is False


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


def test_both_runners_account_one_load_alike(monkeypatch, capfd):
    """``serve_spec.account`` repeats ``serve_lm.run``'s accounting:
    fed the same load, both give the same tokens per second."""
    from benchmark.runners import serve_lm, serve_spec

    n = 6
    t0 = 100.0
    load = types.SimpleNamespace(
        reqs={"prompts": [np.ones(4, np.int32)] * n,
              "max_new": [5, 7, 9, 4, 6, 8]},
        due=[90.0, 99.0, 101.0, 103.0, 104.5, 106.0],
        sent=[90.0, 99.0, 101.0, 103.0, 104.5, 106.0],
        done=[95.0, 102.0, 104.0, 107.0, None, 109.0],
        out=[np.zeros(5), np.zeros(7), np.zeros(9), np.zeros(4), None,
             np.zeros(8)],
        error=[None] * n, eng=None)
    sent = list(range(n))
    run = types.SimpleNamespace(seconds=5.0, trace=1, seed=1,
                                memory_peak=lambda: 0, extras={},
                                mark=lambda label: None,
                                devices=[types.SimpleNamespace(
                                    platform="cpu")])
    run.cell = types.SimpleNamespace(
        config={"family": "gpt2", "vocab_size": 10},
        workload={"dtype": "float32", "engine": {}, "limits": {}},
        traffic={"generator": "generate"})
    mine = serve_spec.account(run, load, sent, t0)

    class Engine:
        compiles = {}

        def close(self):
            pass

    stats = {k: 0 for k in (
        "requests", "tokens", "prefills", "steps", "stream_steps",
        "preempted", "d2h_syncs", "d2h_syncs_saved", "ttft_p50_ms",
        "p50_ms", "p99_ms", "active_streams", "pending")}
    monkeypatch.setattr(serve_lm, "build_engine", lambda r, w: Engine())
    monkeypatch.setattr(serve_lm, "warm_up", lambda r, e, v: None)
    monkeypatch.setattr(serve_lm, "serve_window",
                        lambda r, e, q: (load, sent, stats, t0))
    monkeypatch.setattr(serve_lm, "pick_sample", lambda r, l, s: [])
    monkeypatch.setattr(serve_lm, "serve_check", lambda r, s, f: True)
    monkeypatch.setattr(serve_lm.harness, "plugin",
                        lambda kind, name: types.SimpleNamespace(
                            program_names=lambda d: {},
                            draw=lambda *a, **k: {},
                            requests=lambda *a: {}))
    theirs = serve_lm.run(run)
    capfd.readouterr()
    assert mine["tokens"] / run.seconds == pytest.approx(
        theirs["metrics"]["serve_out_tokens_per_s"])
    assert mine["tokens"] > 0 and mine["failed"] == theirs["failed"] == 1
    assert mine["attempted"] == theirs["attempted"]
