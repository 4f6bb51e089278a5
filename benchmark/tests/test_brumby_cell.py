"""The ``brumby`` family's cell: found by discovery, its configuration
against the catalog, its counters of operations against hand counts, the
family-counted roofline reader on a small made-up trace, a tiny
configuration through ``serve_spec`` on the CPU, and the controls — an
altered served token, a bfloat16 state in the reference and in the
engine itself — driven to ``correct: false``."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from conftest import ROOT, TINY_ROOT

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.flops import brumby as flops
from benchmark.reducers import family_kernel_roofline as roof

CELL = "brumby-14b-pp4.serve-gen-closed"
TINY = "brumby-tiny.serve-tiny-closed"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "brumby")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"retention_step_roofline.serve", "retention_chunk_roofline.serve",
       "retention_share.decode", "retention_share.prefill"}


def test_discovery_finds_the_cell_and_its_metrics():
    cell = harness.Cell(ROOT, CELL)
    assert cell.chips == 1 and cell.workload["runner"] == "serve_spec"
    assert cell.config["family"] == "brumby"
    clients = cell.traffic["arrivals"]["clients"]
    eng = cell.workload["engine"]
    assert clients == eng["max_streams"] == eng["decode_buckets"][0]
    assert cell.traffic["prompt_tokens"]["max"] <= eng["prefill_buckets"][-1]
    assert cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"] <= eng["max_len"]
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"serve_out_tokens_per_s", "setup_s"}
    metrics = {m["name"]: spec for m, spec in cell.per_layer()}
    assert OWN <= set(metrics)
    for name in OWN:
        harness.plugin("reducers", metrics[name]["reducer"])
    # beside its own four, what every closed-loop serving cell reports
    rag = {m["name"] for m, _ in harness.Cell(
        ROOT, "granite-4.0-h-small-ep2.serve-rag-closed").per_layer()}
    assert not rag & OWN
    assert set(metrics) - OWN == rag - {
        "mamba2_step_roofline.serve", "mamba2_chunk_roofline.serve",
        "moe_load_max_over_mean.rag", "moe_gmm_roofline.serve",
        "paged_gqa_roofline.serve", "moe_share.prefill",
        "engine_prefill_share.closed"}


def test_config_holds_the_published_widths():
    cfg = harness.Cell(ROOT, CELL).config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = json.loads([ln for ln in open(CATALOG)
                      if '"Brumby-14B-Base"' in ln][0])
    published = row["config"]
    changed = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["num_hidden_layers"],
            cfg["num_hidden_layers_published"]) == (10, 40)
    assert cfg["state_dtype"] == "float32" \
        and cfg["compute_dtype"] == "bfloat16"
    for key in ("retention_degree", "qk_norm", "gate", "eps", "state_dtype",
                "chunk", "state_rows", "initialisation"):
        assert key in cfg["assumed"]


def test_operation_counts_by_hand():
    cfg = harness.Cell(ROOT, CELL).config
    P = 128 * 129 // 2
    assert P == 8256
    # a row and KV head: 8,256 x 129 entries of state and z; the decay,
    # the update's multiply and add, 5 query heads' multiply and add
    ops, nbytes = flops.retention_step(rows=3, cfg=cfg)
    assert ops == 3 * 8 * 13 * P * 129
    assert nbytes == 3 * (2 * 8 * P * 129 + 2 * 5120 + 2 * 1024 + 8) * 4
    # a prompt of 10 tokens: its outputs in the attention form
    ops, nbytes = flops.retention_chunk(tokens=10, prompts=1, cfg=cfg)
    assert ops == 10 * (8 * 2 * P * 129 + 40 * (4 * 128 + 3) * 11 / 2)
    assert nbytes == 10 * ((2 * 5120 + 2 * 1024) * 2 + 8 * 4) \
        + 8 * P * 129 * 4
    # past 8,256 x 129 x 4 / 515 tokens the recurrent form is the cheaper
    long = flops.retention_chunk(tokens=20000, prompts=1, cfg=cfg)[0]
    assert long == 20000 * (8 + 40) * 2 * P * 129
    stats = {"steps": 10, "stream_steps": 120, "prefills": 4,
             "prefill_tokens": 4000}
    assert flops.need("retention_step", stats, cfg, 2) == tuple(
        10 * x for x in flops.retention_step(12, cfg))
    assert flops.need("retention_chunk", stats, cfg, 2) == tuple(
        10 * x for x in flops.retention_chunk(1000, 1, cfg))
    assert flops.need("retention_step", {"steps": 0}, cfg, 2) is None
    assert flops.need("retention_chunk", {"prefills": 0}, cfg, 2) is None
    with pytest.raises(ValueError):
        flops.need("mamba2_step", stats, cfg, 2)


def made_up_trace():
    """Two decode programs and a prefill inside a window of 100 us; a
    kernel event of each kind, one outside any whole program."""
    k = lambda name: f"{name}{tr.KERNEL_TAG} f32[8,16]]"
    ops = [(k("%retention_step.1"), 11e3, 2e3),
           (k("%retention_step.2"), 14e3, 1e3),
           (k("%retention_step.1"), 31e3, 2e3),
           (k("%retention_chunk.4"), 52e3, 20e3),
           (k("%retention_step.1"), 95e3, 1e3)]    # in no whole program
    modules = [("jit_step_decode_b12x160(1)", 10e3, 10e3),
               ("jit_step_decode_b12x160(1)", 30e3, 10e3),
               ("jit_prefill_t2048(2)", 50e3, 30e3),
               ("jit_step_decode_b12x160(1)", 94e3, 10e3)]  # cut short
    return tr.Trace({
        "/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
        "/host:CPU": {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}})


def test_roofline_share_by_the_familys_own_count(capfd):
    cell = harness.Cell(ROOT, CELL)
    run = types.SimpleNamespace(devices=[types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    stats = {"steps": 10, "stream_steps": 120, "prefills": 4,
             "prefill_tokens": 4000}
    src = {"trace": made_up_trace(), "engine_stats": stats, "cell": cell,
           "run": run}
    got = roof.read(src, kernel="retention_step", program="jit_step_decode")
    _, nbytes = flops.need("retention_step", stats, cell.config, 2)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 2.5e-6)
    got = roof.read(src, kernel="retention_chunk", program="jit_prefill")
    ops, nbytes = flops.need("retention_chunk", stats, cell.config, 2)
    assert ops / 197e12 > nbytes / 819e9         # a prompt is compute's
    assert got == pytest.approx(100 * (ops / 197e12) / 20e-6)
    # the parent of this PR has no such kernel in its programs, no
    # counters where nothing ran: nothing to read, nothing raised
    assert roof.read(src, kernel="retention_step",
                     program="jit_verify") is None
    assert roof.read(dict(src, engine_stats={"steps": 10}),
                     kernel="retention_step",
                     program="jit_step_decode") is None
    assert roof.read(dict(src, trace=None), kernel="retention_step",
                     program="jit_step_decode") is None
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    """The tiny benchmark with a tiny configuration of this family
    added as new files, the way a PR adds them."""
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    shutil.copy(os.path.join(DATA, "brumby-tiny.json"), bdir / "configs")
    shutil.copy(os.path.join(DATA, TINY + ".json"), bdir / "workloads")
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "brumby-tiny", "source": "test",
                         "file": "benchmark/configs/brumby-tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": TINY, "config": "brumby-tiny",
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
    assert result["correct"] is True and result["failed"] == 0, [
        ln for ln in lines if "error" in ln or ln.get("ok") is False]
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    by = checks(lines)
    assert by["served_logit_gap_widest"]["ok"] is True
    # the state of two streams was read back: float32 words, and the
    # recurrent scan's last state AND its last normaliser (bfloat16
    # products on the way, at this size); three layers x 2 KV heads x
    # two streams, of each
    assert by["kda_state_bfloat16_share"]["value"] < 0.01
    assert by["kda_state_gap_worst_head"]["ok"] is True
    assert [ln for ln in lines if "compared_head_states" in ln][0][
        "compared_head_states"] == 2 * 3 * 2 * 2
    assert not [ln for ln in lines if "control" in ln]
    result, _ = run_cell(TINY, root=tiny_root, trace=1)
    # the CPU has no device plane: the readers of counters and of the
    # program's own spans report, the rooflines and shares do not
    assert set(result["metrics"]) >= {
        "decode_batch_fill.closed", "engine_ttft_p50_ms.closed",
        "programs_built.setup"}
    assert not [m for m in result["metrics"] if "retention" in m]


def test_the_bfloat16_state_control_reads_not_correct(tiny_root, capfd,
                                                      monkeypatch):
    """``control_retention``: the run itself is correct; the reference
    with its state rounded to bfloat16 token by token fails by the
    slot's words, and every form named is run and reported."""
    from benchmark import control_retention
    from benchmark.runners import serve_lm, serve_spec

    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)
    monkeypatch.setattr(serve_spec, "CONTROLS", ())
    monkeypatch.setattr(control_retention, "MUST_FAIL", ("bf16_state",))
    rc = control_retention.main(
        ["--workload", TINY, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--controls", "bf16_state,no_gate,bfloat16"],
        root=tiny_root, require_tpu=False)
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    by = checks(lines)
    assert rc == 0 and lines[-1]["correct"] is True
    verdict = {ln["control"]: ln["correct"] for ln in lines
               if "control" in ln}
    assert set(verdict) == {"bf16_state", "no_gate", "bfloat16"}
    assert verdict["bf16_state"] is False
    assert by["control.bf16_state.kda_state_bfloat16_share"]["value"] == 1.0
    assert by["control.no_gate.kda_state_bfloat16_share"]["ok"] is True
    # a state that forgets nothing is another state
    assert by["control.no_gate.kda_state_gap_worst_head"]["ok"] is False


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
    """One token altered in the middle of every answer reads wide on
    the logits; the slots' words stay float32."""
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
