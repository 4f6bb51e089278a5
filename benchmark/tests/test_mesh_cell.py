"""The four-chip training cell's own pieces.  The cell is NOT in
``BENCHMARK.json`` (PERF.md section 7, row 1: its limits wait for
readings of its own at full size); its files are, and this file holds
them: the runner ``train_lm_mesh`` tiny on four virtual CPU devices
against its reference split over them, the fp8 control and three faults
that exist only across chips planted in the step and each driven to
``correct: false``, and the reducer of exposed collective time on a
small made-up trace."""

import json
import os
import shutil

# four virtual devices for the mesh, asked for before any test of the
# session starts a backend (collection comes first)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import pytest  # noqa: E402

from conftest import ROOT, TINY_ROOT  # noqa: E402

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.reducers import collective_exposed  # noqa: E402

CELL = "gpt2-large.train-dp2tp2-zero1"
TINY = "gpt2-tiny.train-tiny-dp2tp2"
# the tiny cell's limits, each between ITS sound largest and the least
# of its faults' readings (five seeds each, four virtual devices):
#                    sound       fp8 control   half batch  gather out
# loss, worst step   <= 8.2e-5   <= 2.4e-4     >= 1.2e-3   >= 4e-4
# token loss rms     <= 0.0035   >= 0.0073     >= 0.10     sound
# gradient, worst    <= 0.0084   0.005-0.013   >= 0.44     sound
# change, worst      <= 0.146    <= 0.018      <= 0.21     0.32-0.34
# change, median     <= 0.0052   <= 0.0028     <= 0.037    0.28-0.30
TINY_LIMITS = {"loss_rel_gap": 3e-4, "token_loss_rms_gap": 0.005,
               "grad_norm_gap": 0.03, "change_norm_gap": 0.48,
               "change_norm_median_gap": 0.05, "change_skip": ["_gamma"]}


def test_the_four_chip_cell_waits_outside_the_benchmark():
    from benchmark import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"] if w["chips"] > 1] == []
    assert "collective_exposed_ms.train" not in {
        m["name"] for m in bench["per_layer"]}
    wl = harness.load_json(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json"))
    assert wl["runner"] == "train_lm_mesh" and wl["kvstore"] == "tpu"
    assert wl["mesh"] == {"dp": 2, "tp": 2, "rules": {"vocab": None}}
    assert set(wl["limits"]) == set(TINY_LIMITS)
    assert "provisional" in wl["limits_from"]
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "collective_exposed_ms.train.json"))
    assert harness.plugin("reducers", spec["reducer"]) is collective_exposed
    harness.plugin("runners", wl["runner"])


def made_up_trace(chips=4):
    """Two whole steps of 40 us on the first chip.  In each: 20 us of
    compute, an all-reduce of 10 us of which 4 run under a fusion, an
    async pair whose ``-done`` waits 3 us alone; a third step is cut by
    the window's end."""
    def step(at):
        return [("%fusion.1", at, 20e3),
                ("%all-reduce.7", at + 16e3, 10e3),
                ("%all-gather-start.2", at + 27e3, 1e3),
                ("%fusion.2", at + 28e3, 5e3),
                ("%all-gather-done.2", at + 33e3, 3e3)]
    ops = step(10e3) + step(50e3) + step(95e3)
    modules = [("jit_step_train(1)", 10e3, 40e3),
               ("jit_step_train(1)", 50e3, 40e3),
               ("jit_step_train(1)", 95e3, 40e3)]
    planes = {f"/device:TPU:{i}": {tr.OPS_LINE: ops,
                                   tr.MODULES_LINE: modules}
              for i in range(chips)}
    planes["/host:CPU"] = {"python3": [(tr.WINDOW_SPAN, 0.0, 100e3)]}
    return tr.Trace(planes)


def test_exposed_collective_time_a_step(capfd):
    got = collective_exposed.read({"trace": made_up_trace()},
                                  program="jit_step")
    # a step: 6 us of the all-reduce past the fusion, 1 us of the
    # start, 3 us of the done = 10 us
    assert got == pytest.approx(0.010)
    assert collective_exposed.read({"trace": made_up_trace(1)},
                                   program="jit_step") is None
    assert collective_exposed.read({"trace": None},
                                   program="jit_step") is None
    assert collective_exposed.read({"trace": made_up_trace()},
                                   program="jit_prefill") is None
    capfd.readouterr()


@pytest.fixture
def tiny_root(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(TINY_ROOT, root)
    bdir = root / "benchmark"
    wl = json.loads((bdir / "workloads" /
                     "gpt2-tiny.train-tiny.json").read_text())
    wl.update(runner="train_lm_mesh", kvstore="tpu", limits=TINY_LIMITS,
              mesh={"dp": 2, "tp": 2, "rules": {"vocab": None}})
    (bdir / "workloads" / (TINY + ".json")).write_text(json.dumps(wl))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": TINY, "config": "gpt2-tiny",
                           "traffic": "train-tiny", "chips": 4,
                           "why": "test"})
    for m in b["end_to_end"]:
        if "gpt2-tiny.train-tiny" in m.get("workloads", ()):
            m["workloads"].append(TINY)
    b["per_layer"].append({
        "name": "collective_exposed_ms.train", "unit": "ms",
        "better": "lower", "source": "device_trace",
        "layer": "parallelism", "moves": "train_mfu", "workloads": [TINY]})
    shutil.copy(os.path.join(ROOT, "benchmark", "layer_metrics",
                             "collective_exposed_ms.train.json"),
                bdir / "layer_metrics")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


def checks(lines, control=None):
    """The compared numbers of the program (or of one control: the
    lines after its ``control_begins``)."""
    out, at = {}, None
    for ln in lines:
        at = ln.get("control_begins", at)
        if "check" in ln and at == control:
            out[ln["check"]] = ln
    return out


def test_tiny_mesh_cell_agrees_with_the_split_reference(run_cell,
                                                        tiny_root,
                                                        monkeypatch):
    """Sound under the tiny cell's own limits; the reference in fp8 in
    the program's place is not (by the loss per token: a gap of norms
    is blind to unbiased rounding, PERF.md section 2)."""
    from benchmark.runners import train_lm_mesh

    monkeypatch.setattr(train_lm_mesh, "CONTROLS", ("fp8",))
    result, lines = run_cell(TINY, root=tiny_root)
    assert result["correct"] is True and result["device"]["count"] == 4
    assert all(ln["ok"] for ln in checks(lines).values())
    assert train_lm_mesh.VERDICTS == {"fp8": False}
    assert checks(lines, "fp8")["token_loss_rms_gap"]["ok"] is False
    report = [ln for ln in lines if "overlap_report" in ln][0]
    assert report["overlap_report"]["collectives"]


def half_batch(monkeypatch):
    """One replica's rows never reach the step, the other's stand in
    for them: what replica 0 computes when the gradients' exchange is
    left out, too."""
    import jax.numpy as jnp

    from benchmark.runners.train_lm_mesh import MeshTrainer

    real = MeshTrainer.feed

    def feed(self, tokens):
        mine = tokens[:, :tokens.shape[1] // 2]
        real(self, jnp.concatenate([mine, mine], axis=1))

    monkeypatch.setattr(MeshTrainer, "feed", feed)


def faulty_update(monkeypatch, fault):
    """The optimizer segment of the fused step with a fault of the
    exchange in it: the gradients summed over the replicas and not
    averaged, or the updated rows of the other replicas never gathered
    (they keep the old values)."""
    import jax.numpy as jnp

    from mxnet_tpu.module.module import Module

    real = Module._make_param_update

    def make(self):
        update, dp = real(self), self._mesh_plan.dp

        def step(params, grads, states, lr, t_f):
            if fault == "gradient_summed":
                grads = {k: g * dp for k, g in grads.items()}
            new, states = update(params, grads, states, lr, t_f)
            if fault == "gather_left_out":
                for n, w in new.items():
                    own = jnp.arange(w.size) < -(-w.size // dp)
                    new[n] = jnp.where(own.reshape(w.shape), w, params[n])
            return new, states

        return step

    monkeypatch.setattr(Module, "_make_param_update", make)


@pytest.mark.parametrize("fault, caught_by, sound", [
    ("half_batch", {"grad_norm_worst_leaf_gap", "token_loss_rms_gap"},
     set()),
    ("gradient_summed", {"grad_norm_worst_leaf_gap"},
     {"token_loss_rms_gap", "change_norm_median_leaf_gap"}),
    # Adam's step hardly moves with the gradient's scale, and half of
    # every leaf stale reads 1 - 1/sqrt(2) under the worst leaf's limit
    ("gather_left_out", {"change_norm_median_leaf_gap"},
     {"grad_norm_worst_leaf_gap", "token_loss_rms_gap",
      "change_norm_worst_leaf_gap"}),
])
def test_planted_fault_reads_not_correct(run_cell, tiny_root, monkeypatch,
                                         fault, caught_by, sound):
    if fault == "half_batch":
        half_batch(monkeypatch)
    else:
        faulty_update(monkeypatch, fault)
    result, lines = run_cell(TINY, root=tiny_root)
    by = checks(lines)
    assert result["correct"] is False
    assert {k for k in caught_by if not by[k]["ok"]} == caught_by
    assert {k for k in sound if by[k]["ok"]} == sound
