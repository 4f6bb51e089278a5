"""Each kind of cell end to end at a tiny size: the reference against
``models.transformer_lm`` (training: loss, gradient, Adam's change;
serving: prefill and decode through the paged cache), the result line's
keys, and the two broken paths that ``correct`` must catch."""

import numpy as np
import pytest

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def checks(lines):
    return {ln["check"]: ln for ln in lines if "check" in ln}


def test_train_cell_agrees_with_reference(run_cell):
    result, lines = run_cell("gpt2-tiny.train-tiny")
    assert KEYS <= set(result) and result["correct"] is True
    assert set(result["metrics"]) == {"setup_s"}  # no peak for a CPU
    seen = checks(lines)
    # float32 reference against the bfloat16 program, 2 layers, d 64
    assert seen["loss_step1_rel_gap"]["value"] < 2e-4
    assert seen["token_loss_rms_gap"]["value"] < 0.01
    assert seen["grad_norm_worst_leaf_gap"]["value"] < 0.03
    assert seen["change_norm_worst_leaf_gap"]["value"] < 0.3


@pytest.mark.parametrize("cell", ["gpt2-tiny.serve-tiny-open",
                                  "gpt2-tiny.serve-tiny-closed"])
def test_serve_cell_agrees_with_reference(run_cell, cell):
    result, lines = run_cell(cell)
    assert KEYS <= set(result) and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    judged = "serve_out_tokens_per_s" if cell.endswith("closed") \
        else "serve_request_p95_ms"
    assert set(result["metrics"]) == {judged, "setup_s"}
    assert checks(lines)["served_logit_gap_widest"]["value"] < 0.02


def test_traced_run_reports_layer_metrics(run_cell):
    result, _ = run_cell("gpt2-tiny.serve-tiny-open", trace=1)
    assert "breakdown" in result
    assert {"busy_s", "window_s"} <= set(result["device"])
    # the CPU has no device plane: trace readers find nothing and are
    # left out; the counters' readers report
    assert set(result["metrics"]) == {
        "engine_ttft_p50_ms.open", "decode_batch_fill.open",
        "programs_built.setup"}


def test_step_that_returns_its_state_unchanged_is_not_correct(
        run_cell, monkeypatch):
    from benchmark.runners import train_lm

    def frozen_step(self):
        batch = self.batches[self.steps % len(self.batches)]
        self.mod.forward(batch, is_train=False)
        self.steps += 1
        return self.mod.get_outputs()[0].handle

    monkeypatch.setattr(train_lm.Trainer, "step", frozen_step)
    result, lines = run_cell("gpt2-tiny.train-tiny")
    assert result["correct"] is False
    assert checks(lines)["change_norm_worst_leaf_gap"]["ok"] is False


def test_altered_served_token_is_not_correct(run_cell, monkeypatch):
    from benchmark.runners import serve_lm

    def altered(future):
        out = np.asarray(future.result()).copy()
        out[len(out) // 2] = out[len(out) // 2] % 7 + 1
        return out

    monkeypatch.setattr(serve_lm, "served_tokens", altered)
    result, lines = run_cell("gpt2-tiny.serve-tiny-open")
    assert result["correct"] is False
    assert checks(lines)["served_logit_gap_widest"]["ok"] is False
