"""chip_smoke.py on the CPU: its phase functions at a tiny size with
the Pallas kernels in interpret mode (the first rehearsal of the
on-chip-measurement guide), and the refusal to report anything when
the platform is not a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY_LM = dict(layers=2, d_model=32, heads=2, vocab=257, seq_len=64,
               batch=2, steps=30, lr=3e-3, period=8)
TINY_SERVE = dict(requests=3, prompt_min=9, prompt_max=30, new_tokens=6,
                  max_streams=4, prefill_buckets=(16, 32),
                  cache_buckets=(2, 4))


@pytest.fixture
def interpret_kernels(monkeypatch):
    # MXNET_PALLAS=1 on CPU = the kernels, run by the Pallas interpreter
    monkeypatch.setenv("MXNET_PALLAS", "1")


def _phases(capsys):
    return [json.loads(line)
            for line in capsys.readouterr().out.splitlines()]


def test_lm_train_then_serve_tiny(interpret_kernels, capsys):
    ctx = mx.cpu()
    # interpret mode lowers to plain HLO: no tpu_custom_call to look for
    mod, toks = chip_smoke.phase_lm_train(TINY_LM, ctx, seed=0,
                                          kernel_marker=None)
    prompts, outs = chip_smoke.phase_lm_serve(mod, toks, TINY_LM, TINY_SERVE, ctx, seed=0,
        kernel_marker=None)
    train, serve = _phases(capsys)
    assert train["phase"] == "lm_train"
    assert train["loss_last"] < train["loss_first"]
    assert train["kernel_in_fused_step"] is False  # not looked for here
    assert serve["phase"] == "lm_serve"
    assert serve["tokens_generated"] == 3 * 6 == serve["engine_tokens"]
    assert len(outs) == len(prompts) == 3
    # the memorized period-8 pattern continues
    for p, o in zip(prompts, outs):
        row = next(r for r in toks if r[0] == p[0])
        np.testing.assert_array_equal(o, row[len(p):len(p) + len(o)])


def test_kernel_check_fails_without_the_kernel(interpret_kernels):
    """On the chip the marker is looked for; a program without it (as
    every CPU program is) fails the phase instead of passing."""
    cfg = dict(TINY_LM, steps=2)
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_lm_train(cfg, mx.cpu(), seed=0)


def test_reference_check_rejects_wrong_tokens(interpret_kernels, capsys):
    ctx = mx.cpu()
    mod, toks = chip_smoke.phase_lm_train(TINY_LM, ctx, seed=0,
                                          kernel_marker=None)
    prompts = chip_smoke.make_prompts(toks, TINY_SERVE, 0, 2)
    wrong = [np.full(6, 5, np.int32) for _ in prompts]
    with pytest.raises(AssertionError, match="disagree"):
        chip_smoke.reference_check(mod, TINY_LM, ctx, prompts, wrong)


def test_resnet_phase_tiny(capsys):
    chip_smoke.phase_resnet_train(dict(batch=4, image=64, classes=10, layers=18, steps=2),
        mx.cpu(), seed=0)
    (line,) = _phases(capsys)
    assert line["phase"] == "resnet_train" and len(line["losses"]) == 2


def test_script_fails_off_the_chip():
    """JAX_PLATFORMS=cpu: non-zero exit, and no "ok": true anywhere."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_script_fails_alone(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo: non-zero exit, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
