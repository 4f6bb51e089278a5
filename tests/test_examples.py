"""Each example script must run end-to-end — single device and on the
virtual 8-CPU mesh (the driver's multi-chip validation model)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(REPO, "examples")


def run_example(script, *args, mesh=False, timeout=180):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8" if mesh \
        else ""
    env["PYTHONPATH"] = REPO
    r = subprocess.run([sys.executable, os.path.join(EX, script), *args],
                       capture_output=True, text=True, env=env, cwd=EX,
                       timeout=timeout)
    assert r.returncode == 0, f"{script} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout + r.stderr  # logging output lands on stderr


def test_train_mnist():
    out = run_example("train_mnist.py", "--network", "mlp",
                      "--num-epochs", "2", "--batch-size", "64",
                      "--disp-batches", "10")
    line = [l for l in out.splitlines() if "final validation" in l][-1]
    acc = float(line.split(":")[1])
    assert acc > 0.9, out


def test_train_mnist_mesh_kvstore_tpu():
    out = run_example("train_mnist.py", "--network", "mlp",
                      "--num-epochs", "1", "--kv-store", "tpu",
                      "--batch-size", "64", mesh=True)
    line = [l for l in out.splitlines() if "final validation" in l][-1]
    acc = float(line.split(":")[1])
    assert acc > 0.7, out


def test_train_imagenet_benchmark():
    out = run_example("train_imagenet.py", "--network", "resnet-18",
                      "--benchmark", "1", "--batch-size", "4",
                      "--image-shape", "3,64,64", "--num-classes", "64",
                      "--num-batches", "4", "--num-epochs", "1",
                      "--disp-batches", "2")
    assert "Epoch[0]" in out and "Speed:" in out


def test_benchmark_score():
    out = run_example("benchmark_score.py", "--networks", "lenet",
                      "--batch-sizes", "1,4", "--num-batches", "2")
    assert "img/s" in out


def test_lstm_bucketing():
    out = run_example("lstm_bucketing.py", "--num-epochs", "3",
                      "--batch-size", "16", "--num-hidden", "32",
                      "--num-embed", "16")
    import re

    lines = [l for l in out.splitlines()
             if re.search(r"Epoch\[\d+\] Train-Perplexity=", l)]
    assert len(lines) == 3, out
    first = float(lines[0].rsplit("=", 1)[1])
    last = float(lines[-1].rsplit("=", 1)[1])
    assert last < first, out  # learning


def test_model_parallel_lstm_mesh():
    out = run_example("model_parallel_lstm.py", "--tp", "2",
                      "--num-epochs", "2", "--batch-size", "8",
                      "--seq-len", "8", "--num-hidden", "32",
                      "--num-embed", "16", mesh=True)
    lines = [l for l in out.splitlines() if "loss=" in l]
    assert "tp=2" in lines[-1], out
    first = float(lines[0].rsplit("=", 1)[1])
    last = float(lines[-1].rsplit("=", 1)[1])
    assert last < first, out


def test_ssd_example():
    out = run_example("ssd.py", "--num-epochs", "2", "--batch-size", "4")
    assert "detections per image" in out


@pytest.mark.slow
def test_train_transformer_lm_3d_mesh():
    """The transformer-LM example: full dp×tp×pp from the rules table,
    zero per-op shard attrs (README '3D parallelism').  Slow marker:
    a fresh-process compile of the pipelined step; the same semantics
    run in-process in tests/test_pp.py::test_transformer_lm_rules_3d."""
    out = run_example("train_transformer_lm.py", "--num-steps", "8",
                      mesh=True)
    assert "train_transformer_lm OK" in out
    assert "dp=2 tp=2 pp=2" in out
