"""Test configuration.

Runs the whole suite on a virtual 8-device CPU mesh (the reference
tests multi-GPU semantics on CPU the same way — SURVEY §4
"Multi-device without a cluster").  Must set flags before jax import.

JAX_PLATFORMS is *overridden*, not setdefault: this is a CPU suite
wherever it runs.  Finite-difference gradient tests need CPU float32
matmul precision, several xdist workers cannot share one chip, and a
machine that has a chip sets JAX_PLATFORMS to it.  What runs on the
chip is ``chip_smoke.py``; what compiles for it without one is
``tests/test_tpu_compile.py``.
"""

import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# pytest plugins (hypothesis) import jax before this file runs; backends
# initialize lazily, so pushing the config through jax.config still works.
if "jax" in sys.modules:
    import jax

    jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-running tests excluded from the "
        "tier-1 `-m 'not slow'` gate (decode-pool fan-out, kill-and-"
        "resume subprocess drills)")


@pytest.fixture(params=[False, True], ids=["lax", "pallas"])
def kernels(request, monkeypatch):
    """Both bodies of every op: the lax fallback and the Pallas kernels
    (interpreted on the CPU)."""
    monkeypatch.setenv("MXNET_PALLAS", "1" if request.param else "0")
    return request.param


@pytest.fixture(scope="module")
def engines():
    """One engine a file for each distinct argument set (``_engines``)."""
    import _engines

    held = _engines.Engines()
    yield held
    held.close()


@pytest.fixture(autouse=True)
def _engines_a_test_built_are_closed():
    yield
    # (not imported here: most files build no engine, and the module
    # imports the package)
    mod = sys.modules.get("_engines")
    while mod is not None and mod.UNCLOSED:
        mod.UNCLOSED.pop().close()
