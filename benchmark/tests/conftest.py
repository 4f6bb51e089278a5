"""The benchmark's own tests: run by hand, on the CPU,

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are no part of the repo's tier-1 suite."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

TINY_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "data", "root")


def last_json(text):
    return json.loads(text.strip().splitlines()[-1])


@pytest.fixture
def run_cell(capfd, monkeypatch):
    """Drive a run past the harness's look for a chip; returns the
    result line and every earlier line."""
    from benchmark import run
    from benchmark.runners import serve_lm

    # the CPU's tiny engine shares two cores with the generator
    monkeypatch.setattr(serve_lm, "LATE_LIMIT_SHARE", 0.25)

    def go(workload, root=TINY_ROOT, seed=2147483999, seconds=2,
           trace=0):
        run.main(["--workload", workload, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", str(trace)],
                 root=root, require_tpu=False)
        out = capfd.readouterr().out
        lines = [json.loads(ln) for ln in out.splitlines()
                 if ln.startswith("{")]
        return lines[-1], lines[:-1]

    return go
