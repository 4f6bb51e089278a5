#!/usr/bin/env python3
"""One run of a ``serve_spec`` cell with its controls beside it.

    python3 benchmark/control_spec.py --workload <name> --seed <n> \\
        --seconds <s> [--controls fp8,bf16_state,bfloat16]

The run is ``benchmark/run.py``'s, unchanged; after the program's own
numbers the runner puts the float32 reference, recomputed in each
precision named, through the same checks against the same limits (one
``{"control": ..., "correct": ...}`` line each, before the result
line).  ``fp8`` (the linear layers in e4m3, the nearest precision below
the bfloat16 the configuration computes in) and ``bf16_state`` (the
recurrent state rounded to bfloat16 token by token, the nearest below
the float32 it states for the state) must each read ``correct: false``:
the exit code is 1 if one of them passes.  ``bfloat16`` (the
reference's products in the program's own precision) is no control: it
counts the positions whose expert sets are unstable.  What the limits
of a cell are set from; not run by the benchmark.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MUST_FAIL = ("fp8", "bf16_state")


def main(argv=None, root=ROOT, require_tpu=True):
    argv = list(sys.argv[1:] if argv is None else argv)
    controls = ",".join(MUST_FAIL)
    if "--controls" in argv:
        at = argv.index("--controls")
        controls = argv[at + 1]
        del argv[at:at + 2]
    from benchmark import run
    from benchmark.runners import serve_spec

    serve_spec.CONTROLS = tuple(c for c in controls.split(",") if c)
    serve_spec.VERDICTS.clear()
    run.main(argv, root=root, require_tpu=require_tpu)
    passed = [c for c in MUST_FAIL if serve_spec.VERDICTS.get(c)]
    if passed:
        print(f"control_spec: {passed} read correct: true — the limits "
              f"do not hold that precision", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
