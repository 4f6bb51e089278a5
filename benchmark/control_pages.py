#!/usr/bin/env python3
"""One run of a ``serve_pages`` cell with its controls beside it.

    python3 benchmark/control_pages.py --workload <name> --seed <n> \\
        --seconds <s> [--controls fp8,no_window,no_rotation]

The run is ``benchmark/run.py``'s, unchanged; after the program's own
numbers the runner puts the float32 reference, recomputed in each form
named, through the same checks against the same limits (one
``{"control": ..., "correct": ...}`` line each, before the result
line).  ``fp8`` (the linear layers in e4m3, the nearest precision below
the bfloat16 the configuration computes in), ``no_window`` (the windowed
layers see every key) and ``no_rotation`` (no layer rotates) must each
read ``correct: false``: the exit code is 1 if one of them passes.  Any
other form the family's reference knows may be named (``bfloat16``
counts the positions whose expert sets are unstable).  What the limits
of a cell are set from; not run by the benchmark.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MUST_FAIL = ("fp8", "no_window", "no_rotation")


def main(argv=None, root=ROOT, require_tpu=True):
    argv = list(sys.argv[1:] if argv is None else argv)
    controls = ",".join(MUST_FAIL)
    if "--controls" in argv:
        at = argv.index("--controls")
        controls = argv[at + 1]
        del argv[at:at + 2]
    from benchmark import run
    from benchmark.runners import serve_pages

    serve_pages.CONTROLS = tuple(c for c in controls.split(",") if c)
    serve_pages.VERDICTS.clear()
    run.main(argv, root=root, require_tpu=require_tpu)
    passed = [c for c in MUST_FAIL if serve_pages.VERDICTS.get(c)]
    if passed:
        print(f"control_pages: {passed} read correct: true — the limits "
              f"do not hold that form", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
