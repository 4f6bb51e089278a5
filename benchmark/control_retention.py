#!/usr/bin/env python3
"""One run of a power-retention ``serve_spec`` cell with its controls
beside it.

    python3 benchmark/control_retention.py --workload <name> --seed <n> \\
        --seconds <s> [--controls fp8,bf16_state,...]

``control_spec.py``'s run, with the ``brumby`` family's forms of the
reference (``reference/brumby.py``) as the list that must fail: ``fp8``
(the linear layers in e4m3, the nearest precision below the bfloat16 the
configuration computes in), ``bf16_state`` (the recurrent state and its
normaliser rounded to bfloat16 token by token, the nearest below the
float32 it states for them), ``no_gate`` (gamma = 0: nothing is
forgotten), ``no_division`` (the summed weights left out),
``no_rotation``, ``no_qk_norm`` and ``degree4`` (the fourth power of q.k
for the square) must each read ``correct: false``: the exit code is 1 if
one of them passes.  ``bfloat16`` (the reference's products in the
program's own precision) is no control and may be named beside them.
What the limits of a cell are set from; not run by the benchmark.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MUST_FAIL = ("fp8", "bf16_state", "no_gate", "no_division", "no_rotation",
             "no_qk_norm", "degree4")


def main(argv=None, root=ROOT, require_tpu=True):
    from benchmark import control_spec

    # that file holds its list in a constant it reads when it runs
    control_spec.MUST_FAIL = MUST_FAIL
    return control_spec.main(argv, root=root, require_tpu=require_tpu)


if __name__ == "__main__":
    sys.exit(main())
