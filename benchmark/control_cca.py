#!/usr/bin/env python3
"""One run of a ``serve_pages_relative`` cell with its controls beside
it.

    python3 benchmark/control_cca.py --workload <name> --seed <n> \\
        --seconds <s> [--controls fp8,no_conv,...] [--requests <n>]

The run is ``benchmark/run.py``'s, unchanged; after the program's own
numbers the runner puts the float32 reference, recomputed in each form
named (``reference/zaya.py``), through the same checks against the same
limits and the same divisor — what the reference's own bfloat16 form
reads on those requests (one ``{"control": ..., "correct": ...}`` line
each, before the result line).  ``fp8`` (the linear layers in e4m3, the
nearest precision below the bfloat16 the configuration computes in),
``no_conv`` (neither convolution: e = u), ``no_qk_mean``,
``value_current`` (the value's second half from the CURRENT token),
``no_rotation``, ``rotate_all`` (all 128 lanes of a head rotated),
``no_temperature``, ``no_carry`` (the router's hidden row not carried),
``weight_one`` (the chosen expert's weight 1.0), ``no_select_bias``
(the choice made by p, not p + b) and ``no_residual_scale`` must each
read ``correct: false``: the exit code is 1 if one of them passes.
``bfloat16`` (the products alone in bfloat16: a yardstick that rounds
less than the runner's) is no control and may be named beside them.
``--requests`` reads the controls on the sample's first n requests (each
is two more passes of the reference a form; left out: the whole sample).
What the limits of a cell are set from; not run by the benchmark.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MUST_FAIL = ("fp8", "no_conv", "no_qk_mean", "value_current",
             "no_rotation", "rotate_all", "no_temperature", "no_carry",
             "weight_one", "no_select_bias", "no_residual_scale")


def taken(argv, flag, default):
    """``flag``'s value, taken out of ``argv``."""
    if flag not in argv:
        return default
    at = argv.index(flag)
    value = argv[at + 1]
    del argv[at:at + 2]
    return value


def main(argv=None, root=ROOT, require_tpu=True):
    argv = list(sys.argv[1:] if argv is None else argv)
    controls = taken(argv, "--controls", ",".join(MUST_FAIL)).split(",")
    requests = taken(argv, "--requests", None)
    from benchmark import run
    from benchmark.runners import serve_pages_relative as runner

    runner.CONTROLS = tuple(c for c in controls if c)
    runner.CONTROL_REQUESTS = None if requests is None else int(requests)
    runner.VERDICTS.clear()
    run.main(argv, root=root, require_tpu=require_tpu)
    passed = [c for c in MUST_FAIL if runner.VERDICTS.get(c)]
    if passed:
        print(f"control_cca: {passed} read correct: true — the limits do "
              f"not hold that form", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
