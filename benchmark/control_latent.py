#!/usr/bin/env python3
"""One run of a latent-attention ``serve_pages`` cell with its controls
beside it.

    python3 benchmark/control_latent.py --workload <name> --seed <n> \\
        --seconds <s> [--controls fp8,fp8_latent,...]

``control_pages.py``'s run for the ``deepseek_v3`` family's forms of the
reference (``reference/deepseek_v3.py``): ``fp8`` (the linear layers in
e4m3, the nearest precision below the bfloat16 the configuration
computes in), ``no_rotation``, ``plain_freq`` (the rotary frequencies not
rescaled), ``no_select_bias`` (the experts chosen by the unbiased
scores) and ``no_group_limit`` (chosen among all experts) must each
read ``correct: false``: the exit code is 1 if one of them passes.
``fp8_latent`` (what a token leaves in the cache rounded to e4m3) is
run and reported beside them and decides nothing: at full size it reads
1.1-1.6 x the sound program's mean gap, inside the limits (PERF.md
section 2) — a served logit does not hold the cache's type.  Any other
form the reference knows may be named.  What the limits of a cell are
set from; not run by the benchmark.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MUST_FAIL = ("fp8", "no_rotation", "plain_freq", "no_select_bias",
             "no_group_limit")
REPORTED = ("fp8_latent",)       # run beside them, decides nothing


def main(argv=None, root=ROOT, require_tpu=True):
    argv = list(sys.argv[1:] if argv is None else argv)
    controls = ",".join(MUST_FAIL + REPORTED)
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
        print(f"control_latent: {passed} read correct: true — the limits "
              f"do not hold that form", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
