"""The arithmetic of the comparisons that decide ``correct``."""

import statistics


def worst_leaf_gap(program, reference, skip=()):
    """The widest gap, over the leaves, between the program's norm and
    the reference's (the gap of the norms, not the norm of a
    difference), measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger: some leaves' gradients are
    all but zero.  Returns (gap, leaf)."""
    names = [k for k in reference if not k.endswith(tuple(skip))] \
        if skip else list(reference)
    median = statistics.median(reference[k] for k in names)
    worst, where = 0.0, None
    for k in names:
        gap = abs(program[k] - reference[k]) / max(reference[k], median)
        if gap > worst or where is None:
            worst, where = gap, k
    return worst, where
