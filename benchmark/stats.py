"""The percentile arithmetic, in one place."""

import math


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default).  An infinite value (a failed
    request counts as worse than any latency) sorts last and is
    returned as such when the percentile reaches it."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
