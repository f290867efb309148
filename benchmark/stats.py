"""The arithmetic from samples to a metric, in one place."""
import math


def percentile(samples, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; unrounded."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
