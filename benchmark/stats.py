"""Statistics of the benchmark's own, kept here so no PR can change them."""

import math


def percentile(samples, q):
    """Nearest-rank q-th percentile (q in [0, 100]); None without samples."""
    if not samples:
        return None
    s = sorted(samples)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]
