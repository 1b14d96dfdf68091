"""One clock for every stamp the benchmark makes, and the percentile it
reports them by."""

import time

now = time.perf_counter


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between the
    order statistics; None of nothing."""
    v = sorted(values)
    if not v:
        return None
    at = (len(v) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (at - lo)
