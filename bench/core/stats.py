"""Percentiles and failure arithmetic over per-request stamps."""
from __future__ import annotations

import math
from typing import Iterable, List, Sequence

# drop reasons that are a correct outcome and not a latency sample
NOT_FAILURES = ("gate-reject",)


def percentile(values: Sequence[float], q: float) -> float:
    """Exact ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), over every value given."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latency_sample(outcomes: Iterable, censor_at: float) -> List[float]:
    """Seconds from due to completion of every window request that was
    not rejected by the gate.  A request that failed (dropped for any
    other reason) or never finished counts as missing: it is given the
    time from its due to ``censor_at``, the end of the wait, which is
    later than any completion."""
    out = []
    for o in outcomes:
        if o.reason in NOT_FAILURES:
            continue
        if o.reason is None and o.end is not None:
            out.append(o.end - o.due)
        else:
            out.append(max(censor_at - o.due, 0.0) + 1e-9)
    return out


def failed_count(outcomes: Iterable) -> int:
    """Window requests that failed: dropped for a reason other than the
    gate, or never finished."""
    return sum(1 for o in outcomes
               if (o.reason is not None and o.reason not in NOT_FAILURES)
               or o.end is None)
