"""Order statistics the metrics share, so that every number is taken the
same way: medians of whole samples, rank percentiles, quartile spread."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def median(values: Sequence[float]) -> float:
    s = sorted(values)
    if not s:
        raise ValueError("median of an empty sample")
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else 0.5 * (s[mid - 1] + s[mid])


def percentile(sorted_vals: Sequence[float], q: float) -> Optional[float]:
    """Rank percentile over an ASCENDING-sorted sample (copy of
    ``benchmark/serving_common.percentile``, the program's one
    convention).  None on an empty sample."""
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median with linearly interpolated quartiles: the
    spread the bounds in BENCHMARK.json are set from."""
    s = sorted(values)

    def at(q):
        pos = q * (len(s) - 1)
        lo = math.floor(pos)
        hi = min(lo + 1, len(s) - 1)
        return s[lo] + (s[hi] - s[lo]) * (pos - lo)

    return (at(0.75) - at(0.25)) / median(s)


def histogram_delta_quantile(before: dict, after: dict, q: float
                             ) -> Optional[float]:
    """Quantile of the observations a registry histogram took between two
    snapshots: the upper edge of the bucket that holds the q-th of them
    (the overflow bucket reports the run's maximum).  None if it took
    none."""
    counts = [a - b for a, b in zip(after["counts"], before["counts"])]
    total = sum(counts)
    if total <= 0:
        return None
    rank, acc = q * total, 0
    for i, c in enumerate(counts):
        acc += c
        if c and acc >= rank:
            edges = after["boundaries"]
            return float(edges[i]) if i < len(edges) else float(after["max"])
    return float(after["max"])
