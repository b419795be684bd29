"""Arithmetic shared by the metric readers in ``bench/metrics/``."""

from __future__ import annotations

import math

import numpy as np

__all__ = ["STEP_MODULE", "percentile_ms", "flush_fill", "lockstep_waste",
           "bucket_for"]

# the serving executable's module name in the device trace
STEP_MODULE = r"search_step"


def percentile_ms(latency_s: np.ndarray, answered: np.ndarray,
                  q: float) -> float:
    """Nearest-rank ``q``-th percentile, in ms, over every due query; a
    query that was not answered counts as +inf."""
    lat = np.where(answered, latency_s, np.inf)
    if not len(lat):
        return math.nan
    s = np.sort(lat)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1]) * 1e3


def bucket_for(n: int, buckets) -> int:
    for b in sorted(buckets):
        if b >= n:
            return b
    raise ValueError(f"flush of {n} exceeds the largest bucket "
                     f"{max(buckets)}")


def flush_fill(flush_sizes, buckets) -> float | None:
    """Real rows over padded bucket rows, as a percentage."""
    if not flush_sizes:
        return None
    rows = sum(flush_sizes)
    padded = sum(bucket_for(n, buckets) for n in flush_sizes)
    return 100.0 * rows / padded


def lockstep_waste(hops: np.ndarray) -> float | None:
    """Over flushes of (S, L) per-lane hop counts: sum of S * L * max(hops)
    over sum of hops (1 = every lane ran as long as the slowest)."""
    hops = np.asarray(hops, np.int64).reshape(len(hops), -1)
    taken = hops.sum()
    if taken <= 0:
        return None
    return float((hops.shape[1] * hops.max(axis=1)).sum() / taken)
