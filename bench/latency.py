"""Latency percentiles with failed requests counted as missing.

A request that failed, was shed, came back degraded or never came back
has no latency that meets any limit: it ranks above every answered one.
Where a percentile falls on such a request it reads ``MISSED_MS``, the
longest a reply is waited for, so that the metric stays a finite number
and is worse than any answered request.
"""

from __future__ import annotations

import math

MISSED_MS = 60_000.0


def percentile(latencies_ms: list[float], failed: int, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of the answered
    latencies together with ``failed`` requests that missed."""
    n = len(latencies_ms) + failed
    if n == 0:
        raise ValueError("no requests")
    rank = max(math.ceil(q / 100.0 * n), 1)
    ranked = sorted(latencies_ms)
    return ranked[rank - 1] if rank <= len(ranked) else MISSED_MS


def quantiles(values: list[float]) -> tuple[float, float, float]:
    """(first quartile, median, third quartile) as ``statistics.quantiles``
    gives them (its default, exclusive method)."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return q1, q2, q3


def spread(values: list[float]) -> float:
    """Distance between the quartiles as a share of the median."""
    q1, q2, q3 = quantiles(values)
    return (q3 - q1) / q2
