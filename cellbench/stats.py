"""The two statistics the end-to-end metrics use."""

from __future__ import annotations

import statistics


def median(values) -> float:
    return float(statistics.median(values))


def p95(values) -> float:
    """The 95th percentile by the nearest-rank rule: the smallest value with
    at least 95% of the sample at or under it."""
    ordered = sorted(values)
    return float(ordered[-(-95 * len(ordered) // 100) - 1])
