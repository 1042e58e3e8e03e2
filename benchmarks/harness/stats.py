"""Percentiles as the benchmark reports them (nearest rank, so every
reported value is a measured one)."""

from __future__ import annotations

import math


def percentile(values, pct: float):
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[rank - 1]
