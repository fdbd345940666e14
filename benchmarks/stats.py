"""Arithmetic the metrics rest on, kept apart so tests can pin it."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q percent
    of the sample at or below it. No interpolation: a p99 is a latency
    some call really had."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = max(int(math.ceil(q / 100.0 * len(xs))), 1)
    return xs[rank - 1]


def beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return n - max(int(math.ceil(q / 100.0 * n)), 1)
