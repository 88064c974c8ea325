"""Arithmetic of the metrics: percentiles, time per output token, gaps
between tokens. Plain Python on lists of floats."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics. Raises on an empty list: a tail of nothing is not 0."""
    if not values:
        raise ValueError("percentile of an empty list")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tpot_s(token_times: Sequence[float]) -> Optional[float]:
    """Time per output token of one request: last token's time minus the
    first's, over the output tokens less one. None for fewer than two."""
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1)


def gaps_s(token_times: Sequence[float], start: float, end: float) -> List[float]:
    """Gaps between consecutive tokens of one request, those that end
    inside ``[start, end)``."""
    return [
        b - a for a, b in zip(token_times, token_times[1:]) if start <= b < end
    ]
