"""Percentiles for the benchmark (a copy of ``benchmarks/_stats.py``'s).

Nearest rank: the p95 of 200 samples is the 190th smallest, a value some
request actually saw, never one interpolated between the two worst.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], p: float) -> float:
    """Nearest-rank percentile of ``xs`` (``p`` in [0, 100])."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0 <= p <= 100:
        raise ValueError(f"percentile {p} outside [0, 100]")
    s = sorted(xs)
    return s[max(1, math.ceil(p / 100.0 * len(s))) - 1]
