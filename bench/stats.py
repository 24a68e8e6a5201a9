"""Nearest-rank percentiles (copied from ``repro.obs.stats``).

The q-th percentile of n sorted samples is the sample at index
``ceil(q·n) - 1``: the smallest sample with at least ``q·n`` samples at or
below it.
"""

from __future__ import annotations

import math
from typing import Iterable


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (``0 < q <= 1``) of ``values``."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of an empty sequence")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q must be in (0, 1], got {q}")
    return s[max(math.ceil(q * len(s)) - 1, 0)]
