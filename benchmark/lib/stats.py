"""Small arithmetic on samples (copied from serve/loadgen.py's `_pct`,
which stays where it is until a later PR deletes it)."""
from __future__ import annotations

import statistics
from typing import Sequence

import numpy as np


def pct(xs: Sequence[float], q: float) -> float:
    """q-th percentile (0..100), linear interpolation; 0.0 of nothing."""
    if len(xs) == 0:
        return 0.0
    return float(np.percentile(np.asarray(xs, np.float64), q))


def quartile_spread(xs: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(n=4)` — the
    spread the driver reads."""
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / abs(statistics.median(xs))
