"""Order statistics of the benchmark, kept with it so no PR can move them.

``percentile_ms`` is the "higher" order statistic: the smallest sample at
or above the requested rank, so a tail is a latency some request paid
(arithmetic copied from the program's ``serve/metrics.percentiles``).
"""
from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], p: float) -> float:
    if not len(samples):
        raise ValueError("no samples")
    s = sorted(samples)
    idx = min(len(s) - 1, max(0, int(math.ceil(p / 100.0 * (len(s) - 1)))))
    return float(s[idx])


def percentile_ms(samples_s: Sequence[float], p: float) -> float:
    return percentile(samples_s, p) * 1e3
