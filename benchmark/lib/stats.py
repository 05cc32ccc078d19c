"""Percentiles and spreads, kept with the benchmark so that no later PR
can move the yardstick.

``percentile`` is a copy of ``deepspeed_tpu/telemetry/derive.py``'s: the
sample at rank ``int(q * (n - 1))`` of the sorted values, no interpolation.
``spread`` is the contract's: the distance between the first and third
quartile as ``statistics.quantiles(values, n=4)`` gives them, as a share of
the median.
"""

from __future__ import annotations

import statistics
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """``q`` in [0, 1].  Raises on no samples: a benchmark metric with no
    sample is left out of the line, never written as 0."""
    if not xs:
        raise ValueError("percentile of no samples")
    ys = sorted(xs)
    return ys[min(len(ys) - 1, int(q * (len(ys) - 1)))]


def spread(xs: Sequence[float]) -> float:
    """Interquartile distance over the median."""
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
