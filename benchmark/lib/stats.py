"""The percentile rule and the few statistics every reduction shares.

One rule for every percentile the benchmark prints: linear interpolation
between the two closest ranks of the sorted sample (Hyndman-Fan type 7,
numpy's default and ``statistics.quantiles(..., method="inclusive")``).
Pure Python: the jax-free client imports this too.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) of ``values``; None when empty."""
    xs: List[float] = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 50.0)


def mean(values: Sequence[float]) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None
