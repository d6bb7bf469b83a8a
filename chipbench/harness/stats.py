"""Order statistics, taken the same way everywhere in the benchmark."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of all ``values``, nearest rank:
    the smallest value with at least q% of the samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])

