"""Percentiles and spreads, kept with the benchmark so that no change to the
program moves the yardstick.

The latency arithmetic is copied from the program's request records
(``repro.serving.metrics``): a time to first token runs from the request's
due time to its first token, and a gap between tokens from one emitted
token to the next of the same request.  Percentiles interpolate linearly
between order statistics, as ``numpy.percentile`` does by default.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values``; raises on no values."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
