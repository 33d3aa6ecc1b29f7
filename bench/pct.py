"""Percentiles as the repo's load report takes them (numpy's linear
interpolation between order statistics), copied here so that the
yardstick does not move with the program."""

from __future__ import annotations

import numpy as np


def pct(vals, q: float) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q))
