"""Tails, in one place."""
from __future__ import annotations

import numpy as np


def p95(values) -> float:
    """95th percentile, linear between the two nearest samples."""
    return float(np.percentile(np.asarray(values, np.float64), 95))
