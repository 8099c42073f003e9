"""The benchmark's own arithmetic on samples."""
from __future__ import annotations

import numpy as np


def percentile(xs, q: float) -> float | None:
    """The q-th percentile with linear interpolation; None for no samples."""
    xs = np.asarray(list(xs), np.float64)
    if xs.size == 0:
        return None
    return float(np.percentile(xs, q))

