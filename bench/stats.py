"""Order statistics over raw samples (the program's
``serving/metrics.LatencyHistogram.percentiles_ms`` arithmetic: numpy's
linear interpolation between closest ranks)."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    values = np.asarray(values, dtype=np.float64)
    if values.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(values, q))
