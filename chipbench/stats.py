"""Percentiles as the benchmark reports them."""
import numpy as np


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), 95))
