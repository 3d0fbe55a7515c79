"""Median, over every request due in the window, of the time from when it
was due to its completion (ms)."""

import numpy as np


def read(run):
    value = float(np.percentile(run.latencies_s(), 50)) * 1e3
    return value if np.isfinite(value) else None
