"""Median, over every decision of the window, of the time from its push's
due time to the decision being on the host (ms)."""

import numpy as np


def read(run):
    n = [p.decisions for p in run.pushes]
    if not sum(n):
        return None
    lat = [p.collected - p.due for p in run.pushes]
    return float(np.percentile(np.repeat(lat, n), 50) * 1e3)
