"""Median push-to-result latency, from each request's due time, over every
request due in the window (host clock)."""

import numpy as np


def read(run):
    return float(np.quantile(run.latencies(), 0.5))
