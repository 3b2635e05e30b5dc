"""Median arrival-to-image latency over every request of the window, on
the engine's clock: from when the request was due to when its result
came back."""
import numpy as np


def read(r):
    return float(np.percentile(r.latency, 50)) if r.latency.size else None
