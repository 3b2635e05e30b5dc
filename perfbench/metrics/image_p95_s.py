"""95th percentile of arrival-to-image latency over every request of the
window, on the engine's clock."""
import numpy as np


def read(r):
    return float(np.percentile(r.latency, 95)) if r.latency.size else None
