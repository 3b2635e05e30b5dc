"""95th percentile of arrival-to-image latency over the requests served
with 0 denoising steps that were not coalesced onto an in-flight
generation: what a cache hit costs its user."""
import numpy as np


def read(r):
    lat = r.latency[r.hit]
    return float(np.percentile(lat, 95)) if lat.size else None
