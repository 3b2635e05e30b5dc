"""95th percentile of the wait from arrival to admission (the program's
own ``queue_delay``, engine clock) over the window's requests."""
import numpy as np


def read(r):
    q = r.queue_delay
    return float(np.percentile(q, 95)) if q.size else None
