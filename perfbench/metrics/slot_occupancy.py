"""Mean share of the slot buffer that held an in-flight chain at each
step launch of the window (the serving engine's own count), in %."""
import numpy as np


def read(r):
    occ = r.window.slot_occupancy
    if not occ:
        return None
    return 100.0 * float(np.mean(occ)) / r.window.slots
