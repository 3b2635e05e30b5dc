"""95th percentile, over the window's hits, of the time a ready result
was held by the serving loop's submission-order release gate (the
program's own ``Completed.release_wait``, engine clock), in s."""
import numpy as np


def read(r):
    waits = [getattr(c, "release_wait", None) for c in r.window.done]
    if not waits or None in waits:
        return None    # the program does not count it
    held = np.array(waits)[r.hit]
    return float(np.percentile(held, 95)) if held.size else None
