"""Host wall per finalized request (``ServePipeline.finalize``: Archive
and Finish, maintenance sweeps included), from the benchmark's span, in
ms."""
import numpy as np


def read(r):
    w = r.rec.walls.get("finalize")
    return 1e3 * float(np.mean(w)) if w else None
