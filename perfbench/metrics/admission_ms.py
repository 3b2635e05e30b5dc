"""Host wall per admission pass (``ServePipeline.run_admission``: Embed
to Plan, the fused scan included), from the benchmark's span, in ms."""
import numpy as np


def read(r):
    w = r.rec.walls.get("admission")
    return 1e3 * float(np.mean(w)) if w else None
