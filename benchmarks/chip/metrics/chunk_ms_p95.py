"""The 95th percentile of the wall time of every compiled chunk in the
window (host clock).  A chunk ends at the host sync at which the round
metrics, and the global model with them, reach the host."""
import numpy as np


def read(ctx):
    return float(np.percentile(np.asarray(ctx.chunk_s) * 1e3, 95)) \
        if ctx.chunk_s else None
