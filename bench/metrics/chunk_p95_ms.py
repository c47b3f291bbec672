"""95th percentile, over every chunk of the window, of one chunk's wall time
from its call to its metrics row on the host (linear interpolation between
order statistics, numpy's default)."""
import numpy as np


def read(run):
    if not run.chunk_s:
        return None
    return float(np.percentile(np.asarray(run.chunk_s), 95)) * 1e3
