"""query_service_us: the median time ``GNNServer.query`` takes for one
lookup batch, from its call to its return, wait excluded (host clock)."""
import numpy as np


def read(r):
    if not len(r.lookup_start):
        return None
    return float(np.median(r.lookup_end - r.lookup_start)) * 1e6
