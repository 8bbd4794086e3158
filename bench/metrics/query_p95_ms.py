"""query_p95_ms: the 95th percentile over all lookup batches of the window,
each timed from its scheduled due time to the return of its rows, so the
wait behind an update in progress counts (host clock)."""
import numpy as np


def read(r):
    if not len(r.lookup_due):
        return None
    return float(np.percentile(r.lookup_end - r.lookup_due, 95)) * 1e3
