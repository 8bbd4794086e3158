"""The comparison that decides ``correct``: an update's served table
against the plain reference of the cell's model kind, which the model's
module holds (``bench/models/<kind>.py``, ``forward``).
"""
from __future__ import annotations

import numpy as np


def gaps(got: np.ndarray, ref: np.ndarray) -> tuple:
    """``(rms, max)``: the RMS and the largest absolute gap between ``got``
    and ``ref``, each over the same statistic of ``ref``. A shape mismatch or
    a value that is not finite reads as infinite."""
    got = np.asarray(got)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf"), float("inf")
    d = got.astype(np.float64) - ref.astype(np.float64)
    r = ref.astype(np.float64)
    rms = float(np.sqrt(np.mean(d * d)) / max(np.sqrt(np.mean(r * r)), 1e-30))
    mx = float(np.abs(d).max() / max(float(np.abs(r).max()), 1e-30))
    return rms, mx
