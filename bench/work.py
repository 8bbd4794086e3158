"""Work of one embedding update that does not depend on the model kind:
the rows each device owns and receives, and the least time an update's
work could take on the chips. The work itself, model FLOPs and per device
and layer ``{"flops", "bytes"}``, is the model kind's
(``bench/models/<kind>.py``, ``work``).
"""
from __future__ import annotations

import numpy as np


def device_rows(nbr: np.ndarray, wts: np.ndarray, assignment: np.ndarray,
                n_devices: int) -> list:
    """``[(owned rows, halo rows received)]`` per device.

    ``nbr``/``wts`` are the global padded sample ``[N, S]``; a slot with
    weight 0 is padding. Device d receives, once per layer, every distinct
    row that a slot of one of its rows names and another device owns.
    """
    out = []
    real = wts != 0
    for d in range(n_devices):
        rows = np.nonzero(assignment == d)[0]
        v = nbr[rows][real[rows]]
        remote = np.unique(v[assignment[v] != d])
        out.append((len(rows), len(remote)))
    return out


def ideal_seconds(work: list, peaks: dict) -> list:
    """Per device, the least time one update's layers could take: per layer
    the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s.
    Returns ``[(seconds, bound)]`` with ``bound`` "memory" or "compute"
    (the bound of the larger layer)."""
    out = []
    for layers in work:
        total, worst, bound = 0.0, -1.0, "memory"
        for lw in layers:
            tc = lw["flops"] / peaks["flops_per_s"]
            tm = lw["bytes"] / peaks["hbm_bytes_per_s"]
            t = max(tc, tm)
            total += t
            if t > worst:
                worst, bound = t, ("compute" if tc > tm else "memory")
        out.append((total, bound))
    return out
