"""Work one embedding update needs, computed from shapes alone.

A GCN layer over a padded neighbour sample of S slots aggregates
``Z = A_hat X`` and transforms ``H = act(Z W + b)``. Per layer:

  model FLOPs       2 N S F_in  (aggregation)  +  2 N F_in F_out  (transform)
  compulsory bytes  the input rows, read once        (N_in F_in x 4)
                    the neighbour and weight tables  (N S x (4 + 4))
                    W                                 (F_in F_out x 4)
                    the output, written once          (N F_out x 4)

On a mesh each device counts its own rows: N is the rows it owns and N_in
adds the halo rows it receives. Counted this way the work does not depend
on what implements it (padding, chunking and tiling are not work), so no
rewrite of a kernel can push a share of the roofline past 100%.
"""
from __future__ import annotations

import numpy as np

ITEM = 4        # float32 features, weights and outputs; int32 indices


def layer_flops(n_rows: int, sample: int, f_in: int, f_out: int) -> tuple:
    """(aggregation, transform) FLOPs of one layer over ``n_rows`` rows."""
    return 2.0 * n_rows * sample * f_in, 2.0 * n_rows * f_in * f_out


def layer_bytes(n_rows: int, n_in_rows: int, sample: int, f_in: int,
                f_out: int) -> float:
    """Compulsory HBM bytes of one layer (module docstring)."""
    return float(ITEM * (n_in_rows * f_in + n_rows * sample * 2
                         + f_in * f_out + n_rows * f_out))


def model_flops(n_rows: int, sample: int, dims) -> float:
    """Model FLOPs of one update of all ``n_rows`` embeddings."""
    return sum(sum(layer_flops(n_rows, sample, a, b))
               for a, b in zip(dims[:-1], dims[1:]))


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


def update_work(dims, sample: int, rows: list) -> list:
    """Per device, per layer ``{"flops", "bytes"}`` of one update.

    ``rows`` is ``[(owned, halo)]`` per device; one device with no halo is
    the centralized case.
    """
    work = []
    for owned, halo in rows:
        layers = []
        for f_in, f_out in zip(dims[:-1], dims[1:]):
            layers.append({
                "flops": sum(layer_flops(owned, sample, f_in, f_out)),
                "bytes": layer_bytes(owned, owned + halo, sample, f_in,
                                     f_out)})
        work.append(layers)
    return work


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
