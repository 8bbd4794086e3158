"""Row-gather plumbing shared by the scalar-prefetch gather kernels.

``csr_aggregate`` and the ``fused_layer`` kernels fetch one feature row per
grid step, steered by a scalar-prefetched neighbour table. Two limits of
the TPU compiler shape how they do it (DESIGN.md §5):

  * Tiling. A block's last two dims must be divisible by (8, 128) or equal
    the array's own. A one-row block ``(1, F)`` of an ``[N, F]`` table is
    neither, so tables and outputs are viewed as ``[N, 1, F]`` and the row
    dim is squeezed: the block's last two dims ``(1, F)`` then equal the
    array's.
  * SMEM. Scalar-prefetched operands sit whole in SMEM (1 MiB on v5e), and
    a 2-D ``[Nd, S]`` table is lane-padded to 128 there. The tables go in
    flat, and ``map_row_chunks`` cuts the destination rows into chunks of at
    most ``TABLE_ENTRIES`` entries per table, one kernel call per chunk, so
    the SMEM a call needs is bounded at any node count.

Each destination row is computed by the same ops in the same order in
every chunk, so chunking does not change a single output bit.

Every launch names its kernel and states its grid (``gather_metadata``):
both reach the profiler trace, so each launch's event there says which
kernel ran and how many grid steps it took.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TABLE_ENTRIES = 1 << 15     # per scalar-prefetched table: 128 KiB of 32-bit


def rows_view(a: jax.Array) -> jax.Array:
    """``[N, F] -> [N, 1, F]``: one row per block under ``row_block``."""
    return a.reshape(a.shape[0], 1, a.shape[1])


def row_block(width: int) -> tuple:
    """Block shape of one ``width``-lane row of a ``rows_view`` array."""
    return (pl.Squeezed(), 1, width)


def gather_metadata(kernel: str, rows: int, slots: int, f_in: int,
                    f_out: int, **grid: int) -> dict:
    """``pallas_call(metadata=...)`` of one gather launch: the kernel's
    name, its chunk's ``rows`` x ``slots`` grid, the widths it reads and
    writes, and any further grid dimension, all as strings. The compiled
    custom call carries it as ``kernel_metadata``, and so does its event in
    a profile."""
    meta = dict(kernel=kernel, rows=rows, slots=slots, f_in=f_in,
                f_out=f_out, **grid)
    return {k: str(v) for k, v in meta.items()}


def map_row_chunks(call, neighbors: jax.Array, weights: jax.Array):
    """Run ``call(nbr_flat, wts_flat) -> [rows, ...]`` over chunks of the
    destination rows and return the stacked ``[Nd, ...]`` result.

    ``neighbors``/``weights`` are ``[Nd, S]``; each call receives its
    chunk's tables flattened row-major (entry ``i * S + s``). A short last
    chunk is padded with weight-0 rows whose outputs are dropped.
    """
    nd, s = neighbors.shape
    weights = weights.astype(jnp.float32)
    rows = max(TABLE_ENTRIES // s, 1)
    if nd <= rows:
        return call(neighbors.reshape(-1), weights.reshape(-1))
    n_chunks = -(-nd // rows)
    pad = ((0, n_chunks * rows - nd), (0, 0))
    nbr = jnp.pad(neighbors, pad).reshape(n_chunks, rows * s)
    wts = jnp.pad(weights, pad).reshape(n_chunks, rows * s)
    out = jax.lax.map(lambda t: call(*t), (nbr, wts))
    return out.reshape(n_chunks * rows, *out.shape[2:])[:nd]
