"""Row-gather plumbing shared by the scalar-prefetch gather kernels.

The kernels gather feature rows steered by a scalar-prefetched neighbour
table, in one of two ways (DESIGN.md §5):

  * One row per grid step (``csr_aggregate``, ``fused_zmax``,
    ``fused_quant_layer``): the table row ``nbr[i, s]`` is the block the
    BlockSpec pipeline fetches at step ``(i, s)``.
  * A block of rows per grid step (``fused_ideal_layer``): the table stays
    in HBM and the kernel copies each row by hand, ``block_rows`` rows and
    all their slots a step (``block_rows`` says how many).

Two limits of the TPU compiler shape both:

  * Tiling. A block's last two dims must be divisible by (8, 128) or equal
    the array's own, and so must a slice a DMA moves. A one-row block
    ``(1, F)`` of an ``[N, F]`` table is neither, so tables are viewed as
    ``[N, 1, F]`` and the row dim is squeezed: the block's last two dims
    ``(1, F)`` then equal the array's.
  * SMEM. Scalar-prefetched operands sit whole in SMEM (1 MiB on v5e), and
    a 2-D ``[Nd, S]`` table is lane-padded to 128 there. The tables go in
    flat, and ``map_row_chunks`` cuts the destination rows into chunks of at
    most ``TABLE_ENTRIES`` entries per table, one kernel call per chunk, so
    the SMEM a call needs is bounded at any node count.

Each destination row is computed by the same ops in the same order in
every chunk and every block, so neither changes a single output bit.

Every launch names its kernel and states its grid (``gather_metadata``):
both reach the profiler trace, so each launch's event there says which
kernel ran and how many grid steps it took.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TABLE_ENTRIES = 1 << 15     # per scalar-prefetched table: 128 KiB of 32-bit
GATHER_BUFFER_BYTES = 8 << 20   # both VMEM buffers of a block gather


def rows_view(a: jax.Array) -> jax.Array:
    """``[N, F] -> [N, 1, F]``: one row per block under ``row_block``."""
    return a.reshape(a.shape[0], 1, a.shape[1])


def row_block(width: int) -> tuple:
    """Block shape of one ``width``-lane row of a ``rows_view`` array."""
    return (pl.Squeezed(), 1, width)


def block_rows(rows: int, slots: int, width: int) -> int:
    """Destination rows a block gather handles per grid step: the largest
    multiple of 8 that divides ``rows`` rounded up to 8, for which its two
    float32 buffers of ``slots`` rows each, ``2 R slots width`` lane-padded
    entries, fit ``GATHER_BUFFER_BYTES``."""
    rows8 = -(-rows // 8) * 8
    lanes = -(-width // 128) * 128
    r = min(max(GATHER_BUFFER_BYTES // (8 * slots * lanes) // 8 * 8, 8),
            rows8)
    while rows8 % r:
        r -= 8
    return r


def gather_metadata(kernel: str, rows: int, slots: int, f_in: int,
                    f_out: int, **grid: int) -> dict:
    """``pallas_call(metadata=...)`` of one gather launch: the kernel's
    name, its chunk's ``rows`` x ``slots`` row-slots, the widths it reads
    and writes, and any further grid dimension or block size, all as
    strings. The compiled custom call carries it as ``kernel_metadata``,
    and so does its event in a profile."""
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
