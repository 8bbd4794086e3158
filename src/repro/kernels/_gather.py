"""Row-gather plumbing shared by the scalar-prefetch gather kernels.

The kernels gather feature rows steered by a scalar-prefetched neighbour
table, in one of two ways (DESIGN.md §5):

  * One row per grid step (``csr_aggregate``, ``fused_zmax``,
    ``fused_quant_layer``): the table row ``nbr[i, s]`` is the block the
    BlockSpec pipeline fetches at step ``(i, s)``.
  * A block of rows per grid step (``fused_ideal_layer``): the table stays
    in HBM and the kernel copies each row by hand, ``block_rows`` rows and
    all their slots a step (``block_rows`` says how many). Where one slot
    names consecutive table rows over the whole block, as a self loop does
    when the destination rows are the table's own, ``block_runs`` finds
    the run and the kernel moves it with one DMA of ``block_rows`` rows
    instead of one a row; ``gather_dmas`` counts what a launch issues.
    The launch reads its neighbour table as ``BlockNeighbors``: each
    chunk's indices flat, with a run table per block size. A plan whose
    sample does not change between calls builds it once
    (``block_neighbors``) and passes it in place of the ``[Nd, S]``
    table; given a raw table, the launch builds it itself.

Two limits of the TPU compiler shape both:

  * Tiling. A block's last two dims must be divisible by (8, 128) or equal
    the array's own, and so must a slice a DMA moves. A one-row block
    ``(1, F)`` of an ``[N, F]`` table is neither, so tables are viewed as
    ``[N, 1, F]`` and the row dim is squeezed: the block's last two dims
    ``(1, F)`` then equal the array's.
  * SMEM. Scalar-prefetched operands sit whole in SMEM (1 MiB on v5e), and
    a 2-D ``[Nd, S]`` table is lane-padded to 128 there. The tables go in
    flat, and ``chunk_tables`` cuts the destination rows into chunks of at
    most ``TABLE_ENTRIES`` entries per table, one kernel call per chunk
    (``map_row_chunks``), so the SMEM a call needs is bounded at any node
    count.

Each destination row is computed by the same ops in the same order in
every chunk and every block, so neither changes a single output bit.

Every launch names its kernel and states its grid (``gather_metadata``):
both reach the profiler trace, so each launch's event there says which
kernel ran and how many grid steps it took.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

TABLE_ENTRIES = 1 << 15     # per scalar-prefetched table: 128 KiB of 32-bit
GATHER_BUFFER_BYTES = 8 << 20   # both VMEM buffers of a block gather


def rows_view(a: jax.Array) -> jax.Array:
    """``[..., N, F] -> [..., N, 1, F]``: one row per block under
    ``row_block``."""
    return a.reshape(*a.shape[:-1], 1, a.shape[-1])


def row_block(width: int) -> tuple:
    """Block shape of one ``width``-lane row of a ``rows_view`` array."""
    return (pl.Squeezed(), 1, width)


def chunk_padded(rows: int) -> int:
    """Rows of a block gather's chunk of ``rows`` destination rows, weight-0
    padding included: ``rows`` rounded up to 8. Every ``block_rows(rows,
    ...)`` divides it, so one chunked table serves every block size."""
    return -(-rows // 8) * 8


def block_rows(rows: int, slots: int, width: int) -> int:
    """Destination rows a block gather handles per grid step: the largest
    multiple of 8 that divides ``chunk_padded(rows)``, for which its two
    float32 buffers of ``slots`` rows each, ``2 R slots width`` lane-padded
    entries, fit ``GATHER_BUFFER_BYTES``."""
    rows8 = chunk_padded(rows)
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


def chunk_rows(nd: int, slots: int) -> int:
    """Destination rows of one kernel call: all ``nd`` where their tables
    fit ``TABLE_ENTRIES``, else as many as do."""
    return min(nd, max(TABLE_ENTRIES // slots, 1))


def chunk_tables(table, rows: int, padded: int):
    """``[..., Nd, S] -> [..., C, padded, S]``: the table cut into chunks of
    ``rows`` destination rows, each chunk zero padded to ``padded`` rows,
    the short last one too. Works on numpy and jnp tables alike."""
    xp = np if isinstance(table, np.ndarray) else jnp
    *lead, nd, s = table.shape
    c = -(-nd // rows)
    keep = [(0, 0)] * len(lead)
    t = xp.pad(table, keep + [(0, c * rows - nd), (0, 0)])
    return xp.pad(t.reshape(*lead, c, rows, s),
                  keep + [(0, 0), (0, padded - rows), (0, 0)])


def map_chunks(call, *tables):
    """``call`` over the leading chunk axis of ``tables``, stacked: one
    traced call for one chunk, else a ``lax.map``."""
    if tables[0].shape[0] == 1:
        return call(*(t[0] for t in tables))[None]
    return jax.lax.map(lambda t: call(*t), tables)


def block_runs(nbr, block: int, n_rows: int):
    """Per block of ``block`` destination rows, the slot whose indices over
    the block are an ascending run, and the run's first table row.

    ``nbr`` is ``[..., M, S]`` with ``M`` a multiple of ``block``, numpy or
    jnp. Slot ``s`` of block ``j`` is a run where ``nbr[jR + r, s] ==
    nbr[jR, s] + r`` for every ``r < R`` and the run lies inside the
    ``n_rows``-row table; its ``R`` rows are then one contiguous slice.
    Returns ``[..., M // block, 2]`` int32: ``(slot, first row)`` of the
    block's first run slot, or ``(-1, 0)`` where it has none. Padding
    rows name row 0, so a block of two rows or more that holds one is no
    run."""
    xp = np if isinstance(nbr, np.ndarray) else jnp
    *lead, m, s = nbr.shape
    t = nbr.reshape(*lead, m // block, block, s)
    first = t[..., 0, :]                                    # [..., J, S]
    step = xp.arange(block, dtype=t.dtype)[:, None]
    run = (xp.all(t == first[..., None, :] + step, axis=-2)
           & (first >= 0) & (first <= n_rows - block))
    lane = xp.arange(s, dtype=xp.int32)
    slot = xp.min(xp.where(run, lane, s), axis=-1)
    start = xp.sum(xp.where(lane == slot[..., None], first, 0), axis=-1)
    return xp.stack([xp.where(slot < s, slot, -1), start],
                    axis=-1).astype(xp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockNeighbors:
    """An ``[..., Nd, S]`` neighbour table in the form a block gather's
    launch reads it (``block_neighbors``), passed where the raw table
    would go.

    ``nbr`` is ``[..., C, M * S]``: each chunk of ``chunk_rows(Nd, S)``
    destination rows, padded to ``M = chunk_padded(...)`` rows, flat as
    the launch prefetches it. ``runs`` maps each block size it was built
    for to that block size's ``block_runs`` table, flat per chunk (``[...,
    C, 2 M / block]``), over a table of ``n_rows`` rows. ``shape`` is the
    raw table's, and ``t[c]`` indexes every array's leading axes, as it
    would the raw table's: ``t[c]`` is cluster ``c``'s sample."""
    nbr: jax.Array
    runs: dict
    nd: int = dataclasses.field(metadata=dict(static=True))
    slots: int = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))

    @property
    def shape(self) -> tuple:
        return (*self.nbr.shape[:-2], self.nd, self.slots)

    def __getitem__(self, i) -> "BlockNeighbors":
        return jax.tree.map(lambda a: a[i], self)

    def launch(self, n_rows: int, block: int) -> tuple:
        """``(nbr, runs)`` of a launch over an ``n_rows``-row table that
        gathers ``block`` rows a grid step."""
        if n_rows != self.n_rows or block not in self.runs:
            raise ValueError(
                f"neighbours prepared for a {self.n_rows}-row table and "
                f"blocks {sorted(self.runs)}; the launch reads a "
                f"{n_rows}-row table in blocks of {block}")
        return self.nbr, self.runs[block]


def block_neighbors(neighbors, n_rows: int, blocks) -> BlockNeighbors:
    """The ``[..., Nd, S]`` ``neighbors`` of an ``n_rows``-row table as the
    block gather reads them, with a run table for each of ``blocks``
    (``BlockNeighbors``). Works on numpy and jnp tables alike."""
    *lead, nd, s = neighbors.shape
    rows = chunk_rows(nd, s)
    t = chunk_tables(neighbors, rows, chunk_padded(rows))
    flat = lambda a: a.reshape(*a.shape[:-2], -1)   # noqa: E731
    return BlockNeighbors(flat(t), {b: flat(block_runs(t, b, n_rows))
                                    for b in blocks}, nd, s, n_rows)


def gather_dmas(neighbors: np.ndarray, n_rows: int, width: int) -> tuple:
    """``(row DMAs, block DMAs)`` that one ``fused_ideal_layer`` launch
    issues over the ``[Nd, S]`` sample of an ``n_rows`` x ``width`` table:
    the same chunks, blocks and runs the launch computes, padding rows
    included. A run slot takes one block DMA in place of ``block_rows``
    row DMAs."""
    nd, s = neighbors.shape
    rows = chunk_rows(nd, s)
    block = block_rows(rows, s, width)
    nbr = chunk_tables(np.asarray(neighbors), rows, chunk_padded(rows))
    runs = int((block_runs(nbr, block, n_rows)[..., 0] >= 0).sum())
    return nbr.size - runs * block, runs


def map_row_chunks(call, neighbors: jax.Array, weights: jax.Array):
    """Run ``call(nbr_flat, wts_flat) -> [rows, ...]`` over chunks of the
    destination rows and return the stacked ``[Nd, ...]`` result.

    ``neighbors``/``weights`` are ``[Nd, S]``; each call receives its
    chunk's tables flattened row-major (entry ``i * S + s``). A short last
    chunk is padded with weight-0 rows whose outputs are dropped.
    """
    nd, s = neighbors.shape
    rows = chunk_rows(nd, s)
    nbr, wts = (chunk_tables(t, rows, rows).reshape(-1, rows * s)
                for t in (neighbors, weights.astype(jnp.float32)))
    out = map_chunks(call, nbr, wts)
    return out.reshape(-1, *out.shape[2:])[:nd]
