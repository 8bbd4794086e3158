"""Pallas TPU kernel for the IMA-GNN aggregation core (node-stationary gather-reduce).

TPU adaptation: the paper activates crossbar rows per incoming edge and sums
analog currents; on TPU the same node-stationary dataflow becomes a
scalar-prefetch gather. Neighbor indices are scalar-prefetched so the
BlockSpec ``index_map`` can steer each HBM->VMEM feature-row fetch directly —
the gather never materializes an [Nd, S, F] tensor. The destination node's
accumulator lives in VMEM (the output block is revisited across the S grid
axis), mirroring the paper's destination-stationary accumulation.

Grid per call: (node, F // bf, S). Feature rows are fetched in (1, bf)
blocks with bf a multiple of 128 (VPU lane aligned), out of the ``[N, 1, F]``
row view; destination rows are chunked across calls so the prefetched
tables fit SMEM at any node count (``kernels._gather``, DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._gather import (gather_metadata, map_row_chunks,
                                  row_block, rows_view)
from repro.kernels._interpret import resolve_interpret


def _kernel(nbr_ref, wts_ref, x_ref, out_ref, *, n_s: int):
    i = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = wts_ref[i * n_s + s]                # scalar edge weight (SMEM)
    out_ref[...] += w * x_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bf", "interpret"))
def csr_aggregate(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
                  bf: int = 128,
                  interpret: bool | None = None) -> jax.Array:
    """Weighted neighbor-feature aggregation via scalar-prefetch gather.

    x: [N, F] float, F % bf == 0; neighbors: [Nd, S] int32; weights: [Nd, S].
    Returns z: [Nd, F] float32. Matches ``ref.csr_aggregate_ref`` exactly.
    """
    interpret = resolve_interpret(interpret)
    n, f = x.shape
    nd, s = neighbors.shape
    assert f % bf == 0, (f, bf)
    x_rows = rows_view(x)

    def call(nbr, wts):
        rows = nbr.shape[0] // s
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # neighbors, weights (flat)
            grid=(rows, f // bf, s),
            in_specs=[
                # one neighbor feature row block, steered by the prefetched
                # index
                pl.BlockSpec(row_block(bf), lambda i, j, ss, nbr, wts:
                             (nbr[i * s + ss], 0, j)),
            ],
            out_specs=pl.BlockSpec(row_block(bf),
                                   lambda i, j, ss, nbr, wts: (i, 0, j)),
        )
        return pl.pallas_call(
            functools.partial(_kernel, n_s=s),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, 1, f), jnp.float32),
            interpret=interpret, name="csr_aggregate",
            metadata=gather_metadata("csr_aggregate", rows, s, f, f,
                                     f_blocks=f // bf),
        )(nbr, wts, x_rows)

    return map_row_chunks(call, neighbors, weights).reshape(nd, f)
