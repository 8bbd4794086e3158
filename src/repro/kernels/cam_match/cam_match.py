"""Pallas TPU kernel for the traversal core's search CAM (IMA-GNN Fig. 2(c)).

TPU adaptation: the TCAM's one-shot analog XNOR match across all rows becomes
a blocked vectorized equality compare — each grid step matches a (bq,) query
block against a (be,) edge block held in VMEM (8x128 VPU lanes replace the
match lines; the MLSA read-out becomes an int8 bitmap + per-block popcount).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._interpret import resolve_interpret


def _kernel(ci_ref, q_ref, match_ref, count_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    ci = ci_ref[...]                       # [1, be]
    q = q_ref[...]                         # [bq, 1]
    m = (ci == q)                          # [bq, be] broadcast XNOR match
    match_ref[...] = m.astype(jnp.int8)
    count_ref[...] += m.sum(axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bq", "be", "interpret"))
def cam_search(ci: jax.Array, queries: jax.Array, bq: int = 8, be: int = 128,
               interpret: bool | None = None):
    """ci: [E] int32 (E % be == 0); queries: [Q] int32 (Q % bq == 0).

    Returns (match [Q, E] int8, counts [Q, 1] int32).
    """
    interpret = resolve_interpret(interpret)
    e, = ci.shape
    q, = queries.shape
    for dim, size, mult in (("E", e, be), ("Q", q, bq)):
        if size % mult:
            raise ValueError(
                f"cam_search needs {dim} divisible by "
                f"{'be' if dim == 'E' else 'bq'}={mult} (one "
                f"{'entry' if dim == 'E' else 'query'} block per grid "
                f"step), got {dim}={size}. Use "
                f"repro.kernels.cam_match.search — the ops layer pads "
                f"E/Q to the block multiples with non-matching sentinels "
                f"for arbitrary shapes.")
    grid = (q // bq, e // be)
    match, counts = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, be), lambda i, j: (0, j)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, be), lambda i, j: (i, j)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, e), jnp.int8),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
        ],
        interpret=interpret, name="cam_search",
        metadata={"kernel": "cam_search", "query_blocks": str(grid[0]),
                  "entry_blocks": str(grid[1])},
    )(ci.reshape(1, e), queries.reshape(q, 1))
    return match, counts
