"""Jitted public wrappers for the fused GNN-layer kernel.

``fused_gnn_layer`` pads to block multiples, handles the bit-accurate path's
global DAC-scale dependency (the one piece of the composed pipeline that
cannot live inside a block-local kernel: the DAC scale is a full-tensor max
over Z), and dispatches to the right kernel:

  * ideal numerics     — one fused kernel launch; Z never touches HBM.
  * bit-accurate       — a scale pass (``fused_zmax``, writes [Nd, 2] scalars
    instead of the [Nd, F] Z block) followed by the fused quantized kernel.
    Both passes keep Z in VMEM; HBM traffic for Z drops from 4 full
    materializations (write + quantize-max read + pos/neg DAC reads) to
    2*Nd floats.

The package ends at one layer: ``core.gnn.layer_step`` calls
``fused_gnn_layer`` for the ``fused`` backend, and the layer loops of every
placement live above it (``core.gnn.forward``, ``distributed.halo``).

Operands that do not change between calls can be built once in the form
the ideal launch reads them: ``prepare_table`` pads a feature table to the
launch's lanes and views it by rows (``[N, 1, k_pad]``), and
``prepare_sample`` chunks the sample tables and finds their runs for the
given block sizes; ``ideal_launch`` gives both sizes by the launch's own
rule. ``fused_gnn_layer`` tells them from raw tables by their form (a
three-dimensional table, ``BlockNeighbors``, three-dimensional weights) and
reads them as they are; the bit-accurate path takes raw tables only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._gather import (BlockNeighbors, block_neighbors,
                                  block_rows, chunk_padded, chunk_rows,
                                  chunk_tables, gather_dmas, rows_view)
from repro.kernels.crossbar_mvm.ref import (CrossbarNumerics,
                                            apply_conductance_noise,
                                            quantize_weights)
from repro.mapper.tiling import padded_grid
from repro.tuning import registry as _tuning_registry
from repro.tuning.space import FusedGeometry

from .fused_layer import fused_ideal_layer, fused_quant_layer, fused_zmax


def _pad_cols(a: jax.Array, to: int) -> jax.Array:
    pad = to - a.shape[-1]
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def _pad_rows(a: jax.Array, to: int) -> jax.Array:
    pad = to - a.shape[0]
    return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)) if pad else a


def _resolve_bf(n, f_in, nbr_shape, f_out, cfg, bf, tuned):
    """Lane block for this launch: explicit ``bf`` wins, else the tuned
    bundle, else the process tuning registry, else the 128 default.
    Resolution is eager (outside the jitted impl); callers inside an outer
    jit thread ``tuned`` so the decision is part of the jit key."""
    if bf is not None:
        return bf
    geom = FusedGeometry(nd=nbr_shape[-2], n=n, f_in=f_in, f_out=f_out,
                         sample=nbr_shape[-1], ideal=cfg.ideal,
                         rows_per_xbar=cfg.rows_per_xbar)
    c = ((tuned.lookup(geom.key()) if tuned is not None else None)
         or _tuning_registry.lookup(geom.key()))
    return c.bf if c else 128


def ideal_launch(n: int, nbr_shape: tuple, f_in: int, f_out: int,
                 tuned=None) -> tuple:
    """``(k_pad, block)`` of the ``fused_ideal_layer`` launch that
    ``fused_gnn_layer`` makes with ideal numerics over an ``[n, f_in]``
    table and an ``[..., Nd, S]`` sample: the lanes it pads the table to,
    and the destination rows a grid step gathers."""
    cfg = CrossbarNumerics(ideal=True)
    bf = _resolve_bf(n, f_in, nbr_shape, f_out, cfg, None, tuned)
    k_pad = padded_grid(n, f_in, f_out, bf, bm=1, bn=bf).k_pad
    nd, s = nbr_shape[-2:]
    return k_pad, block_rows(chunk_rows(nd, s), s, k_pad)


def ideal_layer_dmas(neighbors: np.ndarray, n: int, f_in: int, f_out: int,
                     tuned=None) -> tuple:
    """``(row DMAs, block DMAs)`` of the ``fused_ideal_layer`` launch that
    ``fused_gnn_layer`` makes with ideal numerics over an ``[n, f_in]``
    table and the ``[Nd, S]`` sample ``neighbors``: the lane padding the
    launch gets (``ideal_launch``), then ``_gather.gather_dmas``."""
    k_pad, _ = ideal_launch(n, neighbors.shape, f_in, f_out, tuned)
    return gather_dmas(neighbors, n, k_pad)


def prepare_table(x: jax.Array, k_pad: int) -> jax.Array:
    """``[..., N, F] -> [..., N, 1, k_pad]``: a feature table zero-padded to
    a launch's ``k_pad`` lanes (``ideal_launch``) and viewed by rows, as
    the ideal launch reads it. ``fused_gnn_layer`` takes it in place of the
    ``[N, F]`` table, bit for bit alike."""
    return rows_view(_pad_cols(x, k_pad))


def prepare_sample(neighbors, weights, n_rows: int, blocks) -> tuple:
    """``(BlockNeighbors, [..., C, M, L] weights)``: the ``[..., Nd, S]``
    sample of an ``n_rows``-row table as the ideal launches read it, with
    a run table for each block size of ``blocks`` (``ideal_launch``), and
    the weights chunked and zero-padded to ``L``, ``S`` rounded up to 128
    lanes: the layout the launch's weight block has in memory, which a
    device keeps for this shape as it is. ``fused_gnn_layer`` takes the
    pair in place of the raw tables, bit for bit alike."""
    nd, s = neighbors.shape[-2:]
    rows = chunk_rows(nd, s)
    wts = chunk_tables(weights.astype(jnp.float32), rows, chunk_padded(rows))
    return (block_neighbors(neighbors, n_rows, blocks),
            _pad_cols(wts, -(-s // 128) * 128))


def fused_gnn_layer(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
                    w: jax.Array, b: jax.Array,
                    cfg: CrossbarNumerics = CrossbarNumerics(ideal=True),
                    *, relu: bool = False, bf: int | None = None,
                    tuned=None, interpret: bool | None = None,
                    w_noise: jax.Array | None = None) -> jax.Array:
    """act((A_hat @ X) @ W + b) with Z resident in VMEM throughout.

    x: [N, F]; neighbors: [Nd, S] int32; weights: [Nd, S]; w: [F, H]; b: [H].
    With ideal numerics ``x`` may instead be ``prepare_table``'s row view
    and ``neighbors``/``weights`` ``prepare_sample``'s pair, each built for
    this launch (``ideal_launch``); they are read as they are.
    Matches ``ref.fused_layer_ref`` (the composed csr_aggregate +
    crossbar_mvm path) for both ideal and bit-accurate ``cfg``. ``bf``
    left at ``None`` resolves through the tuned bundle / registry
    (``repro.tuning``); padding is zeros either way, so outputs are
    bit-identical across bf choices. ``w_noise``: optional [F, H]
    conductance-code perturbation on the programmed weights
    (``devices.variation``) — ignored on the ideal path.
    """
    bf = _resolve_bf(x.shape[0], w.shape[0], neighbors.shape, w.shape[1],
                     cfg, bf, tuned)
    return _fused_gnn_layer(x, neighbors, weights, w, b, cfg, relu=relu,
                            bf=bf, interpret=interpret, w_noise=w_noise)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "relu", "bf", "interpret"))
def _fused_gnn_layer(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
                     w: jax.Array, b: jax.Array,
                     cfg: CrossbarNumerics,
                     *, relu: bool, bf: int,
                     interpret: bool | None,
                     w_noise: jax.Array | None = None) -> jax.Array:
    n, (f, h) = x.shape[0], w.shape
    # the mapper emits the padded tile grid for either numerics path: K
    # tiled into physical rows_per_xbar crossbars (bit-accurate) or into
    # bf-lane MXU blocks (ideal), H lane-aligned to bf — arbitrary F/H map.
    grid = padded_grid(n, f, h, bf if cfg.ideal else cfg.rows_per_xbar,
                       bm=1, bn=bf)
    view = x.ndim == 3                  # prepare_table's row view
    want = grid.k_pad if view else f
    if x.shape[-1] != want:
        raise ValueError(f"table {x.shape} against weights {w.shape}: the "
                         f"launch reads {want} lanes")
    if cfg.ideal:
        xp = x if view else _pad_cols(x, grid.k_pad)
        wp = _pad_cols(_pad_rows(w, grid.k_pad), grid.n_pad)
        bp = _pad_cols(b[None], grid.n_pad)[0]
        out = fused_ideal_layer(xp, neighbors, weights, wp, bp,
                                relu=relu, interpret=interpret)
        return out[:, :h]
    if view or isinstance(neighbors, BlockNeighbors) or weights.ndim == 3:
        raise ValueError("the bit-accurate layer reads raw [N, F] and "
                         "[Nd, S] tables, not prepared ones")
    xp = _pad_cols(x, grid.k_pad)
    zmax = fused_zmax(xp, neighbors, weights, interpret=interpret)
    # global DAC scales of max(Z,0) / max(-Z,0) — identical to
    # quantize_inputs() on the materialized Z of the composed path
    scale_pos = jnp.maximum(jnp.max(zmax[:, 0]), 1e-8) / cfg.in_levels
    scale_neg = jnp.maximum(jnp.max(zmax[:, 1]), 1e-8) / cfg.in_levels
    wq, w_scale = quantize_weights(w, cfg)
    wq = apply_conductance_noise(wq, w_noise, cfg)
    wqp = _pad_cols(_pad_rows(wq, grid.k_pad), grid.n_pad)
    bp = _pad_cols(b[None], grid.n_pad)[0]
    scales = jnp.stack([scale_pos, scale_neg, w_scale])
    out = fused_quant_layer(xp, neighbors, weights, wqp, bp, scales, cfg,
                            relu=relu, interpret=interpret)
    return out[:, :h]
