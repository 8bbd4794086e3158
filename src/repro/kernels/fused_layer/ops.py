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

``fused_gnn_forward`` is the multi-layer driver (the full-graph network),
``fused_gnn_forward_batched`` maps it over a leading cluster/device axis —
the building block the decentralized/semi serving paths use per device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._gather import gather_dmas
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


def _resolve_bf(x_shape, nbr_shape, f_out, cfg, bf, tuned):
    """Lane block for this launch: explicit ``bf`` wins, else the tuned
    bundle, else the process tuning registry, else the 128 default.
    Resolution is eager (outside the jitted impl); callers inside an outer
    jit thread ``tuned`` so the decision is part of the jit key."""
    if bf is not None:
        return bf
    geom = FusedGeometry(nd=nbr_shape[0], n=x_shape[0], f_in=x_shape[1],
                         f_out=f_out, sample=nbr_shape[1], ideal=cfg.ideal,
                         rows_per_xbar=cfg.rows_per_xbar)
    c = ((tuned.lookup(geom.key()) if tuned is not None else None)
         or _tuning_registry.lookup(geom.key()))
    return c.bf if c else 128


def ideal_layer_dmas(neighbors: np.ndarray, n: int, f_in: int, f_out: int,
                     tuned=None) -> tuple:
    """``(row DMAs, block DMAs)`` of the ``fused_ideal_layer`` launch that
    ``fused_gnn_layer`` makes with ideal numerics over an ``[n, f_in]``
    table and the ``[Nd, S]`` sample ``neighbors``: the lane padding the
    launch gets, then ``_gather.gather_dmas``."""
    cfg = CrossbarNumerics(ideal=True)
    bf = _resolve_bf((n, f_in), neighbors.shape, f_out, cfg, None, tuned)
    k_pad = padded_grid(n, f_in, f_out, bf, bm=1, bn=bf).k_pad
    return gather_dmas(neighbors, n, k_pad)


def fused_gnn_layer(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
                    w: jax.Array, b: jax.Array,
                    cfg: CrossbarNumerics = CrossbarNumerics(ideal=True),
                    *, relu: bool = False, bf: int | None = None,
                    tuned=None, interpret: bool | None = None,
                    w_noise: jax.Array | None = None) -> jax.Array:
    """act((A_hat @ X) @ W + b) with Z resident in VMEM throughout.

    x: [N, F]; neighbors: [Nd, S] int32; weights: [Nd, S]; w: [F, H]; b: [H].
    Matches ``ref.fused_layer_ref`` (the composed csr_aggregate +
    crossbar_mvm path) for both ideal and bit-accurate ``cfg``. ``bf``
    left at ``None`` resolves through the tuned bundle / registry
    (``repro.tuning``); padding is zeros either way, so outputs are
    bit-identical across bf choices. ``w_noise``: optional [F, H]
    conductance-code perturbation on the programmed weights
    (``devices.variation``) — ignored on the ideal path.
    """
    bf = _resolve_bf(x.shape, neighbors.shape, w.shape[1], cfg, bf, tuned)
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
    n, f = x.shape
    f2, h = w.shape
    assert f == f2, (x.shape, w.shape)
    # the mapper emits the padded tile grid for either numerics path: K
    # tiled into physical rows_per_xbar crossbars (bit-accurate) or into
    # bf-lane MXU blocks (ideal), H lane-aligned to bf — arbitrary F/H map.
    grid = padded_grid(n, f, h, bf if cfg.ideal else cfg.rows_per_xbar,
                       bm=1, bn=bf)
    if cfg.ideal:
        xp = _pad_cols(x, grid.k_pad)
        wp = _pad_cols(_pad_rows(w, grid.k_pad), grid.n_pad)
        bp = _pad_cols(b[None], grid.n_pad)[0]
        out = fused_ideal_layer(xp, neighbors, weights, wp, bp,
                                relu=relu, interpret=interpret)
        return out[:, :h]
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


@functools.partial(jax.jit,
                   static_argnames=("cfg", "final_activation", "bf",
                                    "interpret"))
def fused_gnn_forward(params: list, x: jax.Array, neighbors: jax.Array,
                      weights: jax.Array,
                      cfg: CrossbarNumerics = CrossbarNumerics(ideal=True),
                      *, final_activation: bool = False, bf: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """Multi-layer fused driver: the full-graph GNN forward, one fused
    kernel launch per layer (plus the scale pass on the bit-accurate path).

    params: [{'w': [F_i, F_i+1], 'b': [F_i+1]}, ...]; x: [N, F_0];
    neighbors/weights: [N, S]. Semantics match ``repro.core.gnn.forward``.
    """
    h = x
    n_layers = len(params)
    for i, layer in enumerate(params):
        relu = i < n_layers - 1 or final_activation
        h = fused_gnn_layer(h, neighbors, weights, layer["w"], layer["b"],
                            cfg, relu=relu, bf=bf, interpret=interpret)
    return h


@functools.partial(jax.jit,
                   static_argnames=("cfg", "final_activation", "bf",
                                    "interpret"))
def fused_gnn_forward_batched(params: list, x: jax.Array,
                              neighbors: jax.Array, weights: jax.Array,
                              cfg: CrossbarNumerics = CrossbarNumerics(
                                  ideal=True),
                              *, final_activation: bool = False,
                              bf: int = 128,
                              interpret: bool | None = None) -> jax.Array:
    """Batched multi-layer driver over a leading cluster/device axis.

    x: [K, N, F]; neighbors/weights: [K, N, S]. Each cluster runs the fused
    multi-layer forward on its own subgraph (static unroll — K is the
    partition fan-out, small by construction). Returns [K, N, out_dim].
    """
    outs = [fused_gnn_forward(params, x[k], neighbors[k], weights[k], cfg,
                              final_activation=final_activation, bf=bf,
                              interpret=interpret)
            for k in range(x.shape[0])]
    return jnp.stack(outs)
