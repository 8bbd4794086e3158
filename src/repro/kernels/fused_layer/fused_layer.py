"""Fused GNN-layer Pallas kernels: gather-reduce + crossbar MVM in one pass.

The paper's per-layer dataflow (Fig. 1) is two back-to-back in-memory stages:
aggregation ``Z = A_hat @ X`` on the traversal/aggregation cores feeding
feature extraction ``H = act(Z @ W + b)`` on the MVM crossbar core — the
intermediate ``Z`` never leaves the accelerator. The composed TPU path
(``csr_aggregate`` then ``crossbar_mvm``) loses exactly that property: ``Z``
makes a full HBM round-trip between the two kernels. Here both stages share
one grid step, so the destination node's accumulator row is handed to the
MXU matmul while still resident in VMEM (DESIGN.md §5):

  grid (node i, sample s):
    s == 0     : z_acc[1, F]  = 0                  (VMEM scratch)
    every s    : z_acc       += w[i,s] * X[nbr[i,s]]   (scalar-prefetch gather)
    s == S - 1 : out[i]       = act(z_acc @ W + b)     (MXU, Z stays in VMEM)

Three kernels share the gather loop:

  * ``_fused_ideal_kernel``  — float32 feature extraction (ideal numerics).
  * ``_fused_zmax_kernel``   — emits only per-node (max(z,0), max(-z,0));
    the bit-accurate path needs the *global* DAC scale of Z before it can
    quantize, and this pass provides it without materializing Z in HBM
    (output is [Nd, 2] scalars, an F/2-fold traffic reduction vs writing Z).
  * ``_fused_quant_kernel``  — DAC-quantizes the VMEM-resident z row with the
    prefetched scales and runs the bit-serial crossbar MVM (per-K-tile ADC +
    shift-&-add, pos/neg DAC passes) exactly as ``crossbar_mvm`` does.

Weight matrices of GNN layers are small (F x H, both <= a few 1000), so W is
held fully resident in VMEM across the whole grid rather than K-tiled by
BlockSpec; K-tiling for the per-crossbar ADC happens *inside* the kernel on
the VMEM-resident block, which keeps the reduction-tree position of the ADC
identical to the standalone ``crossbar_mvm`` kernel.

Rows move through the ``[N, 1, F]`` row view, and destination rows are
chunked across calls so the flat prefetched tables fit SMEM at any node
count (``kernels._gather``, DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._gather import map_row_chunks, row_block, rows_view
from repro.kernels._interpret import resolve_interpret
from repro.kernels.crossbar_mvm.ref import CrossbarNumerics


def _fused_ideal_kernel(nbr_ref, wts_ref, x_ref, w_ref, b_ref, out_ref,
                        z_ref, *, n_s: int, relu: bool):
    i = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    w_edge = wts_ref[i * n_s + s]           # scalar edge weight (SMEM)
    z_ref[...] += w_edge * x_ref[...].astype(jnp.float32)

    @pl.when(s == n_s - 1)
    def _transform():
        h = jnp.dot(z_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32) + b_ref[...]
        out_ref[...] = jnp.maximum(h, 0.0) if relu else h


def _fused_zmax_kernel(nbr_ref, wts_ref, x_ref, out_ref, z_ref, *, n_s: int):
    i = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    w_edge = wts_ref[i * n_s + s]
    z_ref[...] += w_edge * x_ref[...].astype(jnp.float32)

    @pl.when(s == n_s - 1)
    def _reduce():
        z = z_ref[...]
        pos = jnp.max(jnp.maximum(z, 0.0), axis=1, keepdims=True)
        neg = jnp.max(jnp.maximum(-z, 0.0), axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(lane == 0, pos, neg)


def _bit_serial_mvm(codes, wq_ref, cfg: CrossbarNumerics, n_k: int):
    """Bit-serial crossbar MVM of one [1, n_k * r] code row against the VMEM-
    resident conductance matrix, ADC per (bit-plane, K-tile) partial sum and
    digital shift-&-add — the same reduction tree as ``crossbar_mvm``."""
    r = cfg.rows_per_xbar
    full_scale = float(r * cfg.w_levels)
    lsb = full_scale / (2 ** cfg.adc_bits - 1)
    acc = jnp.zeros((1, wq_ref.shape[1]), jnp.float32)
    for t in range(n_k):                    # physical crossbars along K
        wq_t = wq_ref[t * r:(t + 1) * r, :]
        codes_t = codes[:, t * r:(t + 1) * r]
        for b in range(cfg.in_bits):        # bit-serial DAC cycles
            plane = ((codes_t >> b) & 1).astype(jnp.float32)
            partial = jnp.dot(plane, wq_t,
                              preferred_element_type=jnp.float32)
            partial = jnp.round(
                jnp.clip(partial, -full_scale, full_scale) / lsb) * lsb
            acc = acc + partial * (2.0 ** b)
    return acc


def _fused_quant_kernel(nbr_ref, wts_ref, scales_ref, x_ref, wq_ref, b_ref,
                        out_ref, z_ref, *, cfg: CrossbarNumerics, n_s: int,
                        n_k: int, relu: bool):
    i = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    w_edge = wts_ref[i * n_s + s]
    z_ref[...] += w_edge * x_ref[...].astype(jnp.float32)

    @pl.when(s == n_s - 1)
    def _transform():
        z = z_ref[...]
        # signed activations: two DAC passes (pos / neg), digital recombine
        scale_pos = scales_ref[0]           # DAC scale of max(Z, 0)
        scale_neg = scales_ref[1]           # DAC scale of max(-Z, 0)
        w_scale = scales_ref[2]             # conductance de-quantization
        acc = jnp.zeros((1, out_ref.shape[1]), jnp.float32)
        for sign, scale in ((1.0, scale_pos), (-1.0, scale_neg)):
            part = jnp.maximum(sign * z, 0.0)
            codes = jnp.clip(jnp.round(part / scale),
                             0, cfg.in_levels).astype(jnp.int32)
            acc += sign * scale * _bit_serial_mvm(codes, wq_ref, cfg, n_k)
        h = acc * w_scale + b_ref[...]
        out_ref[...] = jnp.maximum(h, 0.0) if relu else h


def _gather_spec(f: int, n_s: int):
    # one neighbor feature row, steered by the prefetched flat index table
    return pl.BlockSpec(row_block(f), lambda i, s, nbr, *_:
                        (nbr[i * n_s + s], 0, 0))


def _row_out_spec(h: int):
    return pl.BlockSpec(row_block(h), lambda i, s, *_: (i, 0, 0))


@functools.partial(jax.jit,
                   static_argnames=("relu", "interpret"))
def fused_ideal_layer(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
                      w: jax.Array, b: jax.Array, *, relu: bool = False,
                      interpret: bool | None = None) -> jax.Array:
    """act((A_hat @ X) @ W + b) in one kernel, ideal float numerics.

    x: [N, F]; neighbors/weights: [Nd, S]; w: [F, H]; b: [H].
    Returns [Nd, H] float32. Z never touches HBM.
    """
    interpret = resolve_interpret(interpret)
    n, f = x.shape
    nd, n_s = neighbors.shape
    f2, h = w.shape
    assert f == f2, (x.shape, w.shape)
    x_rows = rows_view(x)
    w = w.astype(jnp.float32)
    b = b.astype(jnp.float32).reshape(1, h)

    def call(nbr, wts):
        rows = nbr.shape[0] // n_s
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # neighbors, weights (flat)
            grid=(rows, n_s),
            in_specs=[
                _gather_spec(f, n_s),
                pl.BlockSpec((f, h), lambda i, s, *_: (0, 0)),  # W resident
                pl.BlockSpec((1, h), lambda i, s, *_: (0, 0)),  # bias
            ],
            out_specs=_row_out_spec(h),
            scratch_shapes=[pltpu.VMEM((1, f), jnp.float32)],   # z row
        )
        return pl.pallas_call(
            functools.partial(_fused_ideal_kernel, n_s=n_s, relu=relu),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, 1, h), jnp.float32),
            interpret=interpret,
        )(nbr, wts, x_rows, w, b)

    return map_row_chunks(call, neighbors, weights).reshape(nd, h)


@functools.partial(jax.jit, static_argnames="interpret")
def fused_zmax(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
               *, interpret: bool | None = None) -> jax.Array:
    """Per-node (max(z, 0), max(-z, 0)) of Z = A_hat @ X, Z kept in VMEM.

    Returns [Nd, 2] float32 — the scale pass of the bit-accurate fused layer
    (HBM write volume Nd*2 instead of Nd*F).
    """
    interpret = resolve_interpret(interpret)
    n, f = x.shape
    nd, n_s = neighbors.shape
    x_rows = rows_view(x)

    def call(nbr, wts):
        rows = nbr.shape[0] // n_s
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, n_s),
            in_specs=[_gather_spec(f, n_s)],
            out_specs=_row_out_spec(2),
            scratch_shapes=[pltpu.VMEM((1, f), jnp.float32)],
        )
        return pl.pallas_call(
            functools.partial(_fused_zmax_kernel, n_s=n_s),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, 1, 2), jnp.float32),
            interpret=interpret,
        )(nbr, wts, x_rows)

    return map_row_chunks(call, neighbors, weights).reshape(nd, 2)


@functools.partial(jax.jit, static_argnames=("cfg", "relu", "interpret"))
def fused_quant_layer(x: jax.Array, neighbors: jax.Array, weights: jax.Array,
                      wq: jax.Array, b: jax.Array, scales: jax.Array,
                      cfg: CrossbarNumerics, *, relu: bool = False,
                      interpret: bool | None = None) -> jax.Array:
    """Bit-accurate fused layer on pre-quantized conductances.

    x: [N, F] with F == n_k * cfg.rows_per_xbar (caller pads);
    wq: [F, H] signed conductance codes; b: [H] float bias;
    scales: [3] = (dac_scale_pos, dac_scale_neg, w_scale).
    Returns [Nd, H] float32 == act(crossbar_matmul_signed(Z, W) + b).
    """
    interpret = resolve_interpret(interpret)
    n, f = x.shape
    nd, n_s = neighbors.shape
    f2, h = wq.shape
    assert f == f2 and f % cfg.rows_per_xbar == 0, (x.shape, wq.shape, cfg)
    n_k = f // cfg.rows_per_xbar
    x_rows = rows_view(x)
    scales = scales.astype(jnp.float32)
    wq = wq.astype(jnp.float32)
    b = b.astype(jnp.float32).reshape(1, h)

    def call(nbr, wts):
        rows = nbr.shape[0] // n_s
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,          # neighbors, weights, scales
            grid=(rows, n_s),
            in_specs=[
                _gather_spec(f, n_s),
                pl.BlockSpec((f, h), lambda i, s, *_: (0, 0)),  # Wq resident
                pl.BlockSpec((1, h), lambda i, s, *_: (0, 0)),
            ],
            out_specs=_row_out_spec(h),
            scratch_shapes=[pltpu.VMEM((1, f), jnp.float32)],
        )
        return pl.pallas_call(
            functools.partial(_fused_quant_kernel, cfg=cfg, n_s=n_s,
                              n_k=n_k, relu=relu),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, 1, h), jnp.float32),
            interpret=interpret,
        )(nbr, wts, scales, x_rows, wq, b)

    return map_row_chunks(call, neighbors, weights).reshape(nd, h)
