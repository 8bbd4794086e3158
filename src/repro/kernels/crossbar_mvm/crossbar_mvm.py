"""Pallas TPU kernel for the bit-serial RRAM crossbar MVM (IMA-GNN Fig. 2(b)).

TPU adaptation of the paper's analog MVM crossbar: one grid step owns one
(M-tile, N-tile, K-tile) block where the K tile is exactly one physical
crossbar's ``rows_per_xbar`` (so the ADC is applied at the same point in the
reduction tree as the hardware applies it). Bit-planes of the DAC-quantized
input are streamed through the MXU; ADC clipping/quantization and the
shift-&-add recombination run on the VPU; cross-crossbar (K-tile) accumulation
is digital via output-block revisiting.

Block shapes are MXU/VPU aligned: (bm, bk) x (bk, bn) with bk = rows_per_xbar
(a multiple of 128 on real configs) and bn a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._interpret import resolve_interpret
from .ref import CrossbarNumerics


def _kernel(xq_ref, wq_ref, out_ref, *, in_bits: int, adc_bits: int,
            rows_per_xbar: int, w_levels: int, depth: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    r = rows_per_xbar
    full_scale = float(r * w_levels)
    lsb = full_scale / (2 ** adc_bits - 1)

    # ``depth`` physical crossbars per grid step (tuner pipeline-depth
    # knob): each owns one rows_per_xbar K-slice of the VMEM-resident
    # block, keeping the ADC at the same reduction-tree position — and the
    # digital cross-crossbar accumulation in the same order — as depth=1,
    # so outputs are bit-identical at any depth.
    for t in range(depth):
        xq = xq_ref[:, t * r:(t + 1) * r]   # [bm, r] int32 DAC codes
        wq = wq_ref[t * r:(t + 1) * r, :]   # [r, bn] f32 conductance codes
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for b in range(in_bits):            # bit-serial DAC cycles
            plane = ((xq >> b) & 1).astype(jnp.float32)
            partial = jnp.dot(plane, wq, preferred_element_type=jnp.float32)
            # ADC: clip to full scale, uniform quantize (mid-tread)
            partial = jnp.round(
                jnp.clip(partial, -full_scale, full_scale) / lsb) * lsb
            acc = acc + partial * (2.0 ** b)  # shift & add
        out_ref[...] += acc


@functools.partial(jax.jit,
                   static_argnames=("cfg", "bm", "bn", "depth", "interpret"))
def crossbar_matmul_quantized(xq: jax.Array, wq: jax.Array,
                              cfg: CrossbarNumerics,
                              bm: int = 128, bn: int = 128, depth: int = 1,
                              interpret: bool | None = None) -> jax.Array:
    """Bit-serial crossbar matmul on pre-quantized codes.

    xq: [M, K] int32 input DAC codes (values < 2**in_bits; the TPU
        compiler has no uint32 -> float32 cast)
    wq: [K, N] float32 signed conductance codes
    K must be a multiple of cfg.rows_per_xbar; M of bm; N of bn.
    ``depth`` (tuner knob) gives each grid step ``depth`` physical
    crossbars along K (``depth`` must divide K / rows_per_xbar); outputs
    are bit-identical at any depth.
    Returns the *integer-domain* accumulation [M, N] f32 (caller rescales).
    """
    interpret = resolve_interpret(interpret)
    m, k = xq.shape
    k2, n = wq.shape
    if xq.dtype != jnp.int32:
        raise ValueError(f"DAC codes must be int32, got {xq.dtype}")
    if k != k2:
        raise ValueError(f"contraction mismatch: xq K={k} vs wq K={k2}")
    for dim, size, mult in (("M", m, bm), ("K", k, cfg.rows_per_xbar),
                            ("N", n, bn)):
        if size % mult:
            raise ValueError(
                f"crossbar_matmul_quantized needs {dim} divisible by "
                f"{mult} (one {'physical crossbar' if dim == 'K' else 'MXU block'}"
                f" per grid step), got {dim}={size}. Pad to the grid from "
                f"repro.mapper.tiling.padded_grid(M, K, N, rows_per_xbar, "
                f"bm, bn) — the ops-layer crossbar_matmul does this for "
                f"arbitrary shapes.")
    if depth < 1 or (k // cfg.rows_per_xbar) % depth:
        raise ValueError(
            f"pipeline depth {depth} must divide the physical crossbar "
            f"count K/rows_per_xbar = {k // cfg.rows_per_xbar} "
            f"(repro.tuning only proposes legal depths)")
    bk = depth * cfg.rows_per_xbar
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(
            _kernel, in_bits=cfg.in_bits, adc_bits=cfg.adc_bits,
            rows_per_xbar=cfg.rows_per_xbar, w_levels=cfg.w_levels,
            depth=depth),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret, name="crossbar_matmul_quantized",
        metadata={"kernel": "crossbar_matmul_quantized",
                  "m_blocks": str(grid[0]), "n_blocks": str(grid[1]),
                  "k_blocks": str(grid[2])},
    )(xq, wq)
