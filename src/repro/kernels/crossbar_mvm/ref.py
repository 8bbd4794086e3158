"""Pure-jnp oracle for the RRAM crossbar MVM numerics (IMA-GNN Fig. 2(b)).

Models the analog dataflow of the paper's aggregation / feature-extraction
cores on a digital substrate, bit-exactly:

  1. DAC      — unsigned uniform quantization of the input activations to
                ``in_bits`` and bit-serial application (one bit-plane per cycle).
  2. crossbar — weights quantized symmetrically to ``w_bits`` and stored as a
                positive and a negative conductance column (1T1R pair); the
                analog dot-product along a source line is an integer matmul of
                a bit-plane against the conductance matrix.
  3. ADC      — each source-line partial sum is sampled by an ADC with
                ``adc_bits`` of resolution over the full-scale range of one
                ``rows_per_xbar`` tile; values are clipped + uniformly
                quantized (this is where analog error enters).
  4. Shift&Add — bit-plane partials are recombined digitally; crossbar row
                tiles (the K dimension split across physical crossbars) are
                accumulated digitally *after* the ADC, as in the paper.

The oracle is intentionally simple jnp so it can double as a reference for
both the Pallas kernel and the behavioural cost model in ``repro.core``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CrossbarNumerics:
    """Numeric configuration of one resistive MVM crossbar fabric."""

    in_bits: int = 8          # DAC resolution (input bit-serial width)
    w_bits: int = 8           # conductance levels per device pair (signed)
    adc_bits: int = 8         # ADC resolution per source line read-out
    rows_per_xbar: int = 512  # physical rows — K-dim tile accumulated post-ADC
    ideal: bool = False       # True: skip quantization entirely (float matmul)

    @property
    def w_levels(self) -> int:
        return 2 ** (self.w_bits - 1) - 1

    @property
    def in_levels(self) -> int:
        return 2 ** self.in_bits - 1


def quantize_inputs(x: jax.Array, cfg: CrossbarNumerics):
    """DAC input quantization: unsigned uniform over [0, max|x|].

    Returns (codes int32 [.., K], scale f32 scalar). Negative inputs are
    clipped — the paper's cores operate post-ReLU; callers that need signed
    activations split sign digitally (see ``crossbar_matmul_signed``).
    """
    x = x.astype(jnp.float32)   # quantize in f32: fusion-order independent
    x_max = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)
    scale = x_max / cfg.in_levels
    codes = jnp.clip(jnp.round(x / scale), 0, cfg.in_levels).astype(jnp.int32)
    return codes, scale


def quantize_weights(w: jax.Array, cfg: CrossbarNumerics):
    """Symmetric weight quantization to signed conductance codes."""
    w = w.astype(jnp.float32)   # quantize in f32: fusion-order independent
    w_max = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8)
    scale = w_max / cfg.w_levels
    codes = jnp.clip(jnp.round(w / scale), -cfg.w_levels, cfg.w_levels)
    return codes.astype(jnp.float32), scale


def apply_conductance_noise(wq: jax.Array, w_noise, cfg: CrossbarNumerics):
    """Perturb programmed conductance codes by an additive noise tensor.

    ``w_noise`` is a ``[K, N]`` float32 draw in conductance-code units
    (``devices.variation.sample_conductance_noise`` — grid-quantized so
    every partial sum stays exactly representable in f32 and the three
    backends remain byte-identical). The result is clipped to the physical
    code range; ``None`` is the clean path, returned untouched. A signed
    MVM shares one draw across both DAC passes — same programmed arrays.
    """
    if w_noise is None:
        return wq
    return jnp.clip(wq + w_noise.astype(jnp.float32),
                    -cfg.w_levels, cfg.w_levels)


def _adc(partial: jax.Array, cfg: CrossbarNumerics) -> jax.Array:
    """ADC transfer function on one source-line partial sum (integer domain).

    Full-scale range = rows_per_xbar * w_levels (max conductance sum for a
    single active bit-plane). Uniform mid-tread quantization + clipping.
    """
    full_scale = cfg.rows_per_xbar * cfg.w_levels
    lsb = full_scale / (2 ** cfg.adc_bits - 1)
    return jnp.round(jnp.clip(partial, -full_scale, full_scale) / lsb) * lsb


@partial(jax.jit, static_argnames="cfg")
def crossbar_matmul_ref(x: jax.Array, w: jax.Array,
                        cfg: CrossbarNumerics = CrossbarNumerics(),
                        w_noise: jax.Array | None = None) -> jax.Array:
    """Behavioural crossbar MVM: y = x @ w through DAC/crossbar/ADC numerics.

    x: [M, K] float (expected >= 0; clipped otherwise), w: [K, N] float.
    ``w_noise``: optional [K, N] conductance-code perturbation
    (``apply_conductance_noise``) — the Monte-Carlo variation hook.
    Returns [M, N] float32.
    """
    if cfg.ideal:
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    xq, xs = quantize_inputs(x, cfg)
    wq, ws = quantize_weights(w, cfg)
    wq = apply_conductance_noise(wq, w_noise, cfg)

    r = cfg.rows_per_xbar
    n_tiles = -(-k // r)
    pad = n_tiles * r - k
    if pad:
        xq = jnp.pad(xq, ((0, 0), (0, pad)))
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    xq = xq.reshape(m, n_tiles, r)
    wq = wq.reshape(n_tiles, r, n)

    def one_tile(xq_t, wq_t):
        # bit-serial over input bits; ADC applied per bit-plane partial.
        acc = jnp.zeros((m, n), jnp.float32)
        for b in range(cfg.in_bits):
            plane = ((xq_t >> b) & 1).astype(jnp.float32)
            partial = jnp.dot(plane, wq_t, preferred_element_type=jnp.float32)
            acc = acc + _adc(partial, cfg) * (2.0 ** b)
        return acc

    acc = jnp.zeros((m, n), jnp.float32)
    for t in range(n_tiles):
        acc = acc + one_tile(xq[:, t, :], wq[t])   # digital cross-tile add
    return acc * (xs * ws)


def crossbar_matmul_signed_ref(x: jax.Array, w: jax.Array,
                               cfg: CrossbarNumerics = CrossbarNumerics(),
                               w_noise: jax.Array | None = None) -> jax.Array:
    """Signed-activation variant: x is split into positive/negative parts that
    are driven in two passes and recombined digitally (2 DAC passes); one
    ``w_noise`` draw is shared by both — same programmed arrays.

    Deliberately not jitted as a whole: called eagerly, the two passes are
    separate jitted calls and ``pos - neg`` a separate op, exactly as in
    ``ops.crossbar_matmul_signed``. One jit would let XLA's CPU backend
    contract the two rescales and the subtraction into a fused multiply-add,
    which differs from the Pallas path in the last bit."""
    if cfg.ideal:
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    pos = crossbar_matmul_ref(jnp.maximum(x, 0.0), w, cfg, w_noise)
    neg = crossbar_matmul_ref(jnp.maximum(-x, 0.0), w, cfg, w_noise)
    return pos - neg
