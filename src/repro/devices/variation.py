"""Monte-Carlo conductance-variation pass (DESIGN.md §13).

Programmed crossbar conductances are not exact: every technology's
``noise_sigma`` is the relative std of one stored level. This module
samples that noise, injects it into the bit-accurate ``crossbar_mvm``
numerics, and turns the trials into the per-technology accuracy bounds the
planner's accuracy evaluator consumes — mean/p99 relative output error of
one MVM and the end-to-end GNN logit flip rate on a concrete dataset.

Design constraints that shape the implementation:

  * **Byte-identical where the backends are.** The composed ``jnp`` and
    ``pallas`` backends share the oracle crossbar stage bit-for-bit, and
    noise draws are quantized to a ``1/NOISE_GRID`` conductance-level
    grid so perturbed codes stay exactly representable in f32
    (|sum| * NOISE_GRID < 2^24 at the stack's geometries) — the same
    seed therefore produces byte-identical outputs *and bounds* on both.
    The ``fused`` kernel is allclose-level vs the oracle by its existing
    contract (tests/test_kernels_fused_layer.py); under noise it stays
    exactly seed-deterministic (same seed → byte-identical rerun) and
    inside the same tolerance.
  * **Platform-determinism.** Draws come from numpy's seeded Philox-free
    ``default_rng`` (bit-reproducible everywhere) rather than device-side
    RNG, and the error statistics are reduced in float64 numpy, so a
    bound is a pure function of ``(technology, seed)`` — safe for the
    deterministic METRICS of ``benchmarks/tech_sweep.py``.
  * **Same physical device, same noise.** A signed MVM drives the same
    programmed arrays twice (pos/neg DAC passes); the noise tensor is
    sampled once per weight matrix and shared by both passes and, end to
    end, by every trial's full forward.

The per-trial MVMs run one jitted call per draw on every backend (one
trace — the noise tensor is a traced argument and shapes are constant);
they are deliberately *not* vmapped: batching re-fuses the matmuls and
splits the backends at the last bit.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from .bank import resolve_technology

# noise codes land on a 1/8 conductance-level grid: fine enough that the
# quantization is ~1% of one level's sigma, coarse enough that every f32
# partial sum stays exactly representable (see module docstring)
NOISE_GRID = 8

_Z99 = 2.326   # one-sided 99th-percentile z-score of a standard normal


def sample_conductance_noise(seed, shape, tech, cfg=None) -> np.ndarray:
    """One additive conductance-code noise draw, grid-quantized.

    ``seed`` may be an int or a sequence of ints (trial substreams derive
    as ``[seed, trial]`` — disjoint, reproducible). Returns float32
    ``shape``-d codes in units of conductance codes: multiples of
    ``1/NOISE_GRID``, std ``noise_sigma * w_levels``.
    """
    tech = resolve_technology(tech)
    if cfg is None:
        from repro.kernels.crossbar_mvm import CrossbarNumerics
        cfg = CrossbarNumerics()
    rng = np.random.default_rng(seed)
    eps = rng.standard_normal(shape)
    delta = tech.noise_sigma * cfg.w_levels * eps
    return (np.round(delta * NOISE_GRID) / NOISE_GRID).astype(np.float32)


def layer_noise(seed, params, tech, cfg) -> list:
    """Per-layer weight-noise tensors for one GNN parameter list (one draw
    per programmed array — shared by every pass that reads it)."""
    return [sample_conductance_noise([*np.atleast_1d(seed), i],
                                     layer["w"].shape, tech, cfg)
            for i, layer in enumerate(params)]


@dataclasses.dataclass(frozen=True)
class VariationBounds:
    """Accuracy bounds of one technology under conductance noise.

    ``mean_err`` / ``p99_err`` — relative output error (|noisy - clean| /
    max|clean|) over all elements and trials; ``ci95`` — 95% confidence
    half-width of ``mean_err`` over the per-trial means (what a
    different-seed rerun must land inside); ``flip_rate`` — fraction of
    nodes whose argmax logit flipped (end-to-end runs only).
    """
    technology: str
    trials: int
    seed: int
    mean_err: float
    p99_err: float
    ci95: float
    flip_rate: float | None = None

    def within_ci(self, other: "VariationBounds", k: float = 2.0) -> bool:
        """Same-population check: the two mean errors agree within ``k``x
        their combined confidence half-widths (different seeds of the same
        technology must pass; see tests/test_devices.py)."""
        return abs(self.mean_err - other.mean_err) <= (
            k * (self.ci95 + other.ci95) + 1e-12)


def modeled_p99_error(tech, k_rows: int, cfg=None) -> float:
    """Closed-form first-order p99 relative MVM output error.

    The per-source-line signal grows linearly with the active rows
    ``r = min(k, rows_per_xbar)`` while the conductance noise accumulates
    in quadrature, so the relative error of one crossbar tile is
    ``~ z99 * sigma * sqrt(2/r)``; digital accumulation over ``n_k`` K
    tiles averages another ``sqrt(n_k)`` away. Deliberately coarse — the
    cheap evaluator the planner prices every candidate with; the
    Monte-Carlo bounds (same ordering, measured constants) ground it in
    ``benchmarks/tech_sweep.py``.
    """
    tech = resolve_technology(tech)
    if tech.noise_sigma <= 0.0:
        return 0.0
    if cfg is None:
        from repro.kernels.crossbar_mvm import CrossbarNumerics
        cfg = CrossbarNumerics()
    r = max(1, min(int(k_rows), cfg.rows_per_xbar))
    n_k = max(1, math.ceil(int(k_rows) / cfg.rows_per_xbar))
    return _Z99 * tech.noise_sigma * math.sqrt(2.0 / r) / math.sqrt(n_k)


def _mvm(x, w, cfg, w_noise, backend: str, interpret):
    """One (optionally noisy) bit-accurate MVM on the requested backend."""
    from repro.kernels.crossbar_mvm import crossbar_matmul_signed_ref
    from repro.kernels.crossbar_mvm.ops import crossbar_matmul_signed
    if backend == "jnp":
        return crossbar_matmul_signed_ref(x, w, cfg, w_noise=w_noise)
    assert backend == "pallas", backend
    return crossbar_matmul_signed(x, w, cfg, interpret=interpret,
                                  w_noise=w_noise)


def _bounds_from_trials(tech, seed, clean: np.ndarray,
                        noisy: np.ndarray, flip_rate=None) -> VariationBounds:
    """Fold stacked per-trial outputs into a ``VariationBounds`` (float64
    numpy reductions — platform-deterministic)."""
    clean64 = np.asarray(clean, np.float64)
    noisy64 = np.asarray(noisy, np.float64)
    scale = max(float(np.abs(clean64).max()), 1e-30)
    err = np.abs(noisy64 - clean64[None]) / scale
    per_trial = err.reshape(err.shape[0], -1).mean(axis=1)
    trials = err.shape[0]
    ci95 = (1.96 * float(per_trial.std(ddof=1)) / math.sqrt(trials)
            if trials > 1 else 0.0)
    return VariationBounds(
        technology=resolve_technology(tech).name, trials=trials,
        seed=int(np.atleast_1d(seed)[0]),
        mean_err=float(err.mean()), p99_err=float(np.quantile(err, 0.99)),
        ci95=ci95, flip_rate=flip_rate)


def mvm_error_bounds(tech, cfg=None, m: int = 32, k: int = 216, n: int = 64,
                     trials: int = 8, seed: int = 0, backend: str = "jnp",
                     interpret=None) -> VariationBounds:
    """Monte-Carlo relative-error bounds of one noisy bit-accurate MVM.

    The input matrices are fixed (seed-independent) so every seed samples
    noise for the *same* workload — the ``within_ci`` contract: two seeds
    estimate one population mean and must agree within their combined
    confidence intervals. The ``trials`` noise draws are applied one
    jitted call each — the noise tensor is a traced argument, so every
    trial reuses one trace, and deliberately *not* vmapped: batching
    re-fuses the matmuls and splits the backends at the last bit, while
    per-trial calls keep every backend byte-identical (what
    ``tests/test_devices.py`` asserts).
    """
    import jax.numpy as jnp
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    tech = resolve_technology(tech)
    cfg = cfg or CrossbarNumerics()
    rng = np.random.default_rng(0x0DA7A)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.1).astype(np.float32))
    clean = np.asarray(_mvm(x, w, cfg, None, backend, interpret))
    noise = np.stack([sample_conductance_noise([seed, t], (k, n), tech, cfg)
                      for t in range(trials)])
    noisy = np.stack([np.asarray(_mvm(x, w, cfg, jnp.asarray(nz),
                                      backend, interpret))
                      for nz in noise])
    return _bounds_from_trials(tech, seed, clean, noisy)


def noisy_forward(params, x, neighbors, weights, cfg, noise: list,
                  interpret=None):
    """GNN forward with per-layer conductance noise on any backend.

    ``core.gnn.forward``'s layer loop with the noise tensors of
    ``layer_noise`` riding on each layer's programmed weights
    (``gnn.layer_step``'s ``w_noise``). ``cfg`` is a ``GNNConfig`` with
    bit-accurate numerics.
    """
    import jax.numpy as jnp
    from repro.core import gnn
    assert not cfg.numerics.ideal, \
        "conductance noise models the bit-accurate path only"
    n_layers = len(params)
    for i, layer in enumerate(params):
        x = gnn.layer_step(
            x, neighbors, weights, layer, cfg,
            i < n_layers - 1 or cfg.final_activation, interpret=interpret,
            w_noise=None if noise[i] is None else jnp.asarray(noise[i]))
    return x


def accuracy_bounds(tech, dataset: str = "taxi", scale: float = 0.02,
                    trials: int = 4, seed: int = 0, backend: str = "jnp",
                    hidden: int = 32, out_dim: int = 10, sample: int = 8,
                    cfg=None, interpret=None) -> VariationBounds:
    """End-to-end bounds: logit error + argmax flip rate on one dataset.

    Builds a downscaled ``dataset_like`` graph, runs the clean bit-accurate
    forward, then ``trials`` noisy forwards (fresh per-layer draws each),
    and reports relative logit error plus the flip rate — the quantity
    that decides whether a technology's noise breaks the bit-accurate
    serving contract.
    """
    import jax
    from repro.core import gnn
    from repro.core.graph import dataset_like
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    tech = resolve_technology(tech)
    g = dataset_like(dataset, scale=scale, seed=seed).gcn_normalize()
    numerics = cfg or CrossbarNumerics()
    gcfg = gnn.GNNConfig(in_dim=g.feature_len, hidden_dims=(hidden,),
                         out_dim=out_dim, sample=sample, numerics=numerics,
                         backend=backend)
    params = gnn.init_params(jax.random.key(seed), gcfg)
    import jax.numpy as jnp
    nb, wt = g.neighbor_sample(sample)
    xs = (jnp.asarray(g.features), jnp.asarray(nb), jnp.asarray(wt))
    clean = np.asarray(gnn.forward(params, *xs, gcfg))
    noisy = np.stack([np.asarray(noisy_forward(
        params, *xs, gcfg, layer_noise([seed, t], params, tech, numerics),
        interpret=interpret)) for t in range(trials)])
    flips = float((noisy.argmax(-1) != clean.argmax(-1)[None]).mean())
    return _bounds_from_trials(tech, seed, clean, noisy, flip_rate=flips)
