"""Measurement harness for tuning survivors.

Synthetic inputs are a pure function of (geometry, seed) so the work a
candidate is timed on is identical across candidates and across runs; the
timer compiles first (block_until_ready) and reports the *minimum* of
``iters`` timed calls — the standard noise-robust estimator for a
deterministic computation (mean/median absorb scheduler noise, min
doesn't).

Kernel imports happen inside the runner builders: the kernels' ops layers
import ``tuning.registry`` for their default-config resolution, so a
module-level import here would be circular.
"""
from __future__ import annotations

import time

import numpy as np

from .space import AggregateConfig, AggregateGeometry, CamConfig, \
    CamGeometry, CrossbarConfig, CrossbarGeometry, FusedConfig, \
    FusedGeometry


def time_callable(fn, iters: int = 3, warmup: int = 1) -> float:
    """Min wall-clock seconds of ``fn()`` over ``iters`` timed calls."""
    import jax
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn())          # compile + cache warm
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def crossbar_runner(geom: CrossbarGeometry, config: CrossbarConfig,
                    seed: int = 0, interpret: bool | None = None):
    """() -> y for one quantized crossbar MVM launch at ``config``."""
    import jax.numpy as jnp
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    from repro.kernels.crossbar_mvm.crossbar_mvm import \
        crossbar_matmul_quantized
    from repro.mapper.tiling import padded_grid

    cfg = CrossbarNumerics(in_bits=geom.in_bits,
                           rows_per_xbar=geom.rows_per_xbar)
    rng = np.random.default_rng(seed)
    grid = padded_grid(geom.m, geom.k, geom.n, geom.rows_per_xbar,
                       bm=config.bm, bn=config.bn)
    xq = jnp.asarray(rng.integers(
        0, 2 ** geom.in_bits, size=(grid.m_pad, grid.k_pad)).astype(
            np.int32))
    wq = jnp.asarray(rng.integers(
        -7, 8, size=(grid.k_pad, grid.n_pad)).astype(np.float32))

    def run():
        return crossbar_matmul_quantized(xq, wq, cfg, bm=config.bm,
                                         bn=config.bn, depth=config.depth,
                                         interpret=interpret)
    return run


def fused_runner(geom: FusedGeometry, config: FusedConfig, seed: int = 0,
                 interpret: bool | None = None):
    """() -> h for one fused GNN-layer launch at ``config``."""
    import jax.numpy as jnp
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    from repro.kernels.fused_layer import fused_gnn_layer

    cfg = (CrossbarNumerics(ideal=True) if geom.ideal
           else CrossbarNumerics(rows_per_xbar=geom.rows_per_xbar))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(geom.n, geom.f_in)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(
        0, geom.n, size=(geom.nd, geom.sample)).astype(np.int32))
    wts = jnp.asarray(np.abs(rng.normal(
        size=(geom.nd, geom.sample))).astype(np.float32))
    w = jnp.asarray(rng.normal(
        size=(geom.f_in, geom.f_out)).astype(np.float32) * 0.05)
    b = jnp.zeros((geom.f_out,), jnp.float32)

    def run():
        return fused_gnn_layer(x, nbr, wts, w, b, cfg, relu=True,
                               bf=config.bf, interpret=interpret)
    return run


def aggregate_runner(geom: AggregateGeometry, config: AggregateConfig,
                     seed: int = 0, interpret: bool | None = None):
    """() -> z for one standalone aggregation launch at ``config``."""
    import jax.numpy as jnp
    from repro.kernels.csr_aggregate import aggregate

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(geom.n, geom.f)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(
        0, geom.n, size=(geom.nd, geom.sample)).astype(np.int32))
    wts = jnp.asarray(np.abs(rng.normal(
        size=(geom.nd, geom.sample))).astype(np.float32))

    def run():
        return aggregate(x, nbr, wts, backend="pallas", bf=config.bf,
                         interpret=interpret)
    return run


def cam_runner(geom: CamGeometry, config: CamConfig, seed: int = 0,
               interpret: bool | None = None):
    """() -> (match, counts) for one CAM search launch at ``config``."""
    import jax.numpy as jnp
    from repro.kernels.cam_match import search

    rng = np.random.default_rng(seed)
    ci = jnp.asarray(rng.integers(0, max(geom.e, 1),
                                  size=geom.e).astype(np.int32))
    qs = jnp.asarray(rng.integers(0, max(geom.e, 1),
                                  size=geom.q).astype(np.int32))

    def run():
        return search(ci, qs, backend="pallas", bq=config.bq, be=config.be,
                      interpret=interpret)
    return run


def make_runner(geom, config, seed: int = 0, interpret: bool | None = None):
    if geom.kernel == "fused_layer":
        return fused_runner(geom, config, seed, interpret)
    if geom.kernel == "csr_aggregate":
        return aggregate_runner(geom, config, seed, interpret)
    if geom.kernel == "cam_match":
        return cam_runner(geom, config, seed, interpret)
    return crossbar_runner(geom, config, seed, interpret)


def measure(geom, config, seed: int = 0, iters: int = 3, warmup: int = 1,
            interpret: bool | None = None) -> float:
    """Default measurement hook: build the runner, time it, seconds."""
    return time_callable(make_runner(geom, config, seed, interpret),
                        iters=iters, warmup=warmup)
