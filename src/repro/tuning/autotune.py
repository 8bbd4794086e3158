"""The tuning driver: enumerate -> roofline-prune -> measure -> cache.

``tune`` decides one (geometry, platform); ``tune_plan`` walks an
``ExecutionPlan``'s per-layer kernel geometries and returns the
``TunedKernels`` bundle the plan threads into its forwards. Winners are
cached (``TuneCache``) keyed by (geometry, platform) the way the mapper
caches mappings on the plan — a cache hit skips measurement entirely.

Determinism contract (tests/test_tuning.py): with a deterministic
``measure_fn``, the winner, the cache record, and the serialized cache
bytes are pure functions of (geometry, backend platform, seed). With the
real timer the *candidate set* is still deterministic (pure roofline
arithmetic); only the measured ranking is machine-dependent — which is
why benches quarantine the winner under ``timing`` keys.
"""
from __future__ import annotations

from repro.analysis.roofline import HW, V5E, peaks_for

from . import registry
from .cache import TuneCache
from .measure import measure as _real_measure
from .prune import prune
from .space import (AggregateGeometry, FusedGeometry, TunedKernels,
                    default_config)


def current_platform() -> str:
    """Cache/registry platform tag: jax backend, '-interp' when Pallas
    kernels would run interpreted there (repro.kernels._interpret)."""
    import jax
    from repro.kernels._interpret import resolve_interpret
    base = jax.default_backend()
    return f"{base}-interp" if resolve_interpret(None) else base


def target_hw() -> HW:
    """Peaks the roofline pruning ranks candidates against: the attached
    TPU's, by ``device_kind`` (an unknown kind raises). Off the TPU the
    kernels run in the interpreter, which has no peaks of its own; the
    candidates are the v5e launches, so they are pruned against v5e."""
    import jax
    dev = jax.devices()[0]
    return peaks_for(dev.device_kind) if dev.platform == "tpu" else V5E


def tune(geom, *, cache: TuneCache | None = None, hw: HW | None = None,
         seed: int = 0, iters: int = 3, warmup: int = 1,
         slack: float = 2.0, max_survivors: int = 4,
         measure_fn=None, force: bool = False,
         register_result: bool = True):
    """Decide the config for one kernel geometry on the current platform.

    Returns ``(config, info)``; ``info`` records whether the cache
    answered (``cached``), the deterministic survivor list with roofline
    bounds, and — when measurement ran — per-survivor seconds including
    the hand-picked default's (``default_s`` / ``winner_s``).
    """
    platform = current_platform()
    info = {"platform": platform, "cached": False}
    if cache is not None and not force:
        hit = cache.get(geom, platform)
        if hit is not None:
            if register_result:
                registry.register(geom.key(), hit)
            info["cached"] = True
            return hit, info

    survivors = prune(geom, hw=hw or target_hw(), slack=slack,
                      max_survivors=max_survivors)
    info["survivors"] = [(c.as_dict(), b) for c, b in survivors]
    measure_fn = measure_fn or (
        lambda g, c: _real_measure(g, c, seed=seed, iters=iters,
                                   warmup=warmup))
    timed = [(measure_fn(geom, c), c, b) for c, b in survivors]
    # winner: fastest, ties broken by config order so reruns agree
    t_win, winner, bound = min(timed, key=lambda r: (r[0], r[1]))
    default = default_config(geom)
    t_default = next(t for t, c, _ in timed if c == default)
    info.update(winner_s=t_win, default_s=t_default,
                measured=[(c.as_dict(), t) for t, c, _ in timed],
                n_candidates=len(survivors))
    if cache is not None:
        cache.put(geom, platform, winner, bound_s=bound,
                  measured_s=round(t_win, 6), default_s=round(t_default, 6),
                  n_measured=len(timed), seed=seed)
        if cache.path is not None:
            cache.save()
    if register_result:
        registry.register(geom.key(), winner)
    return winner, info


def plan_geometries(plan, cfg) -> list:
    """Per-layer kernel geometries an ExecutionPlan's forward launches.

    ``fused`` launches the fused GNN-layer kernel, composed ``pallas``
    launches the standalone aggregation kernel (its crossbar stage is the
    jnp oracle); ``jnp`` is pure XLA, so it tunes nothing — an empty
    bundle, not an error. Bucketed plans launch one kernel shape per
    capacity bucket, so every distinct (rows, table, width) triple gets
    its own geometry.
    """
    if cfg.backend not in ("fused", "pallas"):
        return []
    if getattr(plan, "bucketed", None) is not None:
        bp = plan.bucketed
        shapes = sorted({(bp.n_caps[b], bp.n_caps[b] + bp.h_caps[b],
                          bp.s_caps[b]) for b in range(bp.n_buckets)})
    else:
        nd = int(plan.neighbors.shape[-2])
        # gather table rows: owned + halo rows on distributed settings
        n = nd + (int(plan.part.h_max) if plan.part is not None else 0)
        shapes = [(nd, n, int(plan.sample))]
    dims = cfg.dims
    geoms = []
    for nd, n, s in shapes:
        for f_in, f_out in zip(dims[:-1], dims[1:]):
            if cfg.backend == "fused":
                geoms.append(FusedGeometry(
                    nd=int(nd), n=int(n), f_in=int(f_in), f_out=int(f_out),
                    sample=int(s), ideal=bool(cfg.numerics.ideal),
                    rows_per_xbar=int(cfg.numerics.rows_per_xbar)))
            else:
                geoms.append(AggregateGeometry(
                    nd=int(nd), n=int(n), f=int(f_in), sample=int(s)))
    return geoms


def tune_plan(plan, cfg, *, cache: TuneCache | None = None,
              **tune_kw) -> TunedKernels:
    """Tune every kernel geometry of one plan; returns the TunedKernels
    bundle (also registered process-wide and cached when ``cache``)."""
    mapping = {}
    for geom in plan_geometries(plan, cfg):
        key = geom.key()
        if key in mapping:
            continue
        config, _ = tune(geom, cache=cache, **tune_kw)
        mapping[key] = config
    return TunedKernels.of(mapping)
