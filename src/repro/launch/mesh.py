"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

Every axis is ``AxisType.Auto``: GSPMD propagates shardings from the
annotated inputs, and the ambient mesh is installed with ``jax.set_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """General helper for tests/examples (Auto axis types, any size)."""
    return _mesh(shape, axes)


def preferred_tp(cfg, n_chips: int, max_tp: int = 16) -> int:
    """Divisibility-aware TP degree for an architecture.

    The 16x16 production mesh is the compatibility gate, but a TP degree
    that does not divide the head count (yi-34b: 56 heads), the expert
    count (grok-1: 8 experts), or the FFN width forces GSPMD to replicate
    or reshard attention/dispatch internals — measured 2-13x collective and
    ~2x memory penalties (EXPERIMENTS.md §Perf). Pick the largest TP that
    divides every sharded quantity; the launcher uses it when --mesh is
    not forced.
    """
    tp = max_tp
    while tp > 1:
        ok = (n_chips % tp == 0 and cfg.n_heads % tp == 0
              and cfg.d_ff % tp == 0)
        if cfg.moe is not None:
            # EP-first: splitting an expert's hidden dim costs ~3x vs exact
            # expert parallelism (grok-1 measurement, EXPERIMENTS.md §Perf)
            ok = ok and cfg.moe.n_experts % tp == 0
        if ok:
            return tp
        tp //= 2
    return 1


def preferred_mesh(cfg, n_chips: int = 256):
    """(data, model) mesh with the arch-preferred TP degree."""
    tp = preferred_tp(cfg, n_chips)
    return make_mesh((n_chips // tp, tp), ("data", "model"))
