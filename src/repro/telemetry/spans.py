"""Low-overhead span tracer producing nested span trees (DESIGN.md §14).

A span is one timed node: ``with tracer.span("halo.gather", bucket=3):``.
Spans nest lexically via a per-tracer stack; completed top-level spans are
retained in a bounded ring so long serving runs cannot grow without bound,
while a per-name aggregate (count/total/max) survives ring eviction.

Design constraints (the ≤5% overhead contract of benchmarks/obs_overhead.py):

- When the tracer is disabled and no profile is being captured, ``span()``
  returns a shared immutable ``NULL_SPAN`` singleton whose
  enter/exit/set/add_bytes are no-ops — the disabled cost of an
  instrumented call site is one flag check, one profiler check and one
  method call, no allocation.
- While a JAX profile is being captured
  (``jax.profiler.TraceAnnotation.is_enabled()``), every span writes a
  ``TraceAnnotation`` of its name into the profile, enabled or not: the
  program's spans then sit on the host timeline of any profile, on the
  device trace's clock. A disabled tracer returns a ``_ProfileSpan`` that
  does only that (its ``set``/``add_bytes`` are no-ops).
- Spans never force device synchronisation. JAX dispatch is async, so a
  span around a jitted call measures *dispatch* time only; the device's
  time is read from the device trace of a profile, which shares the
  spans' clock.

Bytes accounting: ``Span.add_bytes`` attaches wire bytes to a span and
``Span.total_bytes()`` sums a subtree.  The instrumentation layer
(telemetry/instrument.py) bills bytes from the same send/recv tables that
``distributed.traffic`` uses, so span-tree totals equal
``ExecutionPlan.measured_traffic`` exactly — by construction, not by luck.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "SpanTracer", "NULL_SPAN"]

_profiling = TraceAnnotation.is_enabled     # a profile is being captured


class Span:
    """One timed node of a span tree (also its own context manager)."""

    __slots__ = ("name", "attrs", "t_start", "t_end", "children", "_tracer", "_ann")

    def __init__(self, name: str, tracer: "Optional[SpanTracer]" = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.t_start = 0.0
        self.t_end = 0.0
        self.children: List[Span] = []
        self._tracer = tracer
        self._ann = None

    # -- attribute / bytes helpers -------------------------------------
    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def add_bytes(self, n: int) -> "Span":
        self.attrs["bytes"] = int(self.attrs.get("bytes", 0)) + int(n)
        return self

    @property
    def duration_s(self) -> float:
        return max(self.t_end - self.t_start, 0.0)

    def total_bytes(self) -> int:
        """Sum of ``bytes`` attrs over this span and all descendants."""
        return int(self.attrs.get("bytes", 0)) + sum(
            c.total_bytes() for c in self.children
        )

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "name": self.name,
            "t_start": self.t_start,
            "duration_s": self.duration_s,
        }
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    # -- context manager -----------------------------------------------
    def __enter__(self) -> "Span":
        tr = self._tracer
        if tr is not None:
            if tr._stack:
                tr._stack[-1].children.append(self)
            tr._stack.append(self)
        if _profiling():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t_end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        tr = self._tracer
        if tr is not None:
            tr._close(self)
        return False

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"Span({self.name!r}, {self.duration_s * 1e3:.3f}ms, "
            f"children={len(self.children)}, attrs={self.attrs})"
        )


class _NullSpan:
    """Shared no-op span returned by a disabled tracer."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def add_bytes(self, n: int) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class _ProfileSpan(_NullSpan):
    """A disabled tracer's span while a profile is captured: its name in
    the profile, nothing recorded."""

    __slots__ = ("_ann",)

    def __init__(self, name: str):
        self._ann = TraceAnnotation(name)

    def __enter__(self) -> "_ProfileSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        return False


class SpanTracer:
    """Produces span trees; keeps a bounded ring of completed root spans.

    Parameters
    ----------
    enabled:
        When False (default) ``span()`` returns ``NULL_SPAN`` outside a
        profile and a ``_ProfileSpan`` inside one — the instrumented hot
        paths pay only a flag check and a profiler check.
    max_roots:
        Ring-buffer capacity for completed top-level span trees.
    registry:
        Optional ``MetricsRegistry``; on span exit the duration is recorded
        into a ``span_seconds{span=<name>}`` histogram so p50/p95/p99 per
        span name fall out of tracing with no second instrumentation pass.
    """

    def __init__(self, enabled: bool = False, max_roots: int = 256,
                 registry: Any = None):
        self.enabled = bool(enabled)
        self.registry = registry
        self.roots: deque = deque(maxlen=int(max_roots))
        self._stack: List[Span] = []
        # name -> [count, total_s, max_s]; survives ring eviction.
        self._agg: Dict[str, List[float]] = {}

    # -- span creation ---------------------------------------------------
    def span(self, name: str, **attrs: Any):
        if self.enabled:
            return Span(name, tracer=self, attrs=attrs or None)
        return _ProfileSpan(name) if _profiling() else NULL_SPAN

    def current(self) -> Optional[Span]:
        return self._stack[-1] if self._stack else None

    def _close(self, sp: Span) -> None:
        # With-blocks guarantee LIFO order per thread; tolerate a foreign
        # top-of-stack (e.g. tracer reset mid-span) by searching.
        stack = self._stack
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:  # pragma: no cover - defensive
            stack.remove(sp)
        if not stack:
            self.roots.append(sp)
        agg = self._agg.get(sp.name)
        dur = sp.duration_s
        if agg is None:
            self._agg[sp.name] = [1, dur, dur]
        else:
            agg[0] += 1
            agg[1] += dur
            if dur > agg[2]:
                agg[2] = dur
        reg = self.registry
        if reg is not None:
            reg.histogram("span_seconds", span=sp.name).observe(dur)

    # -- reporting ---------------------------------------------------------
    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name aggregate over every completed span (incl. evicted)."""
        out: Dict[str, Dict[str, float]] = {}
        for name, (count, total, mx) in sorted(self._agg.items()):
            out[name] = {
                "count": int(count),
                "total_s": float(total),
                "mean_s": float(total / count) if count else 0.0,
                "max_s": float(mx),
            }
        return out

    def export_trace(self, path: str) -> int:
        """Write retained root span trees as JSONL; returns tree count."""
        n = 0
        with open(path, "w") as fh:
            for root in self.roots:
                fh.write(json.dumps(root.to_dict()) + "\n")
                n += 1
        return n

    def reset(self) -> None:
        self.roots.clear()
        self._stack.clear()
        self._agg.clear()
