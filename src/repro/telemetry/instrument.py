"""Instrumentation glue between telemetry and the serving stack.

``instrument_forward`` wraps the callable ``ExecutionPlan.make_forward``
returns.  It cannot time *inside* the jitted forward (spans in traced code
would fire once, at trace time), so it does three things at the Python
boundary instead:

  1. opens a ``plan.forward`` root span tagged with the plan's
     setting/backend/clusters; it closes when the forward returns, so it
     times dispatch and never waits for the device (the device's time is
     in the device trace of a profile, on the span's clock);
  2. bills wire bytes onto zero-duration *accounting spans* computed from
     the plan's own ``measured_traffic`` report — the same executed
     send/recv tables ``distributed.halo`` hands to the exchange.  Span-tree
     byte totals therefore equal ``TrafficReport.total_bytes()`` exactly,
     by construction (the obs_overhead gate asserts this per setting);
  3. increments the ``halo.shipped_bytes`` counter so byte totals survive
     span-ring eviction;
  4. on the SPMD runtime, states on ``plan.forward`` what its exchange
     collective moves as launched: ``chips``, the per-chip ``halo_rows``
     and ``send_rows`` (rows to each peer, padded), and ``launched_bytes``
     (what each chip's collective sends in one forward, padding and the
     self block included). ``halo.exchange`` keeps the true bytes, summed
     over the chips; ``chips x launched_bytes`` less them is the padding;
  5. on the fused backend with ideal numerics, states on ``plan.forward``
     the DMAs the ``fused_ideal_layer`` launches issue in one forward,
     summed over layers and chips: ``row_dmas``, one a (row, slot), and
     ``block_dmas``, one for each block whose slot is a run of
     consecutive table rows (``ExecutionPlan.gather_dmas``), and how
     many of them read operands the plan prepared once:
     ``prepared_launches`` a prepared feature table, ``prepared_samples``
     prepared sample tables (``ExecutionPlan.prepared_launches``).

The traffic report and the DMA counts are computed lazily on the first
*traced* call and cached — with telemetry disabled the wrapper is a flag check plus the
undecorated forward.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

from . import get_registry, get_tracer

__all__ = ["instrument_forward", "record_commit", "record_streaming_traffic"]


def instrument_forward(plan, cfg, mode: str, fwd: Callable,
                       spmd: bool = False) -> Callable:
    """Wrap a plan forward with span + exact bytes accounting; ``spmd``
    says the forward runs the exchange as a collective over a mesh."""
    state: Dict[str, Any] = {}

    def run(params):
        tracer = get_tracer()
        if not tracer.enabled:
            return fwd(params)
        billing = state.get("billing")
        if billing is None:
            rep = plan.measured_traffic(cfg, mode=mode)
            tier0 = int(rep.tier0_bytes().sum())
            per_layer = [int(b) for b in rep.tier1_bytes().sum(axis=1)]
            launched = (dict(chips=rep.n_devices, halo_rows=rep.halo_rows,
                             send_rows=rep.send_rows,
                             launched_bytes=rep.launched_bytes())
                        if spmd else {})
            launched.update(plan.gather_dmas(cfg),
                            **plan.prepared_launches(cfg))
            billing = state["billing"] = (tier0, per_layer,
                                          tier0 + sum(per_layer), launched)
        tier0, per_layer, total, launched = billing
        with tracer.span("plan.forward", setting=plan.setting,
                         backend=plan.backend, clusters=plan.n_clusters,
                         **launched):
            if tier0:
                with tracer.span("halo.tier0_upload") as s0:
                    s0.add_bytes(tier0)
            out = fwd(params)
            for layer, nbytes in enumerate(per_layer):
                if nbytes:
                    with tracer.span("halo.exchange", layer=layer) as sl:
                        sl.add_bytes(nbytes)
            if total:
                get_registry().counter("halo.shipped_bytes",
                                       setting=plan.setting).inc(total)
        return out

    return run


def record_streaming_traffic(traffic, setting: str) -> None:
    """Bill one incremental tick's wire bytes (counter + current span)."""
    reg = get_registry()
    if not reg.enabled or traffic is None:
        return
    total = int(traffic.total_bytes())
    reg.counter("streaming.shipped_bytes", setting=setting).inc(total)
    cur = get_tracer().current()
    if cur is not None:
        cur.add_bytes(total)


def record_commit(update, setting: str) -> None:
    """Fold one StreamingUpdate's accounting into the registry."""
    reg = get_registry()
    if not reg.enabled:
        return
    reg.counter("server.commits").inc()
    if update.full:
        reg.counter("server.full_refreshes").inc()
    reg.histogram("server.commit_seconds").observe(float(update.seconds))
    reg.gauge("streaming.recompute_fraction").set(
        float(update.recompute_fraction))
    record_streaming_traffic(update.traffic, setting)
