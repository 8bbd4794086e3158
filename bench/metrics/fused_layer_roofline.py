"""fused_layer_roofline: the share of its roofline the fused GCN-layer
kernel (``kernels/fused_layer``) reaches, in percent. The least time the
window's updates could take on the chips, from ``work.py``'s compulsory
bytes and model FLOPs per device and layer against ``peaks.py`` (the
larger of the two bounds per layer; at these shapes memory bounds both
layers), over the summed device time of the kernel's trace events.

The Pallas calls carry no ``name=``: the trace names each launch
``%closed_call.<n>``, a ``custom-call`` to ``tpu_custom_call``. With ideal
numerics the fused kernel is the only Pallas kernel on this path, so every
such event is counted as it (``trace.is_pallas``).
"""
from bench import trace, work


def read(r):
    if r.trace is None or not r.n_updates:
        return None
    t = trace.op_time_ns(r.trace, trace.is_pallas)
    if not any(t.values()):
        return None
    ideal = sum(s for s, _ in work.ideal_seconds(r.work, r.peaks))
    return 100.0 * ideal * r.n_updates / (sum(t.values()) / 1e9)
