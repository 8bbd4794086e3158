"""halo_collective_ms: device time per update of the halo exchange's
collective ops (all-to-all, and all-gather in that exchange mode), summed
per chip and averaged over the chips (profiler trace)."""
from bench import trace


def read(r):
    if r.trace is None or not r.n_updates:
        return None
    t = trace.op_time_ns(r.trace, trace.is_collective)
    if not any(t.values()):
        return None
    return sum(t.values()) / len(t) / r.n_updates / 1e6
