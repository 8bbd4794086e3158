"""device_idle_pct: 1 minus the union of the device-op intervals over the
traced window, mean over the chips the cell uses, in percent (profiler
trace)."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace["devices"]:
        return None
    lo, hi = trace.window(r.trace)
    busy = trace.busy_ns(r.trace)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
