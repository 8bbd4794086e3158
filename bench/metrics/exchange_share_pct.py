"""exchange_share_pct: the halo exchange's share of the device's busy
time, in percent (profiler trace). Per chip, the device time of the
collective ops (all-to-all, and all-gather in that exchange mode) over the
union of the intervals in which any op ran in the traced window; then the
mean over the chips. The paper's split of communication against
computation, read on the chip. A trace without collectives gives None."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace["devices"]:
        return None
    coll = trace.op_time_ns(r.trace, trace.is_collective)
    busy = trace.busy_ns(r.trace)
    shares = [coll[d] / busy[d] for d in coll if busy[d]]
    if not any(coll.values()) or not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
