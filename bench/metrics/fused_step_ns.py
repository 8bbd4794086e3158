"""fused_step_ns: device time of the fused GCN-layer kernel per row-slot it
gathers, in nanoseconds (profiler trace).

The program names the kernel's launches ``fused_ideal_layer``
(``pallas_call(name=...)``), so the trace names each launch
``%fused_ideal_layer.<n>``. Per chip: the summed device time of the
window's launches of that kernel over the row-slots the window's updates
gather, then the mean over the chips. A grid step takes a block of rows
and all their slots; an update gathers, per chip and layer, the rows the
chip owns times the sample's slots, from the run's own widths, sample and
rows (``Records``). A program whose kernels carry no name gives the trace
no such launch, and the reader returns None.
"""
import re

from bench import trace

KERNEL = "fused_ideal_layer"
_LAUNCH = re.compile(rf"%{KERNEL}(\.\d+)?")


def is_launch(name: str) -> bool:
    return _LAUNCH.fullmatch(name.split(" ", 1)[0]) is not None


def row_slots(r) -> list | None:
    """Per chip, the row-slots of one update: owned rows x slots x layers;
    None where the record does not say the run's model."""
    if not r.widths or not r.rows:
        return None
    layers = len(r.widths) - 1
    return [owned * r.sample * layers for owned, _ in r.rows]


def read(r):
    if r.trace is None or not r.n_updates:
        return None
    t = trace.op_time_ns(r.trace, is_launch)
    steps = row_slots(r)
    if not any(t.values()) or steps is None:
        return None
    per_chip = [t[dev] / (n * r.n_updates)
                for dev, n in zip(sorted(t), steps) if t[dev]]
    return sum(per_chip) / len(per_chip)
