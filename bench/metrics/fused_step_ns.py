"""fused_step_ns: device time of one grid step of the fused GCN-layer
kernel, in nanoseconds (profiler trace).

The program names the kernel's launches ``fused_ideal_layer``
(``pallas_call(name=...)``), so the trace names each launch
``%fused_ideal_layer.<n>``. Per chip: the summed device time of the
window's launches of that kernel over the grid steps of the window's
updates, then the mean over the chips. One grid step gathers one row-slot,
so an update takes, per chip and layer, its rows times the sample's slots;
the rows of each layer come from ``work.py``'s FLOPs per chip and the
widths and sample of the configuration whose model FLOPs per update are
the run's. A program whose kernels carry no name gives the trace no such
launch, and the reader returns None.
"""
import re

from bench import harness, trace, work

KERNEL = "fused_ideal_layer"
_LAUNCH = re.compile(rf"%{KERNEL}(\.\d+)?")


def is_launch(name: str) -> bool:
    return _LAUNCH.fullmatch(name.split(" ", 1)[0]) is not None


def row_slots(r) -> list | None:
    """Per chip, the grid steps of one update: rows x slots, summed over
    layers. Layer FLOPs per chip are ``2 rows f_in (slots + f_out)``."""
    spec = harness.load_spec()
    for c in spec["configs"]:
        cfg = harness.config_of(spec, c["name"])
        m = cfg["model"]
        dims = (m["in_dim"], *m["hidden_dims"], m["out_dim"])
        s = m["sample"]
        if work.model_flops(cfg["graph"]["nodes"], s, dims) != r.model_flops:
            continue
        return [sum(lw["flops"] / (2.0 * f_in * (s + f_out)) * s
                    for lw, f_in, f_out in zip(layers, dims[:-1], dims[1:]))
                for layers in r.work]
    return None


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
