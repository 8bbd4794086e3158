"""step_mfu_pct: model FLOPs of the updates completed in the window over
the window's length times the chips times the chip's peak (``work.py``,
``peaks.py``), in percent. The whole update's share of the peak, which
bounds what any one kernel's roofline share can give end to end."""


def read(r):
    if not r.n_updates:
        return None
    return (r.model_flops * r.n_updates
            / (r.window_s * r.chips * r.peaks["flops_per_s"]) * 100.0)
