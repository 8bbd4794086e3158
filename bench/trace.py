"""Profiler trace capture and its reduction to what the metric readers read.

``capture(logdir)`` starts JAX's profiler with the Python tracer off (it
would record every Python call of the serving loop); ``stop`` ends it and
returns the ``.xplane.pb`` it wrote.

``reduce(path)`` keeps, from that file,

  * per device plane (``/device:TPU:<n>``), the events of its ``XLA Ops``
    line: ``(name, start_ns, end_ns)``, where the trace's name (the whole
    HLO instruction) is cut to ``"<instruction> <opcode>"``, with
    `` tpu_custom_call`` after a Pallas kernel's: the kernels carry no
    ``name=``, and the trace names a launch ``%closed_call.<n>``;
  * the host spans the benchmark placed with ``jax.profiler.TraceAnnotation``
    (names starting ``bench.``);
  * the window: the ``bench.window`` span.

The functions below work on that reduced form, which is plain data; they
are tested on a trace a chip run recorded (``bench/tests/data``).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
# HLO opcodes of the collectives a trace can hold (async ones end -start)
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
PALLAS = "tpu_custom_call"
_OPCODE = re.compile(r" ([a-z][\w\-.]*)\(")


def short_name(hlo: str) -> str:
    """``"%closed_call.8 = f32[..] custom-call(..), custom_call_target=
    \"tpu_custom_call\", .."`` -> ``"%closed_call.8 custom-call
    tpu_custom_call"``."""
    lhs, eq, rhs = hlo.partition(" = ")
    if not eq:
        return hlo
    m = _OPCODE.search(" " + rhs)
    name = f"{lhs} {m.group(1)}" if m else lhs
    return f"{name} {PALLAS}" if f'"{PALLAS}"' in rhs else name


def capture(logdir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop(logdir: str) -> str:
    import jax
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under "
                                f"{logdir}")
    return found[-1]


def reduce(path: str) -> dict:
    """The reduced trace of one ``.xplane.pb`` (module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (short_name(e.name), int(e.start_ns), int(e.end_ns))
                        for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def window(red: dict) -> tuple:
    """``(start_ns, end_ns)`` of the measured window."""
    for name, a, b in red["spans"]:
        if name == WINDOW:
            return a, b
    raise ValueError(f"no {WINDOW} span in the trace")


def _clip(events, lo: int, hi: int):
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events, lo: int, hi: int) -> list:
    """The union of the events' intervals inside ``[lo, hi)``, merged and
    in order."""
    out = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(red: dict) -> dict:
    """Per device, the nanoseconds of the window in which an op ran."""
    lo, hi = window(red)
    return {dev: sum(b - a for a, b in busy_intervals(ev, lo, hi))
            for dev, ev in red["devices"].items()}


def op_time_ns(red: dict, match) -> dict:
    """Per device, the summed duration of the window's events whose name
    ``match(name)`` accepts."""
    lo, hi = window(red)
    return {dev: sum(b - a for n, a, b in _clip(ev, lo, hi) if match(n))
            for dev, ev in red["devices"].items()}


def opcode(name: str) -> str:
    parts = name.split()
    return parts[1] if len(parts) > 1 else ""


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVES)


def is_pallas(name: str) -> bool:
    return name.endswith(PALLAS)


def leaves(events) -> list:
    """The events that hold no other event (a ``while`` holds its body's
    ops, which the trace lists too)."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= e[2]]


def top_ops(red: dict, k: int = 10) -> list:
    """``[[name, seconds]]``: the ``k`` ops that took most device time in
    the window, summed over their events (innermost only) and averaged
    over devices."""
    lo, hi = window(red)
    tot = {}
    for ev in red["devices"].values():
        for n, a, b in _clip(leaves(ev), lo, hi):
            tot[n] = tot.get(n, 0) + (b - a)
    n_dev = max(len(red["devices"]), 1)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / n_dev / 1e9] for n, t in top]


def idle_gaps(red: dict, k: int = 10) -> list:
    """``[[host activity, seconds]]``: the ``k`` longest idle gaps of the
    first device in the window, each named by the innermost benchmark span
    open on the host at the gap's middle (``bench.loop`` where none is)."""
    if not red["devices"]:
        return []
    lo, hi = window(red)
    dev = sorted(red["devices"])[0]
    busy = busy_intervals(red["devices"][dev], lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    spans = [s for s in red["spans"] if s[0] != WINDOW]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) // 2
        inner = [s for s in spans if s[1] <= mid < s[2]]
        name = (min(inner, key=lambda s: s[2] - s[1])[0] if inner
                else "bench.loop")
        out.append([name, (b - a) / 1e9])
    return out
