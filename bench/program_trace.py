"""The program's own marks in a profiler trace: its kernel launches and its
host spans.

``reduce(path)`` reads, from one ``.xplane.pb``,

  * ``kernels``: per device plane (``/device:TPU:<n>``), the events of its
    ``XLA Ops`` line whose HLO text carries a non-empty
    ``kernel_metadata={...}``: ``(metadata, start_ns, end_ns)``, where
    ``metadata`` is the dict the launch's ``pallas_call(metadata=...)``
    stated (``kernel``, and its grid: ``rows`` and ``slots`` of a gather
    chunk, ``f_in``, ``f_out``; string values);
  * ``program``: the host events whose names start with one of the
    program's span prefixes (``server.``, ``plan.``, ``engine.``,
    ``halo.``, ``cache.``), ``(name, start_ns, end_ns)`` in order of
    start. The program writes them into any profile (``repro.telemetry``),
    whether its telemetry is on or not.

It sits beside ``trace.reduce``, whose form it leaves as it is. The
functions below work on ``[lo, hi)``, a window of the trace.
"""
from __future__ import annotations

import json
import re
import statistics

from bench.trace import DEVICE_PREFIX, OPS_LINE

PROGRAM_PREFIXES = ("server.", "plan.", "engine.", "halo.", "cache.")
_METADATA = re.compile(r"kernel_metadata=(\{.*?\})", re.DOTALL)


def kernel_metadata(hlo: str) -> dict:
    """The ``kernel_metadata`` dict of one HLO instruction's text; empty
    where it has none or an empty one."""
    m = _METADATA.search(hlo)
    return json.loads(m.group(1)) if m else {}


def reduce(path: str) -> dict:
    """``{"kernels", "program"}`` of one ``.xplane.pb`` (module
    docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    kernels, program = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    launches = []
                    for e in line.events:
                        meta = kernel_metadata(e.name)
                        if meta:
                            launches.append((meta, int(e.start_ns),
                                             int(e.end_ns)))
                    kernels[plane.name] = launches
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        program.append((e.name, int(e.start_ns),
                                        int(e.end_ns)))
    program.sort(key=lambda s: s[1])
    return {"kernels": kernels, "program": program}


def step_ns(kernels: dict, kernel: str, lo: int, hi: int) -> float | None:
    """Device nanoseconds per grid step of ``kernel``: per device, the
    summed time of its launches that start in ``[lo, hi)`` over the sum of
    their ``rows x slots``, then the mean over the devices that ran it;
    None where none did."""
    per_device = []
    for launches in kernels.values():
        t = steps = 0
        for meta, a, b in launches:
            if meta.get("kernel") == kernel and lo <= a < hi:
                t += b - a
                steps += int(meta["rows"]) * int(meta["slots"])
        if steps:
            per_device.append(t / steps)
    return statistics.fmean(per_device) if per_device else None


def host_ms(program: list, lo: int, hi: int, span: str = "server.refresh",
            wait: str = "server.refresh.wait") -> float | None:
    """The median, over the ``span`` spans that start in ``[lo, hi)``, of
    the span's duration less that of its ``wait`` children, in
    milliseconds: an update's host work while the device has nothing of it
    queued. None where no such span is."""
    outer = [(a, b) for n, a, b in program if n == span and lo <= a < hi]
    waits = [(a, b) for n, a, b in program if n == wait]
    if not outer:
        return None
    return statistics.median(
        (b - a - sum(wb - wa for wa, wb in waits if a <= wa and wb <= b))
        / 1e6 for a, b in outer)
