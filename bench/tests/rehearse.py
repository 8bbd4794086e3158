"""CPU rehearsal of a cell: a whole run of ``harness.run_cell`` at a tiny
size, with the Pallas kernels interpreted and the harness's look for a
chip steered aside, optionally with the timed path broken underneath.

Steering, for the CPU only: the devices are JAX's CPU devices, the peak
table gets an entry for them, and ``correct`` is judged against the
float32 reference, since the interpreter computes the kernel's dot in
float32 where the chip makes one bfloat16 pass.

    python -m bench.tests.rehearse <cell> [--fault F] [--trace]

prints the result as one JSON line (a cell of several chips needs
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from unittest import mock

import jax

from bench import harness, loadgen, peaks, reference
from bench.faults import FAULTS, broken

NODES = 300
EDGES = NODES * 66              # collab's mean in-degree
SEED = 2 ** 31 + 7              # larger than 32 signed bits hold


def tiny(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg["graph"].update(nodes=NODES, edges=EDGES)
    return cfg


@contextlib.contextmanager
def steered():
    with mock.patch.dict(peaks.PEAKS, {"cpu": peaks.PEAKS["TPU v5 lite"]}), \
            mock.patch.object(reference, "JUDGED", "float32"):
        yield


# a cell whose configuration is ready but that BENCHMARK.json does not
# hold yet (it has not been measured on the chip)
PENDING = {"collab-dec4-refresh": {"name": "collab-dec4-refresh",
                                   "config": "collab-gcn-dec4",
                                   "traffic": "refresh-serve", "chips": 4}}


def run(cell_name: str, fault: str = "none", traced: bool = False,
        seconds: float = 1.0, seed: int = SEED) -> dict:
    """The result line of one rehearsed run."""
    spec = harness.load_spec()
    names = [w["name"] for w in spec["workloads"]]
    cell = (harness.cell_of(spec, cell_name) if cell_name in names
            else PENDING[cell_name])
    with open(os.path.join(harness.HERE, "configs",
                           f"{cell['config']}.json")) as f:
        cfg = tiny(json.load(f))
    mix = loadgen.load_mix(cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    assert len(devices) == cell["chips"], (cell["chips"], jax.devices())
    with steered(), broken(fault):
        result, _ = harness.run_cell(spec, cell, cfg, mix, seed, seconds,
                                     traced, devices, time.monotonic())
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--fault", default="none", choices=FAULTS)
    ap.add_argument("--trace", action="store_true")
    a = ap.parse_args()
    print(json.dumps(run(a.cell, a.fault, a.trace)))
