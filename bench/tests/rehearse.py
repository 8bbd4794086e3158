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
import time
from unittest import mock

import jax

from bench import harness, kinds, loadgen, peaks
from bench.faults import FAULTS, broken

NODES = 300
EDGES = NODES * 66              # collab's mean in-degree
SEED = 2 ** 31 + 7              # larger than 32 signed bits hold


def tiny(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg["graph"].update(nodes=NODES, edges=EDGES)
    return cfg


@contextlib.contextmanager
def steered(kind: str):
    """The CPU's peaks, and the reference of model kind ``kind`` judged at
    float32."""
    model = kinds.load("models", kind)
    with mock.patch.dict(peaks.PEAKS, {"cpu": peaks.PEAKS["TPU v5 lite"]}), \
            mock.patch.object(model, "JUDGED", "float32"):
        yield


def run(cell_name: str, fault: str = "none", traced: bool = False,
        seconds: float = 1.0, seed: int = SEED) -> dict:
    """The result line of one rehearsed run."""
    spec = harness.load_spec()
    cell = harness.cell_of(spec, cell_name)
    cfg = tiny(harness.config_of(spec, cell["config"]))
    mix = loadgen.load_mix(cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    assert len(devices) == cell["chips"], (cell["chips"], jax.devices())
    kind = cfg["model"]["kind"]
    with steered(kind), broken(fault, kind):
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
