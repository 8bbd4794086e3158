"""The general traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws its arrivals from the seed.

A mix says how updates of the served table run (``update``; its ``kind``
names a module ``bench/updates/<kind>.py``, ``bench/kinds.py``) and how
lookups arrive (``lookups``): an arrival process, a rate, a batch size and
how ids are drawn. Arrivals form a schedule fixed before the window opens,
so the generator never runs late; each lookup is timed from its due time.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench import kinds

HERE = os.path.dirname(os.path.abspath(__file__))
ARRIVALS = ("poisson",)
ID_DISTS = ("uniform",)


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    try:
        kinds.load("updates", mix["update"]["kind"], root)
    except kinds.Refused as e:
        raise kinds.Refused(f"mix {name}: {e}") from None
    lk = mix["lookups"]
    if lk["arrival"] not in ARRIVALS or lk["ids"] not in ID_DISTS:
        raise ValueError(f"mix {name}: lookups {lk} not supported "
                         f"(arrivals {ARRIVALS}, ids {ID_DISTS})")
    return mix


def lookup_schedule(mix: dict, seed64: int, horizon_s: float,
                    n_nodes: int) -> tuple:
    """``(due [M] float64 seconds from the window's start, ids [M, batch]
    int64)`` for every lookup due before ``horizon_s``."""
    lk = mix["lookups"]
    rng = np.random.default_rng(seed64)
    rate = float(lk["rate_per_s"])
    n = int(rate * horizon_s + 10 * np.sqrt(rate * horizon_s) + 10)
    due = np.cumsum(rng.exponential(1.0 / rate, size=n))
    due = due[due < horizon_s]
    ids = rng.integers(0, n_nodes, size=(len(due), int(lk["batch"])))
    return due, ids
