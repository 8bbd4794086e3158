#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 [--seconds 2]

For each of ``--seeds``, one run of the cell as ``bench/run.py`` makes it,
with a short window: the program's numbers (the lower readings). For each
of ``--control-seeds``, the same run with the control in the program's
place (``faults.broken("control", kind)``: the model kind's reference
computed in bfloat16 serves every update), judged by the harness's own
comparison: it must come out not correct, and its numbers are the upper
readings. Everything runs in one process, so the program compiles once.
Prints one JSON line per run, then a summary line, and exits non-zero if
a program run is not correct or a control run is; the benchmark's own
runs never run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import faults, harness, loadgen  # noqa: E402


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b) + 1) if b else [int(a)])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    spec = harness.load_spec()
    cell = harness.cell_of(spec, args.workload)
    cfg = harness.config_of(spec, cell["config"])
    mix = loadgen.load_mix(cell["traffic"])
    try:
        devices = harness.check_devices(cell["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    readings = {"program": [], "control": []}
    ok = True
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seed_list(seeds):
            fault = "none" if kind == "program" else "control"
            with faults.broken(fault, cfg["model"]["kind"]):
                res, _ = harness.run_cell(spec, cell, cfg, mix, seed,
                                          args.seconds, False, devices,
                                          time.monotonic())
            row = {k: c["value"] for k, c in res["checks"].items()}
            readings[kind].append(row)
            ok &= res["correct"] == (kind == "program")
            print(json.dumps({kind: seed, **row, "correct": res["correct"]}),
                  flush=True)
    summary = {k: {"lower": max((r[k] for r in readings["program"]),
                                default=None),
                   "upper": min((r[k] for r in readings["control"]),
                                default=None),
                   "limit": lim}
               for k, lim in cfg["checks"].items()}
    print(json.dumps({"summary": summary, "as_expected": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
