#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for (see ``bench/harness.py`` for what a run does). It exits non-zero
without a TPU.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the checkout root replaces this directory on the path, so that the
# benchmark's modules import as ``bench.*`` and never shadow the standard
# library's (``trace``); the program is under ``src``
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
