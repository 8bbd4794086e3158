"""``bench/metrics/exchange_share_pct.py`` on a reduced trace written by
hand: the collectives' device time over each chip's busy time, averaged
over the chips; silent without a trace or without a collective."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, peaks, trace  # noqa: E402

DEV0, DEV1 = f"{trace.DEVICE_PREFIX}0", f"{trace.DEVICE_PREFIX}1"
K = "%closed_call.1 custom-call tpu_custom_call"


def _records(devices):
    red = {"devices": devices, "spans": [("bench.window", 0, 100)]}
    return harness.Records(
        chips=len(devices), setup_s=1.0, window_s=100e-9, n_updates=2,
        lookup_due=np.zeros(0), lookup_start=np.zeros(0),
        lookup_end=np.zeros(0), model_flops=1.0,
        work=[[{"flops": 1.0, "bytes": 1.0}]] * len(devices),
        peaks=peaks.peaks_for("TPU v5 lite"), trace=red)


def test_share_of_busy_time_mean_over_chips():
    read = harness.load_reader("exchange_share_pct")
    # chip 0: busy 0-10 and 20-70, all-to-all 20-30; chip 1: busy 0-60,
    # all-to-all-start 50-60 (the async form); outside the window is cut
    rec = _records({
        DEV0: [(K, 0, 10), ("%a2a.1 all-to-all", 20, 30), (K, 25, 70),
               ("%a2a.2 all-to-all", 150, 160)],
        DEV1: [(K, 0, 50), ("%a2a.3 all-to-all-start", 50, 60)]})
    assert read(rec) == pytest.approx(100 * (10 / 60 + 10 / 60) / 2)


def test_silent_without_trace_or_collective():
    read = harness.load_reader("exchange_share_pct")
    assert read(_records({DEV0: [(K, 0, 50)]})) is None
    rec = _records({DEV0: [(K, 0, 50)]})
    rec.trace = None
    assert read(rec) is None
