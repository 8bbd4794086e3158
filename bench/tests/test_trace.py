"""``bench/trace.py``: the reduction from a profiler trace to busy and idle
time, kernel and collective time and gap attribution."""
import gzip
import os
import shutil

import numpy as np
import pytest

from bench import harness, trace

DEV0, DEV1 = f"{trace.DEVICE_PREFIX}0", f"{trace.DEVICE_PREFIX}1"
K = "%closed_call.1 custom-call tpu_custom_call"


def _synthetic():
    """Two devices over a 100 ns window; device 0 idles 10-20 (host in
    bench.scatter) and 70-100 (host in bench.serve, nested in an update)."""
    return {
        "devices": {
            DEV0: [(K, 0, 10), ("%a2a.1 all-to-all", 20, 30),
                   (K, 25, 70), ("outside", 150, 160)],
            DEV1: [(K, 0, 50), ("%a2a.1 all-to-all", 50, 60)],
        },
        "spans": [("bench.window", 0, 100), ("bench.update", 0, 90),
                  ("bench.scatter", 5, 22), ("bench.serve", 70, 100)],
    }


def test_busy_is_the_union_inside_the_window():
    red = _synthetic()
    assert trace.window(red) == (0, 100)
    assert trace.busy_intervals(red["devices"][DEV0], 0, 100) == [
        [0, 10], [20, 70]]
    assert trace.busy_ns(red) == {DEV0: 60, DEV1: 60}


def test_op_time_and_collectives():
    red = _synthetic()
    assert trace.op_time_ns(red, trace.is_pallas) == {DEV0: 55,
                                                               DEV1: 50}
    assert trace.op_time_ns(red, trace.is_collective) == {DEV0: 10,
                                                          DEV1: 10}
    top = trace.top_ops(red)
    assert top[0] == [K, 105 / 2 / 1e9]
    assert [n for n, _ in top] == [K, "%a2a.1 all-to-all"]


def test_short_names_and_nesting():
    hlo = ('%closed_call.8 = f32[4096,1,128]{2,1,0:T(1,128)S(1)} custom-call('
           's32[32768]{0:T(1024)S(1)} %bitcast.33), custom_call_target='
           '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    assert trace.short_name(hlo) == K.replace(".1 ", ".8 ")
    assert trace.short_name("%while.2 = (s32[]{:T(128)}, f32[8]{0}) while("
                            "(s32[], f32[8]) %t)") == "%while.2 while"
    outer, inner = ("%while.2 while", 0, 10), (K, 2, 5)
    assert trace.leaves([outer, inner, (K, 10, 12)]) == [inner, (K, 10, 12)]


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = trace.idle_gaps(_synthetic())
    assert gaps == [["bench.serve", 30e-9], ["bench.scatter", 10e-9]]


def test_readers_on_a_synthetic_trace():
    from bench import peaks
    red = _synthetic()
    rec = harness.Records(
        chips=2, setup_s=1.0, window_s=100e-9, n_updates=2,
        lookup_due=np.array([0.0, 1.0]),
        lookup_start=np.array([0.5, 1.0]), lookup_end=np.array([0.6, 1.3]),
        model_flops=1e3, work=[[{"flops": 1.0, "bytes": 819.0}]] * 2,
        peaks=peaks.peaks_for("TPU v5 lite"), trace=red)
    read = harness.load_reader
    assert read("device_idle_pct")(rec) == pytest.approx(40.0)
    assert read("halo_collective_ms")(rec) == pytest.approx(10 / 2 / 1e6)
    assert read("query_service_us")(rec) == pytest.approx(0.2e6)
    assert read("query_p95_ms")(rec) == pytest.approx(
        np.percentile([0.6, 0.3], 95) * 1e3)
    assert read("embed_update_ms")(rec) == pytest.approx(50e-9 * 1e3)
    assert read("step_mfu_pct")(rec) == pytest.approx(
        1e3 * 2 / (100e-9 * 2 * 197e12) * 100)
    assert read("fused_layer_roofline")(rec) == pytest.approx(
        100 * 2 * 2 * (819.0 / 819e9) / (105 / 1e9))
    rec.trace = None
    assert read("device_idle_pct")(rec) is None


CHIP_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "collab_refresh_5s.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """The reduced profiler trace of a 5 s traced run of collab-refresh on
    one TPU v5 lite (10 updates), as the chip run recorded it."""
    path = tmp_path_factory.mktemp("trace") / "run.xplane.pb"
    with gzip.open(CHIP_TRACE) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return trace.reduce(str(path))


def test_chip_trace_busy_kernels_and_gaps(chip_trace):
    red = chip_trace
    assert list(red["devices"]) == [DEV0]
    lo, hi = trace.window(red)
    assert hi - lo == 5_230_591_905
    assert [s[0] for s in red["spans"]].count("bench.update") == 10
    assert trace.busy_ns(red) == {DEV0: 5_123_007_416}
    # the fused kernel, one launch per chunk of rows, two layers
    assert trace.op_time_ns(red, trace.is_pallas) == {DEV0: 4_988_923_430}
    top = trace.top_ops(red, 2)
    assert [n for n, _ in top] == [
        "%closed_call.8 custom-call tpu_custom_call",
        "%closed_call.9 custom-call tpu_custom_call"]
    assert trace.op_time_ns(red, trace.is_collective) == {DEV0: 0}
    gaps = trace.idle_gaps(red)
    assert {n for n, _ in gaps} == {"bench.scatter"}
    assert all(0.005 < s < 0.02 for _, s in gaps)


def test_readers_on_the_chip_trace(chip_trace):
    from bench import peaks, work
    dims, n = (496, 64, 16), 372_475
    rec = harness.Records(
        chips=1, setup_s=1.0, window_s=5.230591905, n_updates=10,
        lookup_due=np.zeros(0),
        lookup_start=np.zeros(0), lookup_end=np.zeros(0),
        model_flops=work.model_flops(n, 8, dims),
        work=work.update_work(dims, 8, [(n, 0)]),
        peaks=peaks.peaks_for("TPU v5 lite"), trace=chip_trace)
    read = harness.load_reader
    assert read("device_idle_pct")(rec) == pytest.approx(
        100 * (1 - 5_123_007_416 / 5_230_591_905))
    ideal = (858_309_376 + 143_034_496) / 819e9
    assert read("fused_layer_roofline")(rec) == pytest.approx(
        100 * ideal * 10 / 4.988923430)
    assert read("halo_collective_ms")(rec) is None
