"""``bench/program_trace.py`` and the ``fused_step_ns`` reader: the
program's spans and kernel names as a profile holds them, and
``trace.reduce`` unchanged beside them."""
import gzip
import os
import shutil

import numpy as np
import pytest

from bench import harness, kinds, peaks, program_trace, trace

DEV0 = f"{trace.DEVICE_PREFIX}0"
DIMS, N, S = (496, 64, 16), 372_475, 8          # collab-gcn
GCN = kinds.load("models", "gcn")
CHIP_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "collab_refresh_5s.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace_path(tmp_path_factory):
    """A 5 s traced run of collab-refresh on one TPU v5 lite, recorded
    before the program named its kernels or wrote its spans there."""
    path = tmp_path_factory.mktemp("trace") / "run.xplane.pb"
    with gzip.open(CHIP_TRACE) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


def test_program_spans_nest_in_a_cpu_profile(tmp_path):
    """Telemetry off, a profile on: a refresh and a query of a small server
    leave the program's spans in the profile, each inside its parent."""
    import jax
    from repro import telemetry as tel
    from repro.core import gnn
    from repro.core.graph import random_graph
    from repro.core.partition import plan_execution
    from repro.launch.gnn import GNNServer

    assert not tel.enabled()
    g = random_graph(40, 200, 8, seed=1).gcn_normalize()
    plan = plan_execution(g, "centralized", backend="jnp", sample=4)
    srv = GNNServer(plan, gnn.GNNConfig(in_dim=8, hidden_dims=(8,),
                                        out_dim=4, sample=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.refresh()
        srv.query(np.arange(5))
    finally:
        jax.profiler.stop_trace()
    path, = (os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb"))
    spans = {}
    for name, a, b in program_trace.reduce(path)["program"]:
        spans.setdefault(name, (a, b))
    parents = {"server.refresh.dispatch": "server.refresh",
               "server.refresh.wait": "server.refresh",
               "plan.scatter": "server.refresh",
               "plan.scatter.fetch": "plan.scatter"}
    assert set(parents) | {"server.refresh", "server.query"} <= set(spans)
    for child, parent in parents.items():
        (ca, cb), (pa, pb) = spans[child], spans[parent]
        assert pa <= ca <= cb <= pb, (child, parent)
    assert spans["server.refresh"][1] <= spans["server.query"][0]
    assert not tel.get_tracer().roots


def test_kernel_metadata_is_read_from_the_hlo_text():
    hlo = ('%fused_ideal_layer.6 = f32[4096,1,128]{2,1,0} custom-call('
           's32[32768]{0} %bitcast.33), custom_call_target="tpu_custom_call"'
           ', frontend_attributes={kernel_metadata={\n"f_in":"512",\n'
           '"kernel":"fused_ideal_layer",\n"rows":"4096",\n"slots":"8"\n}}')
    assert program_trace.kernel_metadata(hlo) == {
        "f_in": "512", "kernel": "fused_ideal_layer", "rows": "4096",
        "slots": "8"}
    assert trace.short_name(hlo) == (
        "%fused_ideal_layer.6 custom-call tpu_custom_call")
    assert program_trace.kernel_metadata(
        hlo.split("frontend_attributes")[0]
        + "frontend_attributes={kernel_metadata={}}") == {}


def _meta(kernel, rows, slots=8):
    return {"kernel": kernel, "rows": str(rows), "slots": str(slots)}


def test_step_ns_and_host_ms_on_a_synthetic_trace():
    kernels = {
        DEV0: [(_meta("fused_ideal_layer", 4), 0, 64),
               (_meta("fused_ideal_layer", 2), 70, 86),
               (_meta("fused_zmax", 4), 90, 190),
               (_meta("fused_ideal_layer", 4), 500, 600)],   # outside
        f"{trace.DEVICE_PREFIX}1": [(_meta("fused_ideal_layer", 4), 0, 96)],
    }
    # (64 + 16) / (32 + 16) on device 0, 96 / 32 on device 1
    assert program_trace.step_ns(kernels, "fused_ideal_layer", 0, 200) == (
        pytest.approx((80 / 48 + 3) / 2))
    assert program_trace.step_ns(kernels, "fused_zmax", 0, 200) == (
        pytest.approx(100 / 32))
    assert program_trace.step_ns(kernels, "cam_search", 0, 200) is None
    ms = 1_000_000
    program = [("server.refresh", 0, 15 * ms),
               ("server.refresh.dispatch", 0, 1 * ms),
               ("server.refresh.wait", 1 * ms, 11 * ms),
               ("plan.scatter", 11 * ms, 15 * ms),
               ("server.refresh", 20 * ms, 33 * ms),
               ("server.refresh.wait", 21 * ms, 31 * ms),
               ("server.query", 34 * ms, 35 * ms),
               ("server.refresh", 50 * ms, 70 * ms)]          # outside
    assert program_trace.host_ms(program, 0, 40 * ms) == pytest.approx(4.0)
    assert program_trace.host_ms(program, 0, 40 * ms,
                                 span="server.commit") is None


def _records(red, n_updates=2):
    return harness.Records(
        chips=1, setup_s=1.0, window_s=1.0, n_updates=n_updates,
        lookup_due=np.zeros(0), lookup_start=np.zeros(0),
        lookup_end=np.zeros(0), model_flops=GCN.model_flops(N, S, DIMS),
        work=GCN.update_work(DIMS, S, [(N, 0)]),
        peaks=peaks.peaks_for("TPU v5 lite"), trace=red,
        widths=DIMS, sample=S, rows=[(N, 0)])


def test_fused_step_ns_reads_the_named_kernel():
    """Two updates of two layers, each layer N x S steps at 176 ns and
    0.1 ms of another kernel that is not counted."""
    layer = int(N * S * 176)
    k1 = "%fused_ideal_layer.6 custom-call tpu_custom_call"
    k2 = "%fused_ideal_layer.7 custom-call tpu_custom_call"
    other = "%fused_zmax.6 custom-call tpu_custom_call"
    ev, t = [], 0
    for name in (k1, k2, other, k1, k2, other):
        d = 100_000 if name == other else layer
        ev.append((name, t, t + d))
        t += d
    red = {"devices": {DEV0: ev}, "spans": [("bench.window", 0, t)]}
    read = harness.load_reader("fused_step_ns")
    assert read(_records(red)) == pytest.approx(176.0, rel=1e-6)
    rec = _records(red)
    rec.sample = 2 * S              # the run's own sample: twice the slots
    assert read(rec) == pytest.approx(88.0, rel=1e-6)
    rec.widths = ()                 # a record that does not say its model
    assert read(rec) is None


def test_fused_step_ns_is_silent_on_unnamed_kernels(chip_trace_path):
    """The recorded trace names its launches ``%closed_call.<n>``."""
    red = trace.reduce(chip_trace_path)
    assert harness.load_reader("fused_step_ns")(_records(red, 10)) is None


def test_recorded_trace_reduces_as_before(chip_trace_path):
    red = trace.reduce(chip_trace_path)
    assert set(red) == {"devices", "spans"}
    ev = red["devices"][DEV0]
    assert list(red["devices"]) == [DEV0] and len(ev) == 7910
    assert ev[0] == ("%copy-start copy-start", 44_671_938, 44_671_941)
    assert ev[-1] == ("%copy.11 copy", 5_263_018_320, 5_263_315_048)
    spans = red["spans"]
    assert len(spans) == 31
    assert spans[0] == ("bench.window", 44_697_845, 5_275_289_750)
    assert spans[-1] == ("bench.serve", 5_273_490_350, 5_275_287_210)
    prog = program_trace.reduce(chip_trace_path)
    assert prog == {"kernels": {DEV0: []}, "program": []}
