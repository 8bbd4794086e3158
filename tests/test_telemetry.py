"""Telemetry subsystem (DESIGN.md §14): span trees, the disabled fast
path, histogram percentiles, exporters, the span-bytes == measured-traffic
contract, observer isolation, streaming counters, and the benchmark
runner's ``info`` snapshot embedding."""
import json
import logging

import numpy as np
import pytest

from repro import telemetry as tel
from repro.telemetry import (NULL_SPAN, MetricsRegistry, SpanTracer,
                             default_buckets)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Process-wide singletons: every test starts and ends disabled+empty."""
    tel.reset()
    tel.disable()
    yield
    tel.reset()
    tel.disable()


# ---- span trees ---------------------------------------------------------

def test_span_nesting_builds_tree():
    tr = SpanTracer(enabled=True)
    with tr.span("tick", n=1):
        with tr.span("halo.gather", bucket=0) as g:
            g.add_bytes(100)
        with tr.span("halo.mvm"):
            with tr.span("halo.mvm.inner") as inner:
                inner.add_bytes(28)
    assert len(tr.roots) == 1
    root = tr.roots[0]
    assert root.name == "tick" and root.attrs == {"n": 1}
    assert [c.name for c in root.children] == ["halo.gather", "halo.mvm"]
    assert root.children[1].children[0].name == "halo.mvm.inner"
    # subtree byte totals roll up; durations are measured and ordered
    assert root.total_bytes() == 128
    assert root.children[0].total_bytes() == 100
    assert root.duration_s >= root.children[0].duration_s >= 0.0
    assert [s.name for s in root.walk()] == [
        "tick", "halo.gather", "halo.mvm", "halo.mvm.inner"]
    d = root.to_dict()
    assert d["name"] == "tick" and len(d["children"]) == 2
    # per-name aggregates survive independently of the ring
    assert tr.summary()["halo.gather"]["count"] == 1


def test_root_ring_is_bounded_but_aggregates_are_not():
    tr = SpanTracer(enabled=True, max_roots=4)
    for i in range(10):
        with tr.span("t"):
            pass
    assert len(tr.roots) == 4
    assert tr.summary()["t"]["count"] == 10


# ---- the disabled fast path (the ≤5% overhead contract) -----------------

def test_disabled_tracer_returns_shared_null_span():
    tr = SpanTracer(enabled=False)
    s = tr.span("anything", k=1)
    assert s is NULL_SPAN and tr.span("other") is s
    with s as inner:                       # all no-ops, no allocation
        inner.set(a=1).add_bytes(5)
    assert not tr.roots and tr.summary() == {}


def test_disabled_span_outside_a_profile_is_null_span():
    import jax
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tel.span("server.refresh", setting="centralized") is NULL_SPAN
    assert SpanTracer(enabled=False).span("plan.scatter") is NULL_SPAN


def test_spans_reach_a_profile_with_telemetry_off_or_on(tmp_path):
    """Inside a profile a disabled span writes only its name there (its
    set/add_bytes are no-ops, nothing is recorded); an enabled one still
    builds its tree."""
    import jax
    off, on = SpanTracer(enabled=False), SpanTracer(enabled=True)
    with jax.profiler.trace(str(tmp_path)):
        assert jax.profiler.TraceAnnotation.is_enabled()
        s = off.span("server.query", k=1)
        assert s is not NULL_SPAN
        with s as inner:
            assert inner.set(a=1).add_bytes(5) is inner
        with on.span("tick") as t:
            t.add_bytes(3)
    assert off.span("server.query") is NULL_SPAN
    assert not off.roots and off.summary() == {}
    assert on.roots[0].name == "tick" and on.roots[0].total_bytes() == 3


def test_disabled_registry_mutations_do_not_register():
    reg = MetricsRegistry(enabled=False)
    reg.counter("c").inc(5)
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(0.5)
    reg.event("e", k=1)
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["n_events"] == 0
    # handles resolve to live metrics after enable (call-site lookups)
    reg.enabled = True
    reg.counter("c").inc(2)
    assert reg.snapshot()["counters"] == {"c": 2.0}


def test_enable_disable_roundtrip_on_module_singletons():
    assert not tel.enabled()
    tel.enable()
    assert tel.enabled()
    with tel.span("x"):
        tel.counter("hits").inc()
    tel.disable()
    assert tel.span("y") is NULL_SPAN
    snap = tel.snapshot()                  # data survives disable
    assert snap["counters"]["hits"] == 1.0 and "x" in snap["spans"]


# ---- histograms ---------------------------------------------------------

def test_histogram_percentiles_monotone_and_bounded():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("lat")
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6.0, sigma=1.5, size=500)
    for v in vals:
        h.observe(float(v))
    q = h.quantiles()
    assert q["p50"] <= q["p95"] <= q["p99"]
    assert vals.min() <= q["p50"] and q["p99"] <= vals.max() * (1 + 1e-9)
    assert h.count == 500
    assert h.percentile(0.0) == pytest.approx(h.vmin)
    assert h.percentile(1.0) == pytest.approx(h.vmax)


def test_histogram_empty_and_buckets():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("empty")
    assert h.percentile(0.5) == 0.0 and h.quantiles()["p99"] == 0.0
    b = default_buckets(1e-3, 1.0, per_decade=2)
    assert all(x < y for x, y in zip(b, b[1:]))
    assert b[0] == pytest.approx(1e-3) and b[-1] >= 1.0 - 1e-12


# ---- exporters ----------------------------------------------------------

def test_exporters_parse(tmp_path):
    tel.enable()
    with tel.span("tick"):
        with tel.span("halo.gather") as s:
            s.add_bytes(64)
    tel.counter("reqs", setting="semi").inc(3)
    tel.gauge("frac").set(0.25)
    tel.histogram("lat").observe(1e-3)
    tel.event("planner.plan", recommended="c1k4", score=1.0)

    mpath, tpath = tmp_path / "m.jsonl", tmp_path / "t.jsonl"
    n_m = tel.export_metrics(str(mpath))
    n_t = tel.export_trace(str(tpath))
    mlines = [json.loads(line) for line in mpath.read_text().splitlines()]
    assert len(mlines) == n_m and n_m >= 4
    kinds = {m["type"] for m in mlines}
    assert {"counter", "gauge", "histogram", "event"} <= kinds
    tlines = [json.loads(line) for line in tpath.read_text().splitlines()]
    assert len(tlines) == n_t == 1
    assert tlines[0]["name"] == "tick"
    assert tlines[0]["children"][0]["attrs"]["bytes"] == 64

    text = tel.prometheus_text()
    assert 'reqs{setting="semi"} 3' in text
    assert "lat_bucket{" in text and 'le="+Inf"' in text


# ---- span bytes == measured traffic (the exactness contract) ------------

@pytest.mark.parametrize("setting,buckets", [
    ("centralized", None), ("decentralized", None), ("semi", None),
    ("decentralized", "auto")])
def test_span_bytes_equal_measured_traffic(make_graph, setting, buckets):
    """The forward's span tree bills wire bytes from the same executed
    send/recv tables ``measured_traffic`` counts — totals must be equal,
    exactly (benchmarks/obs_overhead.py gates the same identity)."""
    import jax
    from repro.core import gnn
    from repro.core.partition import plan_execution
    g = make_graph(40, 200, 8, seed=0)
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    plan = plan_execution(g, setting, backend="jnp", sample=4,
                          n_clusters=None if setting == "centralized" else 3,
                          buckets=buckets)
    params = gnn.init_params(jax.random.key(0), plan.gnn_config(cfg))
    tel.enable()
    jax.block_until_ready(plan.make_forward(cfg)(params))
    span_bytes = sum(r.total_bytes() for r in tel.get_tracer().roots
                     if r.name == "plan.forward")
    measured = int(plan.measured_traffic(plan.gnn_config(cfg)).total_bytes())
    assert span_bytes == measured
    if setting == "centralized":
        assert measured == 0               # no exchange to bill
    else:
        assert measured > 0
        key = f'halo.shipped_bytes{{setting="{setting}"}}'
        assert tel.snapshot()["counters"][key] == measured


def test_disabled_forward_is_undecorated(make_graph):
    """With telemetry off the wrapped forward must produce no spans and
    bit-identical outputs to the enabled run."""
    import jax
    from repro.core import gnn
    from repro.core.partition import plan_execution
    g = make_graph(30, 120, 8, seed=1)
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    plan = plan_execution(g, "decentralized", backend="jnp", sample=4,
                          n_clusters=3)
    params = gnn.init_params(jax.random.key(1), plan.gnn_config(cfg))
    fwd = plan.make_forward(cfg)
    off = np.asarray(fwd(params))
    assert not tel.get_tracer().roots
    tel.enable()
    on = np.asarray(fwd(params))
    assert tel.get_tracer().roots
    np.testing.assert_array_equal(off, on)


# ---- plan.forward's DMA counts of the fused kernel ----------------------

def _fused_plan(setting="centralized"):
    """A fused plan over 64 nodes of in-degree 3 and a 4-slot sample: the
    self loop fills slot 3 of every row, so each block of rows is a run."""
    from repro.core.graph import Graph
    from repro.core.partition import plan_execution
    n, d = 64, 3
    rng = np.random.default_rng(0)
    g = Graph(np.arange(n + 1, dtype=np.int64) * d,
              rng.integers(0, n, n * d).astype(np.int32), None,
              rng.normal(size=(n, 8)).astype(np.float32)).gcn_normalize()
    return plan_execution(g, setting, backend="fused", sample=d + 1,
                          n_clusters=None if setting == "centralized" else 2)


def _forward_attrs(plan, cfg):
    import jax
    from repro.core import gnn
    params = gnn.init_params(jax.random.key(0), plan.gnn_config(cfg))
    jax.block_until_ready(plan.make_forward(cfg)(params))
    return [r.attrs for r in tel.get_tracer().roots
            if r.name == "plan.forward"]


@pytest.mark.parametrize("tables", ["self", "random"])
def test_forward_span_states_gather_dmas(tables):
    """A centralized fused plan's ``plan.forward`` states the DMAs of its
    ``fused_ideal_layer`` launches, summed over layers, as the helper
    counts them on the plan's sample: one block DMA a layer where the self
    loop is a run, none for a sample with no run."""
    from repro.core import gnn
    from repro.kernels.fused_layer import ideal_layer_dmas
    plan = _fused_plan()
    if tables == "random":
        rng = np.random.default_rng(1)
        plan.neighbors = rng.integers(0, 64, plan.neighbors.shape
                                      ).astype(np.int32)
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    tel.enable()
    (attrs,) = _forward_attrs(plan, cfg)
    want = [ideal_layer_dmas(plan.neighbors[0], 64, a, b)
            for a, b in ((8, 8), (8, 4))]
    assert attrs["row_dmas"] == sum(r for r, _ in want)
    assert attrs["block_dmas"] == sum(b for _, b in want)
    runs = 2 if tables == "self" else 0
    assert attrs["block_dmas"] == runs
    assert attrs["row_dmas"] == 2 * 64 * 4 - runs * 64


def test_gather_dmas_absent_off_the_fused_kernel(monkeypatch):
    """No DMA counts with telemetry off (the count is never made), nor on
    a backend or numerics that launch no ``fused_ideal_layer``."""
    import dataclasses
    from repro.core import gnn
    from repro.core.partition import ExecutionPlan
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    quant = CrossbarNumerics(in_bits=8, w_bits=8, adc_bits=12,
                             rows_per_xbar=64)
    plan = _fused_plan()
    calls = []
    count = ExecutionPlan.gather_dmas
    monkeypatch.setattr(ExecutionPlan, "gather_dmas",
                        lambda self, c: calls.append(c) or count(self, c))
    assert _forward_attrs(plan, cfg) == [] and not calls
    for backend, numerics in (("fused", quant), ("jnp", cfg.numerics)):
        tel.reset()
        tel.enable()
        plan.backend = backend
        (attrs,) = _forward_attrs(
            plan, dataclasses.replace(cfg, numerics=numerics))
        assert not {"row_dmas", "block_dmas", "prepared_launches",
                    "prepared_samples"} & set(attrs)
        assert not [r for r in tel.get_tracer().roots
                    if r.name == "plan.prepare"]
    assert len(calls) == 2


@pytest.mark.parametrize("setting,launches,samples", [
    ("centralized", 1, 2), ("decentralized", 0, 4)])
def test_forward_span_states_prepared_launches(setting, launches, samples):
    """``plan.forward`` states how many fused launches of one forward read
    operands the plan prepared, summed over layers and clusters: layer 1's
    feature table on one device only (elsewhere the exchange rebuilds it
    every layer), the sample tables in every launch."""
    from repro.core import gnn
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    tel.enable()
    (attrs,) = _forward_attrs(_fused_plan(setting), cfg)
    assert attrs["prepared_launches"] == launches
    assert attrs["prepared_samples"] == samples


@pytest.mark.parametrize("setting", ["centralized", "decentralized"])
def test_plan_prepare_span_once_per_forward_built(setting):
    """Every forward built prepares the plan's operands once, in one
    ``plan.prepare`` span stating how many tables it built and the bytes
    they hold; calling the forward prepares nothing more. 64 rows a
    cluster, S 4, one block size (64 rows at 128 lanes): the sample,
    its run table and the weights, and on one device the features too."""
    import jax
    from repro.core import gnn
    plan = _fused_plan(setting)
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    params = gnn.init_params(jax.random.key(0), plan.gnn_config(cfg))
    tel.enable()
    for _ in range(2):
        fwd = plan.make_forward(cfg)
        for _ in range(2):
            jax.block_until_ready(fwd(params))
    spans = [r for r in tel.get_tracer().roots if r.name == "plan.prepare"]
    assert len(spans) == 2
    built = jax.tree.leaves(plan.forward_inputs(cfg)[
        0 if setting == "centralized" else 1:])
    for sp in spans:
        assert sp.attrs["tables"] == len(built)
        assert sp.attrs["bytes"] == sum(a.nbytes for a in built)
    if setting == "centralized":       # [1, 64, 1, 128] f32 features,
        #                                 [1, 1, 256] nbr, [1, 1, 2] runs,
        #                                 [1, 1, 64, 128] lane-padded weights
        assert spans[0].attrs == {"tables": 4, "bytes": 4 * (
            64 * 128 + 256 + 2 + 64 * 128)}


# ---- streaming server: observer isolation + counters --------------------

def _tiny_server(make_graph, policy="eager"):
    from repro.core import gnn
    from repro.core.partition import plan_execution
    from repro.streaming import StreamingGNNServer
    g = make_graph(30, 120, 8, seed=2)
    plan = plan_execution(g, "decentralized", backend="jnp", sample=4,
                          n_clusters=3)
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    srv = StreamingGNNServer(plan, cfg, policy=policy)
    srv.refresh()
    return g, srv


def _mutate(g, srv, rng, frac=0.2):
    n = max(int(g.n_nodes * frac), 1)
    nodes = rng.choice(g.n_nodes, n, replace=False)
    return srv.ingest(nodes=nodes,
                      rows=rng.normal(size=(n, 8)).astype(np.float32))


def test_observer_exception_is_isolated(make_graph, caplog):
    """A raising observer is logged and skipped — later observers still
    run and the commit itself succeeds (the ISSUE-9 bugfix)."""
    g, srv = _tiny_server(make_graph)
    calls = []

    def bad(server, update):
        raise RuntimeError("observer boom")

    def good(server, update):
        calls.append(update)

    srv.add_observer(bad)
    srv.add_observer(good)
    rng = np.random.default_rng(0)
    with caplog.at_level(logging.ERROR, logger="repro.streaming.server"):
        upd = _mutate(g, srv, rng)
    assert upd is not None                 # commit survived the bad observer
    assert calls == [upd]                  # later observer still notified
    assert any("observer" in r.message for r in caplog.records)

    assert srv.remove_observer(bad) is True
    assert srv.remove_observer(bad) is False   # already gone: no raise
    caplog.clear()
    with caplog.at_level(logging.ERROR, logger="repro.streaming.server"):
        _mutate(g, srv, rng)
    assert not caplog.records              # removed: nothing to isolate
    assert len(calls) == 2


def test_streaming_counters_and_spans(make_graph):
    tel.enable()
    g, srv = _tiny_server(make_graph)
    rng = np.random.default_rng(1)
    for _ in range(3):
        _mutate(g, srv, rng)
    snap = tel.snapshot()
    c = snap["counters"]
    assert c["server.commits"] == srv.commits == 4    # cold full + 3 ticks
    assert c["server.full_refreshes"] == srv.full_refreshes == 1
    assert c["streaming.rows_recomputed"] > 0
    assert c["streaming.rows_cached"] >= 0
    assert c["streaming.recompile_estimate"] >= 1
    assert 0.0 <= snap["gauges"]["streaming.dirty_fraction"] <= 1.0
    for name in ("server.commit", "server.ingest", "engine.full_refresh"):
        assert name in snap["spans"], name
    # span durations feed the histogram registry automatically
    assert 'span_seconds{span="server.commit"}' in snap["histograms"]


def test_query_histogram_via_gnn_server(make_graph):
    from repro.core import gnn
    from repro.core.partition import plan_execution
    from repro.launch.gnn import GNNServer
    tel.enable()
    g = make_graph(30, 120, 8, seed=3)
    plan = plan_execution(g, "centralized", backend="jnp", sample=4)
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(8,), out_dim=4, sample=4)
    srv = GNNServer(plan, cfg)
    srv.refresh()
    srv.query(np.arange(6))
    srv.query(np.arange(3))
    snap = tel.snapshot()
    assert snap["counters"]["server.queries"] == 9
    assert snap["spans"]["server.query"]["count"] == 2
    h = snap["histograms"]['span_seconds{span="server.query"}']
    assert h["count"] == 2 and h["p50"] <= h["p99"]


# ---- benchmark runner embedding -----------------------------------------

def test_run_one_embeds_telemetry_info():
    """Every bench record carries the run's telemetry snapshot under the
    record-level ``info`` key, which the determinism projection drops."""
    import types

    from benchmarks.run import canonical_metrics, run_one

    def fake_main():
        tel.counter("fake.hits").inc(7)
        with tel.span("fake.phase"):
            pass
        fake.METRICS.update(answer=42)
        return 0

    fake = types.SimpleNamespace(main=fake_main, METRICS={}, SMOKE_ARGV=[])
    rc, record = run_one("fake", fake, smoke=True)
    assert rc == 0 and record["metrics"]["answer"] == 42
    snap = record["info"]["telemetry"]
    assert snap["counters"]["fake.hits"] == 7.0
    assert "fake.phase" in snap["spans"]
    # info is volatile: two runs' canonical records agree regardless of it
    assert "info" not in canonical_metrics(record)
    assert not tel.enabled()               # run_one restored the off state
