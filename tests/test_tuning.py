"""repro.tuning: the roofline-guided kernel autotuner (DESIGN.md §11).

Contracts under test:
  * candidate enumeration and roofline pruning are deterministic pure
    functions of the geometry (default always survives);
  * with a deterministic measure_fn the winner and the serialized cache
    are byte-identical across runs of the same (geometry, platform, seed);
  * a cache hit answers without re-measuring;
  * tuned configs never change numerics — forward outputs are
    bit-identical to the hand-picked defaults on every backend;
  * ExecutionPlan.tune_kernels threads the bundle into the serving path.
"""
import numpy as np
import pytest

from repro.tuning import (AggregateConfig, AggregateGeometry, CrossbarConfig,
                          CrossbarGeometry, FusedConfig, FusedGeometry,
                          TuneCache, TunedKernels, candidates,
                          current_platform, default_config, launch_cost,
                          prune, registry, tune)


@pytest.fixture(autouse=True)
def _clean_registry():
    registry.clear()
    yield
    registry.clear()


XGEOM = CrossbarGeometry(m=40, k=700, n=64, rows_per_xbar=128)
FGEOM = FusedGeometry(nd=40, n=40, f_in=12, f_out=16, sample=8)


# ---- candidate space + pruning determinism --------------------------------

def test_candidates_default_first_unique_and_legal():
    for geom in (XGEOM, FGEOM):
        cands = candidates(geom)
        assert cands[0] == default_config(geom)
        assert len(set(cands)) == len(cands)
        assert cands == candidates(geom)           # deterministic
    # depth must divide the physical crossbar count (k=700 @128 -> n_k=6)
    assert all(XGEOM.n_k % c.depth == 0 for c in candidates(XGEOM))


def test_prune_deterministic_and_keeps_default():
    for geom in (XGEOM, FGEOM):
        a, b = prune(geom), prune(geom)
        assert a == b
        assert any(c == default_config(geom) for c, _ in a)
        assert len(a) <= 4 + 1                     # max_survivors (+default)
        bounds = [bd for _, bd in a if True]
        assert all(bd > 0 for bd in bounds)


def test_prune_bounds_sorted_and_slack_filtered():
    survivors = prune(XGEOM, slack=2.0, max_survivors=16)
    bounds = [b for _, b in survivors]
    # default may be appended out of order at the end; the rest is sorted
    body = bounds[:-1] if survivors[-1][0] == default_config(XGEOM) \
        else bounds
    assert body == sorted(body)
    assert all(b <= 2.0 * min(bounds) for b in body)


def test_launch_cost_scales_with_geometry():
    c = CrossbarConfig(bm=8, bn=128, depth=1)   # bm | m: no padding slack
    small = launch_cost(XGEOM, c)
    big = launch_cost(CrossbarGeometry(m=80, k=700, n=64,
                                       rows_per_xbar=128), c)
    assert big.flops == 2 * small.flops
    assert big.hbm_bytes > small.hbm_bytes
    assert small.vmem_bytes > 0 and small.grid_steps >= 1


# ---- tune(): determinism, caching, registry -------------------------------

def _fake_measure():
    """Deterministic measure_fn preferring large bn then large bm/bf,
    counting invocations."""
    calls = []

    def fn(geom, config):
        calls.append(config)
        d = config.as_dict()
        return 1.0 / (1 + sum(d.values()))
    return fn, calls


def test_tune_deterministic_cache_bytes():
    dumps = []
    for _ in range(2):
        cache = TuneCache()
        fn, _ = _fake_measure()
        winner, info = tune(XGEOM, cache=cache, seed=3, measure_fn=fn,
                            register_result=False)
        assert not info["cached"]
        dumps.append(cache.dumps())
    assert dumps[0] == dumps[1]                   # byte-identical
    assert f'"{current_platform()}"' in dumps[0]


def test_tune_winner_never_slower_than_default():
    fn, _ = _fake_measure()
    winner, info = tune(FGEOM, measure_fn=fn, register_result=False)
    assert info["winner_s"] <= info["default_s"]
    assert any(c == default_config(FGEOM).as_dict()
               for c, _ in info["measured"])


def test_cache_hit_skips_measurement(tmp_path):
    path = str(tmp_path / "tuned.json")
    cache = TuneCache(path)
    fn, calls = _fake_measure()
    w1, info1 = tune(XGEOM, cache=cache, measure_fn=fn)
    n_measured = len(calls)
    assert n_measured == info1["n_candidates"] > 0

    # same cache object and a reloaded one: no new measurements
    w2, info2 = tune(XGEOM, cache=cache, measure_fn=fn)
    w3, info3 = tune(XGEOM, cache=TuneCache.load(path), measure_fn=fn)
    assert info2["cached"] and info3["cached"]
    assert (w1, w1) == (w2, w3)
    assert len(calls) == n_measured
    # force=True re-measures
    _, info4 = tune(XGEOM, cache=cache, measure_fn=fn, force=True)
    assert not info4["cached"] and len(calls) == 2 * n_measured


def test_tune_registers_winner_for_eager_resolution():
    fn, _ = _fake_measure()
    winner, _ = tune(FGEOM, measure_fn=fn)
    assert registry.lookup(FGEOM.key()) == winner
    assert registry.lookup(XGEOM.key()) is None


def test_registry_activate_from_cache(tmp_path):
    path = str(tmp_path / "tuned.json")
    cache = TuneCache(path)
    fn, _ = _fake_measure()
    winner, _ = tune(FGEOM, cache=cache, measure_fn=fn,
                     register_result=False)
    assert registry.lookup(FGEOM.key()) is None
    n = registry.activate(TuneCache.load(path))
    assert n == 1 and registry.lookup(FGEOM.key()) == winner


def test_tuned_kernels_bundle_is_hashable_and_ordered():
    a = TunedKernels.of({FGEOM.key(): FusedConfig(256),
                         XGEOM.key(): CrossbarConfig(64, 256, 2)})
    b = TunedKernels.of({XGEOM.key(): CrossbarConfig(64, 256, 2),
                         FGEOM.key(): FusedConfig(256)})
    assert a == b and hash(a) == hash(b)          # insertion-order free
    assert a.lookup(FGEOM.key()) == FusedConfig(256)
    assert a.lookup(("nope",)) is None
    merged = a.merged(TunedKernels.of({FGEOM.key(): FusedConfig(512)}))
    assert merged.lookup(FGEOM.key()) == FusedConfig(512)
    assert len(merged) == 2


# ---- numerics invariance: tuned == default, bit for bit -------------------

def test_forward_bit_identical_with_tuned_configs(backend, make_graph):
    """A non-default tuned bundle threaded through GNNConfig.tuned must
    not change a single output bit on any backend — block sizes only move
    zero padding, depth only regroups whole-crossbar accumulation."""
    import dataclasses
    import jax
    from repro.core import gnn

    g = make_graph(n=40, e=200, f=12, seed=1)
    nbr, wts = g.neighbor_sample(8)
    cfg = gnn.GNNConfig(in_dim=12, hidden_dims=(16,), out_dim=4, sample=8,
                        backend=backend)
    params = gnn.init_params(jax.random.key(0), cfg)
    x = np.asarray(g.features, np.float32)
    ref = np.asarray(gnn.forward(params, x, nbr, wts, cfg))

    geoms = [FusedGeometry(nd=40, n=40, f_in=f_in, f_out=f_out, sample=8,
                           ideal=True, rows_per_xbar=512)
             for f_in, f_out in zip(cfg.dims[:-1], cfg.dims[1:])]
    tuned = TunedKernels.of({gm.key(): FusedConfig(256) for gm in geoms})
    out = np.asarray(gnn.forward(params, x, nbr, wts,
                                 dataclasses.replace(cfg, tuned=tuned)))
    assert np.array_equal(ref, out)


def test_crossbar_kernel_bit_identical_across_depth_and_blocks():
    """The quantized crossbar kernel at tuned (bm, bn, depth) equals the
    default launch bit for bit (the ADC stays per physical crossbar)."""
    import jax.numpy as jnp
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    from repro.kernels.crossbar_mvm.ops import crossbar_matmul

    rng = np.random.default_rng(0)
    x = jnp.asarray(np.abs(rng.normal(size=(24, 700))).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(700, 48)).astype(np.float32))
    cfg = CrossbarNumerics(rows_per_xbar=128)
    ref = np.asarray(crossbar_matmul(x, w, cfg, bm=8, bn=8, interpret=True))
    for bm, bn, depth in ((8, 16, 2), (16, 8, 3), (8, 8, 6)):
        got = np.asarray(crossbar_matmul(x, w, cfg, bm=bm, bn=bn,
                                         depth=depth, interpret=True))
        assert np.array_equal(ref, got), (bm, bn, depth)


def test_aggregate_kernel_bit_identical_across_bf():
    """The pallas csr_aggregate at any tuned feature-block width equals
    the default launch bit for bit — bf only moves zero padding, the
    per-slot accumulation order is unchanged."""
    from repro.kernels.csr_aggregate import aggregate

    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 24)).astype(np.float32)
    nbr = rng.integers(0, 40, size=(40, 6)).astype(np.int32)
    wts = np.abs(rng.normal(size=(40, 6))).astype(np.float32)
    ref = np.asarray(aggregate(x, nbr, wts, backend="pallas"))
    for bf in (8, 16, 64, 256):
        got = np.asarray(aggregate(x, nbr, wts, backend="pallas", bf=bf))
        assert np.array_equal(ref, got), bf
    # and through GNNConfig.tuned (the jit-threaded resolution path)
    geom = AggregateGeometry(nd=40, n=40, f=24, sample=6)
    tuned = TunedKernels.of({geom.key(): AggregateConfig(16)})
    got = np.asarray(aggregate(x, nbr, wts, backend="pallas", tuned=tuned))
    assert np.array_equal(ref, got)


def test_crossbar_depth_must_divide_crossbar_count():
    import jax.numpy as jnp
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    from repro.kernels.crossbar_mvm.crossbar_mvm import \
        crossbar_matmul_quantized

    xq = jnp.zeros((8, 256), jnp.int32)
    wq = jnp.zeros((256, 128), jnp.float32)
    with pytest.raises(ValueError, match="depth 3 must divide"):
        crossbar_matmul_quantized(xq, wq, CrossbarNumerics(rows_per_xbar=128),
                                  bm=8, bn=128, depth=3, interpret=True)


def test_ops_resolve_through_registry_eagerly():
    """With no explicit block args, the ops wrapper picks up a registry
    entry registered *after* a previous call — no stale-trace capture."""
    import jax.numpy as jnp
    from repro.kernels.fused_layer import fused_gnn_layer
    from repro.kernels.crossbar_mvm import CrossbarNumerics

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(12, 20)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, 12, size=(12, 4)).astype(np.int32))
    wts = jnp.asarray(np.abs(rng.normal(size=(12, 4))).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(20, 8)).astype(np.float32))
    b = jnp.zeros((8,), jnp.float32)
    cfg = CrossbarNumerics(ideal=True)
    ref = np.asarray(fused_gnn_layer(x, nbr, wts, w, b, cfg))
    geom = FusedGeometry(nd=12, n=12, f_in=20, f_out=8, sample=4,
                         ideal=True, rows_per_xbar=cfg.rows_per_xbar)
    registry.register(geom.key(), FusedConfig(256))
    out = np.asarray(fused_gnn_layer(x, nbr, wts, w, b, cfg))
    assert np.array_equal(ref, out)               # still bit-identical


# ---- plan integration -----------------------------------------------------

def test_execution_plan_tune_kernels_end_to_end(tmp_path, make_graph):
    import jax
    from repro.core import gnn
    from repro.core.partition import plan_execution

    g = make_graph(n=30, e=120, f=10, seed=2)
    plan = plan_execution(g, "decentralized", backend="fused", sample=4,
                          n_clusters=2)
    cfg = gnn.GNNConfig(in_dim=10, hidden_dims=(8,), out_dim=4, sample=4)
    fn, _ = _fake_measure()
    cache = TuneCache(str(tmp_path / "tuned.json"))
    tuned = plan.tune_kernels(cfg, cache=cache, measure_fn=fn)
    assert len(tuned) == len(cfg.dims) - 1        # one geometry per layer
    assert plan.tuned is tuned
    assert plan.gnn_config(cfg).tuned == tuned
    # the tuned forward still matches the untuned one bit for bit
    params = gnn.init_params(jax.random.key(0), plan.gnn_config(cfg))
    out_tuned = np.asarray(plan.make_forward(cfg)(params))
    plan.tuned = None
    registry.clear()
    out_plain = np.asarray(plan.make_forward(cfg)(params))
    assert np.array_equal(out_tuned, out_plain)


def test_plan_geometries_per_backend(make_graph):
    from repro.core import gnn
    from repro.core.partition import plan_execution
    from repro.tuning import AggregateGeometry, plan_geometries

    g = make_graph(n=20, e=80, f=6, seed=0)
    cfg = gnn.GNNConfig(in_dim=6, hidden_dims=(8,), out_dim=4, sample=4)
    # jnp is pure XLA: nothing tunable
    plan = plan_execution(g, "centralized", backend="jnp", sample=4)
    assert plan_geometries(plan, plan.gnn_config(cfg)) == []
    assert len(plan.tune_kernels(cfg)) == 0
    # composed pallas launches the standalone aggregation kernel per layer
    plan = plan_execution(g, "centralized", backend="pallas", sample=4)
    geoms = plan_geometries(plan, plan.gnn_config(cfg))
    assert len(geoms) == len(cfg.dims) - 1
    assert all(isinstance(gm, AggregateGeometry) for gm in geoms)
    assert [gm.f for gm in geoms] == [6, 8]
    assert all(gm.nd == g.n_nodes and gm.sample == 4 for gm in geoms)


def test_plan_geometries_bucketed_one_shape_per_bucket(make_graph):
    from repro.core import gnn
    from repro.core.partition import plan_execution
    from repro.tuning import plan_geometries

    g = make_graph(n=40, e=200, f=6, seed=1)
    cfg = gnn.GNNConfig(in_dim=6, hidden_dims=(8,), out_dim=4, sample=4)
    plan = plan_execution(g, "decentralized", backend="fused", sample=4,
                          n_clusters=4, buckets="auto")
    geoms = plan_geometries(plan, plan.gnn_config(cfg))
    bp = plan.bucketed
    shapes = {(bp.n_caps[b], bp.n_caps[b] + bp.h_caps[b], bp.s_caps[b])
              for b in range(bp.n_buckets)}
    assert len(geoms) == len(shapes) * (len(cfg.dims) - 1)
    assert {(gm.nd, gm.n, gm.sample) for gm in geoms} == shapes
