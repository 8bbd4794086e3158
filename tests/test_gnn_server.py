"""GNNServer staleness contract: ``query`` must refresh whenever the
params/plan version moved, not only when embeddings were never computed
(the docstring always promised "refresh if stale"; it used to refresh only
on ``embeddings is None``)."""
import numpy as np
import jax
import pytest

from repro.core import gnn
from repro.core.graph import random_graph
from repro.core.partition import plan_execution
from repro.launch.gnn import GNNServer


def _server(seed=0, **plan_kw):
    g = random_graph(40, 200, 24, seed=seed).gcn_normalize()
    plan = plan_execution(g, plan_kw.pop("setting", "centralized"),
                          sample=4, **plan_kw)
    cfg = gnn.GNNConfig(in_dim=24, hidden_dims=(16,), out_dim=8, sample=4)
    return GNNServer(plan, cfg, seed=seed), cfg, g


def test_query_refreshes_on_param_update():
    srv, cfg, _ = _server()
    ids = np.arange(5)
    first = srv.query(ids).copy()
    assert srv.refreshes == 1
    # same version: queries serve the cached embeddings, no refresh
    srv.query(ids)
    assert srv.refreshes == 1 and not srv.stale
    # new params: stale -> next query refreshes and the embeddings move
    new_params = gnn.init_params(jax.random.key(123), srv.cfg)
    srv.update_params(new_params)
    assert srv.stale
    second = srv.query(ids)
    assert srv.refreshes == 2
    assert not np.allclose(first, second)


def test_query_refreshes_on_plan_update():
    srv, cfg, _ = _server()
    srv.query(np.arange(3))
    assert srv.refreshes == 1
    g2 = random_graph(40, 200, 24, seed=7).gcn_normalize()
    srv.update_plan(plan_execution(g2, "centralized", sample=4), cfg)
    assert srv.stale
    srv.query(np.arange(3))
    assert srv.refreshes == 2 and not srv.stale


def test_explicit_refresh_clears_staleness():
    srv, _, _ = _server()
    srv.update_params(srv.params)      # bump version before any serve
    srv.refresh()
    assert not srv.stale
    srv.query(np.arange(2))
    assert srv.refreshes == 1          # query reused the explicit refresh


def test_batched_query_handles_duplicates_and_shape():
    srv, _, g = _server()
    ids = np.array([3, 7, 3, 0, 7, 7])
    out = srv.query(ids)
    assert out.shape == (6, srv.cfg.out_dim)
    np.testing.assert_array_equal(out[0], out[2])      # duplicate rows agree
    np.testing.assert_array_equal(out[1], out[4])
    np.testing.assert_array_equal(out, srv.embeddings[ids])
    # nd batches keep their shape
    out2 = srv.query(ids.reshape(2, 3))
    assert out2.shape == (2, 3, srv.cfg.out_dim)
    np.testing.assert_array_equal(out2.reshape(6, -1), out)


def test_query_rejects_out_of_range_ids():
    srv, _, g = _server()
    with np.testing.assert_raises(IndexError):
        srv.query([0, g.n_nodes])                      # one past the end
    with np.testing.assert_raises(IndexError):
        srv.query([-1])
    assert srv.query(np.zeros(0, np.int64)).shape == (0, srv.cfg.out_dim)


def test_update_plan_to_different_node_count_swaps_staleness_domain():
    """After swapping to a smaller graph, the refreshed table serves the
    new node set and ids valid only in the old graph fail loudly."""
    srv, cfg, g = _server()
    srv.query([g.n_nodes - 1])
    g2 = random_graph(24, 120, 24, seed=11).gcn_normalize()
    srv.update_plan(plan_execution(g2, "centralized", sample=4), cfg)
    assert srv.stale
    out = srv.query(np.arange(24))                     # refresh on new graph
    assert out.shape == (24, cfg.out_dim) and srv.refreshes == 2
    with np.testing.assert_raises(IndexError):
        srv.query([g.n_nodes - 1])                     # old-domain id: 39


def test_lower_forward_takes_tables_as_arguments():
    """``lower_forward`` lowers the program ``make_forward`` runs, with the
    plan's tables as arguments of its ``main`` (the chip check reads
    tpu_custom_call from it) — and that program computes what the server
    serves. A centralized fused plan's ``main`` takes the features as the
    first launch reads them: zero-padded from 24 to 128 lanes and viewed by
    rows, ``[1, N, 1, 128]``; the other placements rebuild layer 1's table
    from the exchange and take the features as they are."""
    for setting in ("centralized", "decentralized", "semi"):
        srv, cfg, g = _server(setting=setting, backend="fused",
                              n_clusters=None if setting == "centralized"
                              else 2)
        srv.refresh()
        lowered = srv.plan.lower_forward(srv.params, srv.cfg)
        feats = ((1, g.n_nodes, 1, 128) if setting == "centralized"
                 else srv.plan.feats.shape)
        shape = "x".join(str(d) for d in feats)
        main = next(line for line in lowered.as_text().splitlines()
                    if "func.func public @main(" in line)
        assert f"tensor<{shape}xf32>" in main
        out = lowered.compile()(srv.params,
                                *srv.plan.forward_inputs(srv.cfg))
        np.testing.assert_array_equal(srv.plan.scatter(out), srv.embeddings)


def test_device_inputs_split_clusters_over_mesh():
    from jax.sharding import PartitionSpec
    from repro.launch.mesh import make_mesh
    srv, _, _ = _server(setting="decentralized", n_clusters=1)
    mesh = make_mesh((1,), ("data",))
    for arr in srv.plan.device_inputs(mesh):
        assert arr.sharding.spec == PartitionSpec("data")
        assert [s.device for s in arr.addressable_shards] == \
            list(mesh.devices.flat)


def test_scatter_puts_each_cluster_row_at_its_node():
    """``scatter`` of a dense per-cluster output is one gather; it places
    exactly what the masked per-cluster loop places, bit for bit."""
    rng = np.random.default_rng(0)
    for setting in ("decentralized", "semi"):
        srv, _, g = _server(setting=setting, n_clusters=3)
        part = srv.plan.part
        out = rng.normal(size=(*part.local_nodes.shape, 5)).astype(
            np.float32)
        want = np.full((g.n_nodes, 5), np.nan, np.float32)
        for c in range(part.n_clusters):
            m = part.local_mask[c]
            want[part.local_nodes[c][m]] = out[c][m]
        assert not np.isnan(want).any(), "every node is some cluster's row"
        np.testing.assert_array_equal(srv.plan.scatter(out), want)


def _raw_forward(srv, feats=None):
    """``gnn.forward`` of ``srv``'s config over the plan's raw tables."""
    import jax.numpy as jnp
    plan = srv.plan
    feats = plan.feats[0] if feats is None else feats
    return np.asarray(gnn.forward(srv.params, jnp.asarray(feats),
                                  jnp.asarray(plan.neighbors[0]),
                                  jnp.asarray(plan.weights[0]), srv.cfg))


def test_centralized_fused_forward_matches_gnn_forward():
    """The centralized fused plan's forward reads its prepared feature and
    sample tables, and returns ``gnn.forward`` over the raw tables bit for
    bit."""
    srv, _, _ = _server(backend="fused")
    feats, nbr, _ = srv.plan.forward_inputs(srv.cfg)
    assert feats.shape == (1, 40, 1, 128) and nbr.shape == (1, 40, 4)
    srv.refresh()
    np.testing.assert_array_equal(srv.embeddings, _raw_forward(srv))


@pytest.mark.parametrize("backend,ideal", [("fused", False),
                                           ("jnp", True), ("pallas", True)])
def test_raw_table_callers_are_unchanged(backend, ideal):
    """Off the ideal fused launch (bit-accurate numerics, the jnp and
    pallas backends) the forward takes the plan's tables as they are, and
    matches ``gnn.forward`` over them."""
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    numerics = (CrossbarNumerics(ideal=True) if ideal else CrossbarNumerics(
        in_bits=8, w_bits=8, adc_bits=12, rows_per_xbar=64))
    g = random_graph(40, 200, 24, seed=0).gcn_normalize()
    plan = plan_execution(g, "centralized", sample=4, backend=backend)
    cfg = gnn.GNNConfig(in_dim=24, hidden_dims=(16,), out_dim=8, sample=4,
                        numerics=numerics)
    srv = GNNServer(plan, cfg)
    ins = plan.forward_inputs(srv.cfg)
    assert [a.shape for a in ins] == [
        a.shape for a in (plan.feats, plan.neighbors, plan.weights)]
    srv.refresh()
    np.testing.assert_array_equal(srv.embeddings, _raw_forward(srv))


@pytest.mark.parametrize("how", ["stream", "update_plan"])
def test_forward_built_after_a_feature_update_reads_the_new_rows(how):
    """A forward is built from the plan's features as they are then: after
    a streaming commit (``_sync_plan_feats``) or ``update_plan``, the next
    forward built reads the new rows, bit for bit as ``gnn.forward`` over
    them; a forward built before keeps the features it prepared."""
    from repro.streaming import StreamingGNNServer
    g = random_graph(40, 200, 24, seed=0).gcn_normalize()
    plan = plan_execution(g, "centralized", sample=4, backend="fused")
    cfg = gnn.GNNConfig(in_dim=24, hidden_dims=(16,), out_dim=8, sample=4)
    if how == "update_plan":
        srv = GNNServer(plan, cfg)
        srv.refresh()
        old = srv.embeddings
        g2 = random_graph(40, 200, 24, seed=9).gcn_normalize()
        srv.update_plan(plan_execution(g2, "centralized", sample=4,
                                       backend="fused"), cfg)
        srv.refresh()
        np.testing.assert_array_equal(srv.embeddings, _raw_forward(srv))
        assert not np.array_equal(srv.embeddings, old)
        return
    srv = StreamingGNNServer(plan, cfg, policy="eager")
    before = plan.make_forward(srv.cfg)
    old = np.asarray(before(srv.params))[0]
    nodes = np.arange(0, 40, 3)
    rows = np.random.default_rng(3).normal(size=(len(nodes), 24))
    srv.ingest(nodes=nodes, rows=rows.astype(np.float32))
    new_feats = srv.engine.graph.features
    assert np.array_equal(plan.feats[0], new_feats)
    after = np.asarray(plan.make_forward(srv.cfg)(srv.params))[0]
    np.testing.assert_array_equal(after, _raw_forward(srv, new_feats))
    assert not np.array_equal(after, old)
    np.testing.assert_array_equal(np.asarray(before(srv.params))[0], old)
