"""GNNServer staleness contract: ``query`` must refresh whenever the
params/plan version moved, not only when embeddings were never computed
(the docstring always promised "refresh if stale"; it used to refresh only
on ``embeddings is None``)."""
import numpy as np
import jax

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
    plan's tables as arguments (the chip check reads tpu_custom_call from
    it) — and that program computes what the server serves."""
    for setting in ("centralized", "decentralized", "semi"):
        srv, cfg, g = _server(setting=setting, backend="fused",
                              n_clusters=None if setting == "centralized"
                              else 2)
        srv.refresh()
        lowered = srv.plan.lower_forward(srv.params, srv.cfg)
        feats = srv.plan.feats
        shape = "x".join(str(d) for d in feats.shape)
        assert f"tensor<{shape}xf32>" in lowered.as_text()
        out = lowered.compile()(srv.params, *srv.plan.device_inputs()[:3])
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
