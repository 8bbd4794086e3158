"""The work of one update (the ``gcn`` model kind's, ``bench/models/gcn.py``),
hand-checked for collab, and ``bench/work.py``'s per-device split against
the program's measured exchange tables."""
import numpy as np
import pytest

from bench import graphs, kinds, peaks, work

N, S, DIMS = 372_475, 8, (496, 64, 16)
GCN = kinds.load("models", "gcn")


def test_collab_layer_flops_by_hand():
    assert GCN.layer_flops(N, S, 496, 64) == (2_955_961_600.0,
                                              23_647_692_800.0)
    assert GCN.layer_flops(N, S, 64, 16) == (381_414_400.0, 762_828_800.0)
    assert GCN.model_flops(N, S, DIMS) == 27_747_897_600.0   # 2.77e10


def test_collab_compulsory_bytes_by_hand():
    # rows read once, tables (index + weight), W, output written once
    l1 = 4 * (N * 496 + N * S * 2 + 496 * 64 + N * 64)
    l2 = 4 * (N * 64 + N * S * 2 + 64 * 16 + N * 16)
    assert (l1, l2) == (858_309_376, 143_034_496)
    [[w1, w2]] = GCN.update_work(DIMS, S, [(N, 0)])
    assert (w1["bytes"], w2["bytes"]) == (l1, l2)
    assert w1["bytes"] + w2["bytes"] == pytest.approx(1.0e9, rel=2e-3)


def test_collab_roofline_is_memory_bound():
    [(secs, bound)] = work.ideal_seconds(GCN.update_work(DIMS, S, [(N, 0)]),
                                         peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert secs == pytest.approx((858_309_376 + 143_034_496) / 819e9)


def test_peaks_refuse_an_unknown_kind():
    with pytest.raises(ValueError, match="no peak table"):
        peaks.peaks_for("TPU v9 imaginary")


def test_device_split_matches_the_programs_exchange_tables():
    """Owned rows and halo rows received per device, counted from the
    benchmark's own sample table, equal the program's partition and its
    measured all-to-all rows on a small K=4 plan."""
    from repro.core import gnn
    from repro.core.graph import Graph
    from repro.core.partition import plan_execution
    n = 2_000
    g = graphs.csr(n, n * 66, structure_seed=3, min_in_degree=S - 1)
    ew, sl = GCN.graph_weights(g)
    x = np.zeros((n, 8), np.float32)
    plan = plan_execution(Graph(g["indptr"], g["indices"], ew, x, sl),
                          "decentralized", backend="jnp", sample=S,
                          n_clusters=4, seed=0)
    nbr, wts = graphs.sample_table(g, ew, sl, S)
    rows = work.device_rows(nbr, wts, plan.part.assignment, 4)
    owned = [o for o, _ in rows]
    halo = [h for _, h in rows]
    cfg = gnn.GNNConfig(in_dim=8, hidden_dims=(4,), out_dim=2, sample=S)
    rep = plan.measured_traffic(cfg, mode="alltoall")
    assert owned == plan.part.local_mask.sum(axis=1).tolist()
    assert halo == rep.tier1_rows.sum(axis=1).tolist()
    assert sum(halo) > 0
    per_dev = GCN.update_work((8, 4, 2), S, rows)
    assert sum(lw["flops"] for d in per_dev for lw in d) == \
        GCN.model_flops(n, S, (8, 4, 2))
    # layer-1 input rows per device: owned plus received
    assert [d[0]["bytes"] for d in per_dev] == [
        GCN.layer_bytes(o, o + h, S, 8, 4) for o, h in rows]
