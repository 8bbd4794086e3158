"""Fused GNN-layer kernel vs the composed aggregate -> crossbar_matmul path.

Tolerances: the ideal path runs the same f32 ops in the same order as the
composed path, so it is checked essentially exactly (atol 1e-5 for the
sequential-vs-einsum reduction order of the gather). The bit-accurate path
performs the identical integer-domain DAC/ADC math; the only divergence is
f32 summation order of the (integer-valued, lsb-scaled) partials, so
atol=1e-4 * full-scale-output, rtol=1e-4 covers it with margin.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core import gnn
from repro.kernels._gather import (block_rows, block_runs, chunk_rows,
                                  chunk_tables, gather_dmas)
from repro.kernels.crossbar_mvm import CrossbarNumerics
from repro.kernels.fused_layer import (fused_gnn_layer, fused_ideal_layer,
                                       fused_layer_ref, ideal_launch,
                                       prepare_sample, prepare_table)

QUANT = CrossbarNumerics(in_bits=8, w_bits=8, adc_bits=12, rows_per_xbar=64)


def _case(n, f, h, nd, s, seed=0, weight_sign=True):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, n, size=(nd, s)).astype(np.int32))
    wts = rng.normal(size=(nd, s)).astype(np.float32)
    if not weight_sign:
        wts = np.abs(wts)
    w = jnp.asarray(rng.normal(size=(f, h)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(h,)).astype(np.float32))
    return x, nbr, jnp.asarray(wts), w, b


def _check(x, nbr, wts, w, b, cfg, relu):
    ref = fused_layer_ref(x, nbr, wts, w, b, cfg, relu=relu)
    out = fused_gnn_layer(x, nbr, wts, w, b, cfg, relu=relu, bf=32)
    scale = float(jnp.abs(ref).max()) or 1.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("n,f,h,nd,s", [
    (20, 32, 16, 20, 4),       # aligned
    (23, 50, 17, 11, 5),       # odd shapes, Nd != N
    (7, 300, 33, 7, 1),        # F > rows_per_xbar (multi K-tile), S = 1
    (40, 16, 128, 40, 9),      # H > F
    (40, 496, 64, 100, 8),     # collab's F = 496, padded to 512 lanes
])
def test_matches_composed_ideal(n, f, h, nd, s, relu):
    x, nbr, wts, w, b = _case(n, f, h, nd, s, seed=n + f)
    _check(x, nbr, wts, w, b, CrossbarNumerics(ideal=True), relu)


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("n,f,h,nd,s", [
    (20, 32, 16, 20, 4),
    (23, 50, 17, 11, 5),
    (7, 130, 33, 7, 3),        # 130 -> three 64-row crossbars after padding
])
def test_matches_composed_quantized(n, f, h, nd, s, relu):
    x, nbr, wts, w, b = _case(n, f, h, nd, s, seed=n + f)
    _check(x, nbr, wts, w, b, QUANT, relu)


@pytest.mark.parametrize("rows,slots,width,block", [
    (4096, 8, 512, 256),       # collab layer 1: two 4 MiB buffers
    (4096, 8, 128, 1024),      # collab layer 2
    (4096, 8, 496, 256),       # lanes padded to 512
    (20, 8, 128, 24),          # one block: the rows rounded up to 8
    (6553, 5, 128, 1312),      # a divisor of 6,560 under the 1,632 cap
    (300, 8, 1024, 16),        # wide rows: small divisors only
])
def test_block_rows(rows, slots, width, block):
    assert block_rows(rows, slots, width) == block


# tables whose slots hold runs of consecutive rows: a self loop names the
# destination row's own table row, so their tables hold at least nd rows
RUN_TABLES = ("self", "self-ragged", "self-broken", "two-runs", "self-tail")


def _block_case(n, f, h, nd, s, seed, tables="random"):
    if tables in RUN_TABLES:
        n = max(n, nd)
    x, nbr, wts, w, b = _case(n, f, h, nd, s, seed=seed)
    rng = np.random.default_rng(seed)
    rows = np.arange(nd, dtype=np.int32)
    if tables == "padded":     # weight-0 padding slots naming row 0
        pad = rng.random((nd, s)) < 0.3
        nbr = jnp.where(pad, 0, nbr)
        wts = jnp.where(pad, 0.0, wts)
    if tables == "repeated":   # one source row named by half the slots
        nbr = nbr.at[:, : (s + 1) // 2].set(3 % n)
    if tables in ("self", "self-broken", "two-runs"):
        nbr = nbr.at[:, -1].set(rows)   # the self loop last, as built
    if tables == "self-broken":         # one row breaks its block's run
        nbr = nbr.at[nd // 2, -1].set((nd // 2 + 1) % n)
    if tables == "two-runs":            # a second run, which wraps once
        nbr = nbr.at[:, 1].set((rows + nd // 3) % n)
    if tables == "self-tail":           # the run ends at the table's end
        nbr = nbr.at[:, -1].set(rows + n - nd)
    if tables == "self-ragged":
        # the self loop at slot min(deg, S - 1), padding after it: rows of
        # degree under S - 1 in the first half only, so some blocks are
        # runs and some are not
        deg = np.full(nd, s - 1)
        low = (rows < nd // 2) & (rng.random(nd) < 0.05)
        deg[low] = rng.integers(0, s - 1, int(low.sum()))
        slot = np.arange(s)[None, :]
        t = np.where(slot < deg[:, None], np.asarray(nbr), 0)
        t[rows, deg] = rows
        nbr = jnp.asarray(t)
        wts = jnp.where(jnp.asarray(slot <= deg[:, None]), wts, 0.0)
    return x, nbr, wts, w, b


@pytest.mark.parametrize("nd,s,f,tables", [
    (300, 8, 1024, "random"), (304, 8, 1024, "self"),
    (300, 8, 1024, "self"), (300, 8, 1024, "self-ragged"),
    (300, 8, 1024, "self-broken"), (300, 8, 1024, "two-runs"),
    (288, 8, 1024, "self-tail"), (4500, 8, 128, "self"),
    (300, 8, 1024, "padded")])
def test_block_runs_numpy_matches_jnp(nd, s, f, tables):
    """The run rule gives the same table on numpy and jnp, finds the runs
    each kind of table was built to hold, and ``gather_dmas`` counts one
    block DMA for each in place of its rows' DMAs."""
    x, nbr, *_ = _block_case(300, f, 8, nd, s, nd, tables)
    n = x.shape[0]
    rows = chunk_rows(nd, s)
    block = block_rows(rows, s, f)
    padded = -(-rows // block) * block
    t = chunk_tables(np.asarray(nbr), rows, padded)
    runs = block_runs(t, block, n)
    np.testing.assert_array_equal(
        np.asarray(block_runs(chunk_tables(nbr, rows, padded), block, n)),
        runs)
    slot, start = runs[..., 0], runs[..., 1]
    c, j = np.indices(slot.shape)
    end = (j + 1) * block
    full = (end <= rows) & (c * rows + end <= nd)   # no padding row
    if tables in ("random", "padded"):
        assert (slot == -1).all()
    if tables in ("self", "self-tail"):
        np.testing.assert_array_equal(slot, np.where(full, s - 1, -1))
    if tables == "self-tail":
        assert start[full].max() + block == n
    if tables == "self-ragged":
        assert 0 < (slot >= 0).sum() < full.sum()
    if tables == "self-broken":
        assert (slot >= 0).sum() == full.sum() - 1
    if tables == "two-runs":            # the first run slot is taken
        assert set(slot[full].tolist()) == {1, s - 1}
    n_runs = int((slot >= 0).sum())
    assert gather_dmas(np.asarray(nbr), n, f) == (t.size - n_runs * block,
                                                  n_runs)


def test_gather_dmas_collab_chunking():
    """A self loop in the last slot of every row: one block DMA per block
    with no padding row, for each width's block size."""
    nd, s, n = 9000, 8, 9000
    nbr = np.zeros((nd, s), np.int32)
    nbr[:, -1] = np.arange(nd)
    # 3 chunks of 4,096 rows: 512-lane rows take blocks of 256, 128-lane
    # rows blocks of 1,024; the last chunk holds 808 rows
    assert gather_dmas(nbr, n, 496) == (3 * 4096 * s - 35 * 256, 35)
    assert gather_dmas(nbr, n, 64) == (3 * 4096 * s - 8 * 1024, 8)


@pytest.mark.parametrize("n,f,h,nd,s,tables", [
    (50, 128, 128, 203, 8, "random"),    # nd not a multiple of the block
    (300, 1024, 16, 300, 8, "random"),   # several blocks in one launch
    (30, 128, 64, 5, 8, "random"),       # nd smaller than one block
    (300, 128, 128, 4500, 8, "random"),  # more than one TABLE_ENTRIES chunk
    (40, 256, 32, 64, 8, "repeated"),    # one row named by several slots
    (4, 128, 16, 24, 6, "random"),       # four rows for all slots of a block
    (60, 128, 32, 70, 8, "padded"),      # weight-0 padding slots
    (30, 128, 128, 17, 1, "random"),     # S = 1
    (304, 1024, 16, 304, 8, "self"),     # the self loop: every block a run
    (300, 1024, 16, 300, 8, "self"),     # a padded last block: no run
    (300, 1024, 16, 300, 8, "self-ragged"),  # runs in some blocks only
    (300, 1024, 16, 300, 8, "self-broken"),  # one row breaks one run
    (300, 1024, 16, 300, 8, "two-runs"),     # runs in two slots
    (300, 1024, 16, 288, 8, "self-tail"),    # a run ends at the last row
    (300, 128, 32, 4500, 8, "self"),     # runs in more than one chunk
])
def test_block_gather_matches_ref(n, f, h, nd, s, tables):
    x, nbr, wts, w, b = _block_case(n, f, h, nd, s, n + nd, tables)
    for relu in (False, True):
        ref = fused_layer_ref(x, nbr, wts, w, b, relu=relu)
        out = fused_ideal_layer(x, nbr, wts, w, b, relu=relu)
        scale = float(jnp.abs(ref).max()) or 1.0
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4 * scale)


@jax.jit
def _slot_order_layer(x, nbr, wts, w):
    z = jnp.zeros((nbr.shape[0], x.shape[1]), jnp.float32)
    for k in range(nbr.shape[1]):
        z = z + wts[:, k:k + 1] * x[nbr[:, k]]
    return jnp.dot(z, w, preferred_element_type=jnp.float32)


@pytest.mark.parametrize("nd,s,tables", [
    (203, 8, "random"), (4500, 8, "padded"), (9, 3, "repeated"),
    (2048, 8, "self"), (2040, 8, "self"), (2048, 8, "self-ragged"),
    (2048, 8, "self-broken"), (2048, 8, "two-runs"),
    (2048, 8, "self-tail")])
def test_block_gather_z_bit_exact(nd, s, tables):
    """Z is each row's float32 sum over its slots in slot order, bit for
    bit: with W = I and no bias the layer returns Z itself. The reference
    is jitted, as the kernel is, so both sums compile alike."""
    f = 128
    x, nbr, wts, _, _ = _block_case(64, f, f, nd, s, nd, tables)
    eye = jnp.eye(f, dtype=jnp.float32)
    want = _slot_order_layer(x, nbr, wts, eye)
    got = fused_ideal_layer(x, nbr, wts, eye, jnp.zeros((f,)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("prepared", ["table", "sample", "both"])
@pytest.mark.parametrize("n,f,h,nd,s,tables", [
    (300, 496, 64, 300, 8, "self"),      # F 496 -> 512 lanes; runs, and
    #                                      a padded last block without one
    (300, 64, 16, 300, 8, "self-ragged"),    # F 64 -> 128; runs in some blocks
    (300, 128, 32, 4500, 8, "self"),     # Nd over one chunk, the last short
    (60, 200, 24, 70, 8, "padded"),      # no run slot anywhere
])
def test_prepared_operands_match_raw(n, f, h, nd, s, tables, prepared):
    """``fused_gnn_layer`` reads ``prepare_table``'s row view and
    ``prepare_sample``'s tables, each built by ``ideal_launch``'s sizes,
    as they are, and returns the raw path's output bit for bit, with and
    without an activation."""
    x, nbr, wts, w, b = _block_case(n, f, h, nd, s, n + nd, tables)
    k_pad, block = ideal_launch(x.shape[0], nbr.shape, f, h)
    xp, nbrp, wtsp = x, nbr, wts
    if prepared in ("table", "both"):
        xp = prepare_table(x, k_pad)
        assert xp.shape == (x.shape[0], 1, k_pad)
    if prepared in ("sample", "both"):
        nbrp, wtsp = prepare_sample(nbr, wts, x.shape[0], (block,))
        assert nbrp.shape == nbr.shape
    for relu in (False, True):
        want = fused_gnn_layer(x, nbr, wts, w, b, relu=relu)
        got = fused_gnn_layer(xp, nbrp, wtsp, w, b, relu=relu)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prepared_operands_refuse_what_they_do_not_fit():
    """A sample prepared for another table or block size, a row view of
    other lanes, and prepared operands on the bit-accurate path raise
    instead of gathering the wrong rows."""
    x, nbr, wts, w, b = _block_case(300, 496, 64, 300, 8, 0, "self")
    k_pad, block = ideal_launch(300, nbr.shape, 496, 64)
    nbrp, wtsp = prepare_sample(nbr, wts, 300, (block,))
    with pytest.raises(ValueError, match="prepared for a 300-row table"):
        fused_gnn_layer(x[:299], nbrp, wtsp, w, b)
    with pytest.raises(ValueError, match="blocks of"):
        fused_gnn_layer(x[:, :64], nbrp, wtsp, w[:64], b)
    with pytest.raises(ValueError, match="lanes"):
        fused_gnn_layer(prepare_table(x, 1024), nbr, wts, w, b)
    with pytest.raises(ValueError, match="bit-accurate"):
        fused_gnn_layer(prepare_table(x, k_pad), nbr, wts, w, b, QUANT,
                        bf=128)


def test_signed_activations_quantized():
    """Negative Z exercises the neg-DAC pass + its separate global scale."""
    x, nbr, wts, w, b = _case(16, 48, 8, 16, 6, seed=3, weight_sign=True)
    _check(x, nbr, wts, w, b, QUANT, relu=False)


def test_zero_degree_nodes():
    """All-zero edge weights (zero-degree / fully padded rows) must yield
    exactly act(b) on both numerics paths."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(12, 32)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, 12, size=(5, 4)).astype(np.int32))
    wts = jnp.zeros((5, 4), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    for cfg in (CrossbarNumerics(ideal=True), QUANT):
        out = fused_gnn_layer(x, nbr, wts, w, b, cfg, relu=True, bf=32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.tile(np.maximum(np.asarray(b), 0),
                                           (5, 1)), atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 30), f=st.sampled_from([8, 48, 100]),
       h=st.sampled_from([4, 24]), s=st.integers(1, 8),
       ideal=st.booleans(), seed=st.integers(0, 2**31 - 1))
def test_property_fused_composed_equivalence(n, f, h, s, ideal, seed):
    x, nbr, wts, w, b = _case(n, f, h, n, s, seed=seed)
    cfg = CrossbarNumerics(ideal=True) if ideal else QUANT
    _check(x, nbr, wts, w, b, cfg, relu=bool(seed % 2))


def test_gnn_forward_backend_dispatch(backend, make_graph):
    """GNNConfig(backend=...) routes each backend of the shared conftest
    axis through its kernel path and agrees with the jnp composed oracle
    for both numerics (the grid that used to be a fused-only loop)."""
    import dataclasses
    g = make_graph(30, 150, 16, seed=6)
    nbr, wts = g.neighbor_sample(8)
    args = (jnp.asarray(g.features), jnp.asarray(nbr), jnp.asarray(wts))
    for numerics in (CrossbarNumerics(ideal=True), QUANT):
        cfg = gnn.GNNConfig(in_dim=16, hidden_dims=(24,), out_dim=5,
                            sample=8, numerics=numerics)
        params = gnn.init_params(jax.random.key(1), cfg)
        ref = np.asarray(gnn.forward(params, *args, cfg))
        got = np.asarray(gnn.forward(
            params, *args, dataclasses.replace(cfg, backend=backend)))
        scale = np.abs(ref).max() or 1.0
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4 * scale)
