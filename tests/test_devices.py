"""Device-technology subsystem (DESIGN.md §13): bank registry, anchor
bit-exactness through the mapper, Monte-Carlo variation determinism across
backends, host calibration round-trip + staleness, and the planner's
technology axis (mixed-tier frontier, noise-tolerance rejection)."""
import dataclasses
import json

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core import costmodel
from repro.core.graph import TAXI_STATS
from repro.devices import (ANCHOR, CalibrationStaleError, HostCalibration,
                           NOISE_GRID, UnknownTechnologyError,
                           known_technologies, load_calibration,
                           modeled_p99_error, mvm_error_bounds,
                           noisy_forward, primitive_scales, resolve_technology,
                           sample_conductance_noise, save_calibration,
                           technology_table)
from repro.devices.params import SOT_MRAM, TechnologyParams
from repro.kernels.crossbar_mvm import CrossbarNumerics
from repro.mapper.compile import compile_mapping
from repro.planner import WorkloadProfile, plan

DIMS = (max(TAXI_STATS.feature_len, 1), 128)
TECHS = ("sot-mram", "reram", "sram", "fefet")
PAIR = ("reram", "sram")


# ------------------------------------------------------------- bank

def test_registry_contents():
    names = known_technologies()
    assert len(names) >= 4 and set(TECHS) <= set(names)
    for name in names:
        t = resolve_technology(name)
        assert t.name == name
        assert t.read_latency_s > 0 and t.read_energy_j > 0
    # a record resolves to itself (ad-hoc records need no registration)
    assert resolve_technology(SOT_MRAM) is SOT_MRAM
    assert {r["name"] for r in technology_table()} == set(names)


def test_unknown_technology_error_names_the_registry():
    with pytest.raises(UnknownTechnologyError, match="sot-mram.*reram"):
        resolve_technology("nvmeee")
    err = pytest.raises(UnknownTechnologyError,
                        resolve_technology, "nvmeee").value
    assert err.name == "nvmeee" and set(TECHS) <= set(err.known)


def test_compile_mapping_unknown_technology_is_named():
    # the regression the satellite asks for: a typo'd --tech fails with the
    # named registry error before any latency rollup
    with pytest.raises(UnknownTechnologyError, match="registered"):
        compile_mapping(DIMS, TAXI_STATS, technology="sot_mram")


def test_anchor_scales_are_exact_identity():
    assert primitive_scales(ANCHOR) == (1.0, 1.0)
    lat, ene = primitive_scales("reram")
    assert lat > 1.0 and ene < 1.0        # slower reads, cheaper reads


def test_anchor_compile_is_bit_identical():
    for setting in ("centralized", "decentralized", "semi"):
        base = compile_mapping(DIMS, TAXI_STATS, setting=setting,
                               n_clusters=16)
        anch = compile_mapping(DIMS, TAXI_STATS, setting=setting,
                               n_clusters=16, technology=ANCHOR)
        assert anch.t_compute == base.t_compute      # ==, not allclose
        assert anch.energy_j == base.energy_j
        assert base.technology == anch.technology == ANCHOR


def test_technology_scales_latency_and_energy():
    base = compile_mapping(DIMS, TAXI_STATS)
    reram = compile_mapping(DIMS, TAXI_STATS, technology="reram")
    sram = compile_mapping(DIMS, TAXI_STATS, technology="sram")
    assert reram.t_compute > base.t_compute > sram.t_compute
    assert sram.energy_j > base.energy_j > reram.energy_j
    assert reram.technology == "reram"


def test_calibrated_mode_rejects_technology():
    with pytest.raises(ValueError, match="derived"):
        costmodel.predict("centralized", TAXI_STATS, technology="reram")


# ------------------------------------------------------------- variation

def test_noise_draws_are_grid_quantized_and_seeded():
    nz = sample_conductance_noise(7, (16, 8), "reram")
    assert nz.shape == (16, 8) and nz.dtype == np.float32
    assert np.array_equal(nz * NOISE_GRID, np.round(nz * NOISE_GRID))
    assert np.array_equal(nz, sample_conductance_noise(7, (16, 8), "reram"))
    assert not np.array_equal(
        nz, sample_conductance_noise(8, (16, 8), "reram"))
    assert np.all(sample_conductance_noise(7, (16, 8), "sram") == 0.0)


BOUNDS_KW = dict(m=8, k=64, n=16, trials=4, seed=0)


def test_bounds_byte_identical_across_exact_backends():
    # jnp and pallas share the oracle crossbar stage bit-for-bit; the same
    # seed must therefore produce byte-identical *bounds*, not just close
    jnp_b = mvm_error_bounds("reram", **BOUNDS_KW, backend="jnp")
    pal_b = mvm_error_bounds("reram", **BOUNDS_KW, backend="pallas")
    assert jnp_b == pal_b                      # dataclass field equality
    assert jnp_b.mean_err > 0 and jnp_b.p99_err >= jnp_b.mean_err


def test_bounds_seed_deterministic_rerun():
    for backend in ("jnp", "pallas"):
        a = mvm_error_bounds("fefet", **BOUNDS_KW, backend=backend)
        b = mvm_error_bounds("fefet", **BOUNDS_KW, backend=backend)
        assert a == b


def test_sram_zero_noise_is_exactly_clean():
    b = mvm_error_bounds("sram", **BOUNDS_KW)
    assert b.mean_err == 0.0 and b.p99_err == 0.0 and b.ci95 == 0.0


def test_noisy_forward_without_noise_is_forward(backend, make_graph):
    """With every layer's noise ``None``, the noisy forward is
    ``gnn.forward`` to the bit on each backend: both run
    ``gnn.layer_step`` over the same layer loop."""
    import jax
    import jax.numpy as jnp
    from repro.core import gnn
    g = make_graph(30, 150, 16, seed=4)
    nbr, wts = g.neighbor_sample(8)
    args = (jnp.asarray(g.features), jnp.asarray(nbr), jnp.asarray(wts))
    cfg = gnn.GNNConfig(in_dim=16, hidden_dims=(24,), out_dim=5, sample=8,
                        numerics=CrossbarNumerics(), backend=backend)
    params = gnn.init_params(jax.random.key(2), cfg)
    want = np.asarray(gnn.forward(params, *args, cfg))
    got = np.asarray(noisy_forward(params, *args, cfg, [None] * len(params)))
    np.testing.assert_array_equal(got, want)


def test_bounds_monotone_in_sigma():
    errs = {t: mvm_error_bounds(t, **BOUNDS_KW).mean_err for t in TECHS}
    order = sorted(TECHS, key=lambda t: resolve_technology(t).noise_sigma)
    vals = [errs[t] for t in order]
    assert vals == sorted(vals)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=1, max_value=2 ** 20))
def test_different_seeds_agree_within_ci(seed):
    # a different-seed rerun estimates the same population mean: the two
    # bounds must agree within their combined confidence intervals
    ref = mvm_error_bounds("reram", m=8, k=64, n=16, trials=6, seed=0)
    other = mvm_error_bounds("reram", m=8, k=64, n=16, trials=6, seed=seed)
    assert ref.within_ci(other)
    assert other.seed == seed


def test_modeled_p99_error_shape():
    assert modeled_p99_error("sram", 128) == 0.0
    assert modeled_p99_error("reram", 128) > modeled_p99_error("fefet", 128)
    # more active rows average more noise away per line
    cfg = CrossbarNumerics()
    assert modeled_p99_error("reram", 8, cfg) > \
        modeled_p99_error("reram", cfg.rows_per_xbar, cfg)


# ------------------------------------------------------------- calibration

def test_calibration_roundtrip_and_staleness(tmp_path):
    from repro.tuning import current_platform
    path = str(tmp_path / "cal.json")
    cal = HostCalibration(platform=current_platform(), t_cam=1e-4,
                          t_agg=2e-3, t_fx=3e-4)
    save_calibration(cal, path)
    assert load_calibration(path) == cal           # strict: platform match
    stale = dataclasses.replace(cal, platform="tpu")
    with open(path, "w") as f:
        json.dump(stale.as_dict(), f)
    with pytest.raises(CalibrationStaleError, match="tpu"):
        load_calibration(path)
    assert load_calibration(path, strict=False) == stale


def test_calibration_validates_positive():
    with pytest.raises(ValueError, match="t_agg"):
        HostCalibration(platform="cpu", t_cam=1e-4, t_agg=0.0, t_fx=1e-4)


def test_calibration_reanchors_derived_primitives():
    from repro.tuning import current_platform
    cal = HostCalibration(platform=current_platform(), t_cam=1e-4,
                          t_agg=2e-3, t_fx=3e-4)
    base = compile_mapping(DIMS, TAXI_STATS)
    recal = compile_mapping(DIMS, TAXI_STATS, calibration=cal)
    # wall-clock anchors are ~ms vs the paper's ~ns primitives: the rollup
    # must actually consume them
    assert recal.t_compute > base.t_compute * 100
    # and the technology scaling still rides on top of the new anchor
    sram = compile_mapping(DIMS, TAXI_STATS, calibration=cal,
                           technology="sram")
    assert sram.t_compute < recal.t_compute


# ------------------------------------------------------------- planner axis

MIXED = WorkloadProfile(churn=0.01, queries_per_tick=64, sample=8)


def test_planner_mixed_technology_on_frontier():
    result = plan(TAXI_STATS, "throughput", workload=MIXED,
                  technologies=(*TECHS, PAIR))
    assert any("+" in sc.candidate.tech_key for sc in result.frontier)
    # a pair candidate is semi-only and splits into spoke/head tiers
    pair = next(sc.candidate for sc in result.scored
                if sc.candidate.tech_key == "reram+sram")
    assert pair.setting == "semi"
    assert (pair.spoke_technology, pair.head_technology) == PAIR


def test_noise_tolerance_rejects_noisy_heads():
    loose = plan(TAXI_STATS, "energy", workload=MIXED, technologies=TECHS)
    tight = plan(TAXI_STATS, "energy",
                 workload=dataclasses.replace(MIXED, noise_tolerance=1e-4),
                 technologies=TECHS)
    noisy = resolve_technology(loose.recommended.candidate.head_technology)
    quiet = resolve_technology(tight.recommended.candidate.head_technology)
    assert noisy.noise_sigma > 0.0              # cheap-but-noisy wins loose
    assert quiet.noise_sigma == 0.0             # tolerance flips to quiet


def test_register_technology_type_checked():
    from repro.devices import register_technology
    with pytest.raises(TypeError, match="TechnologyParams"):
        register_technology({"name": "bogus"})
    assert isinstance(SOT_MRAM, TechnologyParams)
