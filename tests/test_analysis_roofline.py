"""analysis/roofline.py and analysis/breakdown.py against hand-computed
ground truth — the bound arithmetic the tuner's pruning (repro.tuning)
and the perf-trajectory benches now lean on.
"""
import json
import sys

import jax
import jax.numpy as jnp

from repro.analysis.breakdown import instruction_rows
from repro.analysis.hlo import ModuleCost
from repro.analysis.roofline import HW, V5E, roofline_terms


def _compiled_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


# ---- roofline_terms bound correctness ------------------------------------

def test_bound_is_max_term_and_dominant_names_it():
    hw = HW(peak_flops=100.0, hbm_bw=10.0, link_bw=1.0, n_links=2)
    t = roofline_terms(ModuleCost(flops=200.0, hbm_bytes=30.0,
                                  collective_bytes=2.0), hw)
    assert t.compute_s == 2.0
    assert t.memory_s == 3.0
    assert t.collective_s == 1.0
    assert t.bound_s == 3.0
    assert t.dominant == "memory"


def test_hand_computed_v5e_intensity_crossover():
    """The v5e compute/memory balance point is peak_flops/hbm_bw ≈ 240.5
    flops/byte: a kernel above it must be compute-dominant, below it
    memory-dominant."""
    balance = V5E.peak_flops / V5E.hbm_bw
    above = roofline_terms(ModuleCost(flops=(balance * 2) * 1e6,
                                      hbm_bytes=1e6))
    below = roofline_terms(ModuleCost(flops=(balance / 2) * 1e6,
                                      hbm_bytes=1e6))
    assert above.dominant == "compute" and below.dominant == "memory"
    assert above.bound_s == above.compute_s
    assert below.bound_s == below.memory_s


def test_degenerate_zero_cost_is_all_zero_not_nan():
    t = roofline_terms(ModuleCost(flops=0.0, hbm_bytes=0.0,
                                  collective_bytes=0.0))
    assert t.compute_s == t.memory_s == t.collective_s == 0.0
    assert t.bound_s == 0.0
    assert t.useful_ratio == 0.0          # no division by zero flops
    assert t.dominant in ("compute", "memory", "collective")
    d = t.as_dict()
    assert d["flops"] == 0.0 and d["useful_ratio"] == 0.0


def test_zero_flop_memory_only_cost():
    t = roofline_terms(ModuleCost(flops=0.0, hbm_bytes=V5E.hbm_bw))
    assert t.compute_s == 0.0
    assert abs(t.memory_s - 1.0) < 1e-12
    assert t.dominant == "memory" and t.bound_s == t.memory_s


def test_duck_typed_launch_cost_matches_module_cost():
    """repro.tuning.LaunchCost feeds the same roofline math ModuleCost
    does — the pruning contract (DESIGN.md §11)."""
    from repro.tuning import LaunchCost
    lc = LaunchCost(flops=3.94e12, hbm_bytes=8.19e9, vmem_bytes=0,
                    grid_steps=1, collective_bytes=4e9)
    mc = ModuleCost(flops=3.94e12, hbm_bytes=8.19e9, collective_bytes=4e9)
    a, b = roofline_terms(lc), roofline_terms(mc)
    assert (a.compute_s, a.memory_s, a.collective_s) == \
           (b.compute_s, b.memory_s, b.collective_s)


# ---- roofline_table agreement with hand-computed terms -------------------

def test_roofline_table_renders_hand_computed_terms(tmp_path, capsys):
    """The table's ms columns must be exactly the recorded roofline terms
    (x1e3), records dedup by (arch, shape, mesh) with last-wins, and
    failed records count toward the return code."""
    t = roofline_terms(ModuleCost(flops=197e12 * 0.25,
                                  hbm_bytes=819e9 * 0.125),
                       model_flops=197e12 * 0.125)
    stale = dict(arch="a1", shape="s", mesh="1x1", ok=True,
                 roofline=dict(t.as_dict(), compute_s=99.0),
                 memory={"live_bytes": 2 ** 30})
    fresh = dict(stale, roofline=t.as_dict())
    bad = dict(arch="a2", shape="s", mesh="1x1", ok=False, error="boom")
    path = tmp_path / "dryrun.jsonl"
    path.write_text("not json\n" + "\n".join(
        json.dumps(r) for r in (stale, fresh, bad)) + "\n")

    sys.path.insert(0, "benchmarks")
    from benchmarks import roofline_table
    rc = roofline_table.main(path=str(path))
    out = capsys.readouterr().out
    assert rc == 1                              # the failed record
    assert f"{0.25 * 1e3:9.2f}" in out          # compute_s == 0.25 s
    assert f"{0.125 * 1e3:9.2f}" in out         # memory_s  == 0.125 s
    assert "compute" in out                     # dominant column
    assert f"{0.5:7.2f}" in out                 # useful_ratio
    assert "99000" not in out                   # stale record superseded
    assert "boom" in out


# ---- breakdown.instruction_rows ------------------------------------------

def test_instruction_rows_charges_dot_flops_exactly():
    a = jax.ShapeDtypeStruct((32, 48), jnp.float32)
    b = jax.ShapeDtypeStruct((48, 16), jnp.float32)
    rows = instruction_rows(_compiled_text(lambda a, b: a @ b, a, b))
    dot_flops = sum(f for _, f, _, op, _ in rows if op.startswith("dot"))
    assert dot_flops == 2 * 32 * 48 * 16


def test_instruction_rows_scales_by_while_trip_count():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32)
    rows = instruction_rows(_compiled_text(f, x, w))
    total_flops = sum(f for _, f, _, _, _ in rows)
    assert total_flops == 2 * 64 ** 3 * 12
    # the scan-body dot is charged with the x12 multiplier, visibly
    assert any(m == 12 and f > 0 for _, f, m, _, _ in rows)


def test_instruction_rows_agrees_with_analyze_module():
    """The per-instruction rows are the decomposition of analyze_module's
    totals: summing them must reproduce the module-level dot flops."""
    from repro.analysis import analyze_module

    def f(a, b, c):
        return (a @ b) @ c

    a = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 24), jnp.float32)
    c = jax.ShapeDtypeStruct((24, 8), jnp.float32)
    text = _compiled_text(f, a, b, c)
    rows = instruction_rows(text)
    assert sum(f for _, f, _, _, _ in rows) == analyze_module(text).flops


# ---- peak table keyed by device kind ---------------------------------------

def test_peaks_keyed_by_device_kind_unknown_raises():
    import pytest
    from repro.analysis.roofline import PEAKS, peaks_for
    assert peaks_for("TPU v5 lite") is V5E
    assert V5E.peak_flops == 197e12 and V5E.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no peak table"):
        peaks_for("cpu")
    assert "cpu" not in PEAKS


def test_tuner_prunes_against_v5e_off_the_tpu():
    """Off the TPU the kernels run interpreted; the tuner ranks the v5e
    launches against v5e peaks rather than inventing CPU ones."""
    from repro.tuning.autotune import target_hw
    assert jax.devices()[0].platform != "tpu"
    assert target_hw() is V5E
