"""CPU rehearsal of both cells: the traffic loop, the metric arithmetic
and the comparison that decides ``correct``, at a tiny size with the
kernels interpreted. The look for a chip is steered aside here
(``rehearse.steered``); a broken timed path must come out not correct.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = harness.load_spec(ROOT)


def _names(group, cell):
    return sorted(m["name"] for m in SPEC[group]
                  if cell in m.get("workloads", [cell]))


def test_sound_run_is_correct_and_reports_its_metrics():
    res = rehearse.run("collab-refresh")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert sorted(res["metrics"]) == _names("end_to_end", "collab-refresh")
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"embed_rms_rel", "lookups_wrong"}


def test_traced_run_reports_host_side_layers():
    """On the CPU the trace holds no TPU ops, so the device readers find
    nothing and leave their metrics out; the host-side ones remain."""
    res = rehearse.run("collab-refresh", traced=True)
    assert res["correct"], res["checks"]
    assert {"query_service_us", "step_mfu_pct"} <= set(res["metrics"])
    assert "halo_collective_ms" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "answer", "control"])
def test_broken_path_is_not_correct(fault):
    assert not rehearse.run("collab-refresh", fault=fault)["correct"]


def _four_devices(cell, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-m", "bench.tests.rehearse", cell,
                        "--fault", fault], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["none", "exchange", "unchanged"])
def test_dec4_on_four_host_devices(fault):
    res = _four_devices("collab-dec4-refresh", fault)
    assert res["device"]["count"] == 4
    assert res["correct"] == (fault == "none"), res["checks"]


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "collab-refresh",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300)


def test_entry_refuses_a_machine_without_a_tpu():
    r = _entry(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_entry_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _entry(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
