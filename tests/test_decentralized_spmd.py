"""The SPMD decentralized runtime on four host devices: the served table
against the plain float32 reference, the exchange tables as sharded
arguments of the program (never constants), and the exchange counters of
the ``plan.forward`` span. Each case runs in a subprocess with four forced
host devices, so the test process keeps its one-device view."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json, re, sys
import numpy as np, jax, jax.numpy as jnp
from repro import telemetry as tel
from repro.core import gnn, random_graph
from repro.core.partition import plan_execution
from repro.distributed.halo import build_halo_plan
from repro.launch.gnn import GNNServer
from repro.launch.mesh import make_mesh

case, arg = sys.argv[1], sys.argv[2]
g = random_graph(300, 3000, 24, seed=7).gcn_normalize()
cfg = gnn.GNNConfig(in_dim=24, hidden_dims=(16,), out_dim=6, sample=8)
mesh = make_mesh((4,), ("data",))
out = {}
if case == "refresh":
    plan = plan_execution(g, "decentralized", backend=arg, sample=8,
                          n_clusters=4)
    params = gnn.init_params(jax.random.key(3), plan.gnn_config(cfg))
    srv = GNNServer(plan, cfg, params=params, mesh=mesh)
    srv.refresh()
    nbr, wts = g.neighbor_sample(8)
    with jax.default_matmul_precision("highest"):
        ref = gnn.forward(params, jnp.asarray(g.features), jnp.asarray(nbr),
                          jnp.asarray(wts), cfg)
    out = dict(got=srv.embeddings.tolist(), ref=np.asarray(ref).tolist())
elif case == "lower":
    plan = plan_execution(g, arg, backend="jnp", sample=8, n_clusters=4)
    params = gnn.init_params(jax.random.key(3), plan.gnn_config(cfg))
    text = plan.lower_forward(params, plan.gnn_config(cfg),
                              mesh=mesh).as_text()
    main = re.search(r"func\.func public @main\((.*?)\) ->", text).group(1)
    args = re.findall(r"%arg\d+: tensor<([^>]*)>( \{sdy\.sharding = "
                      r"#sdy\.sharding<@mesh, \[\{\"data\"\})?", main)
    consts = re.findall(r"stablehlo\.constant dense<.*?> : tensor<([^>]*)>",
                        text)
    hp = build_halo_plan(plan.part)
    out = dict(args=[[t, bool(s)] for t, s in args], consts=consts,
               send=list(hp.send_slot.shape), feats=list(plan.feats.shape))
elif case == "counters":
    plan = plan_execution(g, "decentralized", backend="jnp", sample=8,
                          n_clusters=4)
    params = gnn.init_params(jax.random.key(3), plan.gnn_config(cfg))
    tel.enable()
    for m in (mesh, None):
        jax.block_until_ready(plan.make_forward(cfg, mesh=m)(params))
    spans = [dict(r.attrs, exchange=[c.attrs["bytes"] for c in r.children
                                     if c.name == "halo.exchange"])
             for r in tel.get_tracer().roots if r.name == "plan.forward"]
    hp = build_halo_plan(plan.part)
    rep = plan.measured_traffic(plan.gnn_config(cfg))
    out = dict(spans=spans, h_max=int(hp.src_cluster.shape[1]),
               s_max=int(hp.s_max),
               per_chip=rep.tier1_bytes().sum(axis=0).tolist())
print("RESULT " + json.dumps(out))
"""


def _run(case: str, arg: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", _SCRIPT, case, arg],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("backend", ["fused", "jnp"])
def test_refresh_on_four_devices_matches_the_float32_reference(backend):
    """``GNNServer.refresh`` through ``make_forward(mesh=4 devices)``
    serves what ``gnn.forward`` computes on the whole graph. Both sides
    are float32 (the interpreted kernel's dot included); they differ only
    in the order of the slot sums and of the dot's accumulation, a few
    ulps of values near 4 (7e-7 seen), while one bfloat16 pass would be
    off by about 1e-2: hence 1e-5."""
    import numpy as np
    res = _run("refresh", backend)
    got, ref = np.array(res["got"]), np.array(res["ref"])
    assert got.shape == ref.shape == (300, 6)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("setting", ["decentralized", "semi"])
def test_spmd_exchange_tables_are_sharded_arguments(setting):
    """The lowered SPMD forward takes every exchange table as an argument
    of ``main`` split over the mesh's ``data`` axis, like the features;
    no constant of a table's shape is left in the module."""
    res = _run("lower", setting)
    args = {t: s for t, s in res["args"]}
    send = "x".join(map(str, res["send"]))
    tables = [t for t in args if t.startswith(send + "x")]
    # alltoall: send_slot and recv_to_halo (i32), send/recv masks (f32)
    assert sorted(tables) == [f"{send}xf32", f"{send}xi32"], res["args"]
    sharded = [t for t, s in res["args"] if s]
    assert sum(t.startswith(send + "x") for t in sharded) == 4
    feats = "x".join(map(str, res["feats"]))
    assert any(t.startswith(feats + "x") for t in sharded)
    assert not [c for c in res["consts"] if c.startswith(send + "x")]


def test_plan_forward_states_the_launched_exchange():
    """With telemetry on, ``plan.forward`` of the SPMD plan states the
    collective as launched: chips, per-chip halo and send rows, and the
    bytes each chip's all-to-all sends over both layers (K x s_max rows a
    layer at its input width, 4 bytes each). ``halo.exchange`` bills the
    true bytes, summed over the chips: no chip receives more than it is
    launched, and the gap is the padding. The mesh-free forward runs no
    collective and states none of it."""
    res = _run("counters", "")
    spmd, emulated = res["spans"]
    k, s_max, widths = 4, res["s_max"], 24 + 16
    assert spmd["chips"] == k
    assert spmd["halo_rows"] == res["h_max"]
    assert spmd["send_rows"] == s_max
    assert spmd["launched_bytes"] == k * s_max * widths * 4
    assert max(res["per_chip"]) <= spmd["launched_bytes"]
    assert k * spmd["launched_bytes"] >= sum(spmd["exchange"]) > 0
    assert spmd["exchange"] == emulated["exchange"]
    assert not {"chips", "halo_rows", "send_rows",
                "launched_bytes"} & set(emulated)
