#!/usr/bin/env python3
"""Chip smoke test: the GNN serving path on a TPU at collab's Table-2 size.

    python3 chip_smoke.py [--seed N]      # phases 0-2, one chip
    python3 chip_smoke.py --chips 4       # phase 0 + the 4-chip mesh phase

It goes through the entry points a user calls, the same path as
``python -m repro.launch.gnn``: ``plan_execution`` -> ``GNNServer`` /
``StreamingGNNServer``. The graph is ``dataset_like("collab", scale=1.0)``
(372,475 nodes, ~24.6M edges, F=496) and the weights are random, both from
``--seed``; the model is the launcher's default ``GNNConfig`` (one hidden
layer of 64, 16 outputs, sample 8).

  Phase 0  exits non-zero unless JAX's first device is a TPU: JAX falls
           back to the CPU when libtpu fails to start, and the kernels
           would then run interpreted.
  Phase 1  centralized, decentralized (K=4, emulated on one chip) and semi
           (K=4 heads) on the ``fused`` and ``pallas`` backends: refresh,
           query batches, embeddings against the ``jnp`` backend in float32
           at the highest matmul precision; one bit-accurate fused pass
           against the composed jnp bit-accurate reference; every lowered
           forward must hold ``tpu_custom_call`` (compiled Pallas kernels).
  Phase 2  StreamingGNNServer on the decentralized fused plan: a few ticks
           at 1% feature churn with queries between commits, then the
           incrementally refreshed embeddings against a full refresh.
  --chips 4  decentralized and semi with K=4 on a 4-device mesh (the
           ``shard_map`` runtime), allgather and alltoall, against the
           emulated forward of the same plan. No other phase runs.

Any failed check exits non-zero. Times printed are host-clock seconds
around work that ends in ``block_until_ready``, on the chip named in
phase 0. The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SAMPLE = 8
CLUSTERS = 4
# Stated before any chip run. Ideal numerics: max|err| over max|ref| — the
# served path runs at the default matmul precision (bf16 passes on the MXU,
# ~3e-3 relative per layer) and the reference at float32.
TOL_IDEAL = 2e-2
# Bit-accurate numerics: a last-ulp difference in Z (the kernel sums S rows
# in order, XLA's einsum in its own order) can flip a DAC code at a rounding
# boundary, and the 8-bit ADC turns one flipped code into a whole ADC step.
# So at most TOL_QUANT_FRAC of the outputs may differ by more than
# TOL_QUANT_ELEM * max|ref|.
TOL_QUANT_ELEM = 1e-3
TOL_QUANT_FRAC = 1e-2
# Same kernels on both sides, rows computed independently.
TOL_SAME = 1e-5
CHURN = 0.01
TICKS = 3
QUERY_BATCHES = 4
QUERY_BATCH = 64
COLLECTIVE = {"allgather": "all_gather", "alltoall": "all_to_all"}


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def phase0(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"phase 0: jax {jax.__version__}, platform {d.platform}, "
          f"device kind {d.device_kind!r}, device count {len(devs)}",
          flush=True)
    check(d.platform == "tpu",
          f"no TPU: JAX's first device is {d.platform!r}; refusing to run "
          f"the kernels interpreted")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"found {len(devs)}")
    return d, len(devs)


def build(seed: int):
    from repro.core import dataset_like, gnn
    t0 = time.perf_counter()
    g = dataset_like("collab", scale=1.0, seed=seed).gcn_normalize()
    cfg = gnn.GNNConfig(in_dim=g.feature_len, hidden_dims=(64,),
                        out_dim=16, sample=SAMPLE)
    print(f"collab: {g.n_nodes} nodes, {g.n_edges} edges, "
          f"F={g.feature_len}; built in {time.perf_counter() - t0:.1f} s "
          f"(host)", flush=True)
    return g, cfg


def make_plan(g, setting: str, backend: str, seed: int):
    from repro.core.partition import plan_execution
    t0 = time.perf_counter()
    plan = plan_execution(
        g, setting, backend=backend, sample=SAMPLE, seed=seed,
        n_clusters=None if setting == "centralized" else CLUSTERS,
        spokes_per_head=4)
    print(f"plan {setting}: {plan.n_clusters} clusters, tables "
          f"{tuple(plan.neighbors.shape)}; planned in "
          f"{time.perf_counter() - t0:.1f} s (host)", flush=True)
    return plan


def rel_err(got, ref) -> tuple[float, float]:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite embeddings")
    err = float(np.abs(got - ref).max())
    return err, err / max(float(np.abs(ref).max()), 1e-30)


def reference(plan, cfg, seed: int):
    """Embeddings of the jnp backend on the same plan, float32 at the
    highest matmul precision."""
    import jax
    from repro.launch.gnn import GNNServer
    ref_plan = dataclasses.replace(plan, backend="jnp")
    with jax.default_matmul_precision("highest"):
        srv = GNNServer(ref_plan, cfg, seed=seed)
        srv.refresh()
    return srv.embeddings


def assert_compiled(plan, srv, label: str, mesh=None, mode="alltoall",
                    collective: str | None = None) -> None:
    text = plan.lower_forward(srv.params, srv.cfg, mesh=mesh,
                              mode=mode).as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, f"{label}: no tpu_custom_call in the lowered forward — "
          f"the Pallas kernels did not compile for the TPU")
    if collective is not None:
        check(collective in text, f"{label}: no {collective} in the "
              f"lowered forward — the shard_map exchange is missing")
    print(f"  {label}: lowered forward holds {n} tpu_custom_call(s)"
          + (f" and {collective}" if collective else ""), flush=True)


def serve(plan, cfg, seed: int, label: str, **kw):
    """GNNServer refresh twice (first includes compile) + query batches."""
    import numpy as np
    from repro.launch.gnn import GNNServer
    srv = GNNServer(plan, cfg, seed=seed, **kw)
    t_first = srv.refresh()
    t_steady = srv.refresh()
    rng = np.random.default_rng(seed)
    n = plan.graph.n_nodes
    t0 = time.perf_counter()
    for _ in range(QUERY_BATCHES):
        ids = rng.integers(0, n, QUERY_BATCH)
        out = srv.query(ids)
        check(np.array_equal(out, srv.embeddings[ids]),
              f"{label}: query rows differ from the served table")
    t_query = time.perf_counter() - t0
    print(f"  {label}: chip time compile+first refresh {t_first:.3f} s, "
          f"steady refresh {t_steady:.3f} s, {QUERY_BATCHES}x{QUERY_BATCH} "
          f"queries {t_query * 1e3:.2f} ms", flush=True)
    return srv


def phase1(g, cfg, seed: int):
    """Returns the decentralized fused plan, which phase 2 streams over."""
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    for setting in ("centralized", "decentralized", "semi"):
        plan = make_plan(g, setting, "fused", seed)
        if setting == "decentralized":
            stream_plan = plan
        ref = reference(plan, cfg, seed)
        for backend in ("fused", "pallas"):
            p = plan if backend == "fused" else dataclasses.replace(
                plan, backend=backend)
            label = f"{setting}/{backend}"
            srv = serve(p, cfg, seed, label)
            assert_compiled(p, srv, label)
            err, rel = rel_err(srv.embeddings, ref)
            print(f"  {label}: max|err| {err:.3e} vs jnp float32, "
                  f"relative {rel:.3e} (tolerance {TOL_IDEAL:g})",
                  flush=True)
            check(rel <= TOL_IDEAL, f"{label}: relative error {rel:.3e} "
                  f"above {TOL_IDEAL:g}")
            del srv
    # bit-accurate numerics: fused_zmax + fused_quant_layer
    import numpy as np
    cfg_q = dataclasses.replace(cfg, numerics=CrossbarNumerics(ideal=False))
    plan = make_plan(g, "centralized", "fused", seed)
    label = "centralized/fused bit-accurate"
    srv = serve(plan, cfg_q, seed, label)
    assert_compiled(plan, srv, label)
    ref = reference(plan, cfg_q, seed)
    err, rel = rel_err(srv.embeddings, ref)
    diff = np.abs(np.asarray(srv.embeddings, np.float64) - ref)
    frac = float((diff > TOL_QUANT_ELEM * np.abs(ref).max()).mean())
    print(f"  {label}: max|err| {err:.3e} (relative {rel:.3e}); "
          f"{frac:.3e} of outputs beyond {TOL_QUANT_ELEM:g} x max|ref| "
          f"(tolerance {TOL_QUANT_FRAC:g})", flush=True)
    check(frac <= TOL_QUANT_FRAC, f"{label}: {frac:.3e} of outputs off")
    return stream_plan


def phase2(plan, cfg, seed: int) -> None:
    import numpy as np
    from repro.launch.gnn import GNNServer
    from repro.streaming import StreamingGNNServer
    srv = StreamingGNNServer(plan, cfg, seed=seed, policy="eager")
    t_cold = srv.refresh()
    print(f"  stream: chip time cold full refresh (compile included) "
          f"{t_cold:.3f} s", flush=True)
    rng = np.random.default_rng(seed + 1)
    n, f = plan.graph.n_nodes, plan.graph.feature_len
    n_mut = max(int(n * CHURN), 1)
    for tick in range(TICKS):
        nodes = rng.choice(n, n_mut, replace=False)
        rows = rng.normal(size=(n_mut, f)).astype(np.float32)
        t0 = time.perf_counter()
        upd = srv.ingest(nodes=nodes, rows=rows)
        dt = time.perf_counter() - t0
        check(upd is not None and not upd.full,
              f"tick {tick}: expected an incremental commit")
        ids = rng.integers(0, n, QUERY_BATCH)
        check(np.array_equal(srv.query(ids), srv.embeddings[ids]),
              f"tick {tick}: query rows differ from the served table")
        print(f"  stream tick {tick}: {n_mut} rows changed, recompute "
              f"fraction {upd.recompute_fraction:.3f}, chip time commit "
              f"{dt:.3f} s", flush=True)
    fresh = GNNServer(plan, cfg, params=srv.params)
    fresh.refresh()
    err, rel = rel_err(srv.embeddings, fresh.embeddings)
    print(f"  stream: max|err| {err:.3e} vs a from-scratch full refresh, "
          f"relative {rel:.3e} (tolerance {TOL_SAME:g})", flush=True)
    check(rel <= TOL_SAME, f"stream: relative error {rel:.3e}")


def phase_mesh(g, cfg, seed: int) -> None:
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((CLUSTERS,), ("data",))
    for setting in ("decentralized", "semi"):
        plan = make_plan(g, setting, "fused", seed)
        feats, _, _ = plan.device_inputs(mesh)
        devices = {s.device for s in feats.addressable_shards}
        check(len(devices) == CLUSTERS
              and all(s.data.shape[0] == 1 for s in feats.addressable_shards),
              f"{setting}: feature shards not one cluster per device")
        print(f"  {setting}: {CLUSTERS} feature shards of "
              f"{feats.addressable_shards[0].data.shape} on {len(devices)} "
              f"devices", flush=True)
        del feats
        emu = serve(plan, cfg, seed, f"{setting} emulated")
        for mode in ("allgather", "alltoall"):
            label = f"{setting} shard_map {mode}"
            srv = serve(plan, cfg, seed, label, mesh=mesh, mode=mode)
            assert_compiled(plan, srv, label, mesh=mesh, mode=mode,
                            collective=COLLECTIVE[mode])
            err, rel = rel_err(srv.embeddings, emu.embeddings)
            print(f"  {label}: max|err| {err:.3e} vs emulated, relative "
                  f"{rel:.3e} (tolerance {TOL_SAME:g})", flush=True)
            check(rel <= TOL_SAME, f"{label}: relative error {rel:.3e}")
            del srv


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    try:
        dev, count = phase0(args.chips)
        from repro.launch.compile_cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        g, cfg = build(args.seed)
        if args.chips == 4:
            print("phase mesh: shard_map on 4 chips", flush=True)
            phase_mesh(g, cfg, args.seed)
        else:
            print("phase 1: one chip", flush=True)
            stream_plan = phase1(g, cfg, args.seed)
            print("phase 2: streaming", flush=True)
            phase2(stream_plan, cfg, args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
