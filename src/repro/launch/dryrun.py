import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build the production mesh (16x16 single-pod or 2x16x16
multi-pod), construct abstract params / optimizer state / inputs as
ShapeDtypeStructs (no allocation), attach the sharding rules from
``repro.distributed.sharding``, then ``jit(...).lower(...).compile()``.
A successful compile proves the distribution config is coherent: every
parameter / activation / cache spec matches, the collectives the partitioner
emits are supported, and the per-device program fits in principle.

The compiled artifact is mined for the roofline inputs (per-device FLOPs /
HBM traffic / collective link bytes; see repro.analysis) and everything is
appended to a JSON report consumed by EXPERIMENTS.md and benchmarks.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun.json
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import analyze_module, model_flops, roofline_terms
from repro.configs import ARCHS, SHAPES, cells, get_config
from repro.distributed.sharding import (activation_rules, batch_shardings,
                                        cache_shardings, optimizer_shardings,
                                        param_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (batch_struct, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import build
from repro.optim import AdamWConfig, adamw_init


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _struct(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def lower_cell(arch: str, shape_name: str, mesh, *, remat: str | None = None,
               overrides: dict | None = None, seq_parallel: bool = False):
    """Returns (lowered, cfg, meta) for one cell on ``mesh``."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if remat is not None:
        cfg = _dc.replace(cfg, remat=remat)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    spec = SHAPES[shape_name]
    model = build(cfg)
    rules = activation_rules(cfg, mesh, seq_parallel=seq_parallel)

    params_s = _struct(jax.eval_shape(model.init, jax.random.key(0)))
    p_ns = _ns(mesh, param_shardings(params_s, cfg, mesh))
    meta = {"arch": arch, "shape": shape_name, "kind": spec.kind,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    with jax.set_mesh(mesh):
        if spec.kind == "train":
            opt_s = _struct(jax.eval_shape(adamw_init, params_s))
            mom_specs = optimizer_shardings(
                param_shardings(params_s, cfg, mesh), params_s, mesh)
            o_ns = _ns(mesh, {"m": mom_specs, "v": mom_specs, "step": P()})
            batch_s = batch_struct(cfg, spec.global_batch, spec.seq_len)
            b_ns = _ns(mesh, batch_shardings(mesh, "train", batch_s))
            step = make_train_step(model, AdamWConfig(), rules)
            jitted = jax.jit(step, in_shardings=(p_ns, o_ns, b_ns),
                             out_shardings=(p_ns, o_ns, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, batch_s)
            n_tokens = spec.global_batch * spec.seq_len
        elif spec.kind == "prefill":
            batch_s = batch_struct(cfg, spec.global_batch, spec.seq_len)
            b_ns = _ns(mesh, batch_shardings(mesh, "prefill", batch_s))
            step = make_prefill_step(model, rules)
            jitted = jax.jit(step, in_shardings=(p_ns, b_ns))
            lowered = jitted.lower(params_s, batch_s)
            n_tokens = spec.global_batch * spec.seq_len
        else:  # decode
            b = spec.global_batch
            caches_s = _struct(jax.eval_shape(
                functools.partial(model.init_caches, b, spec.seq_len)))
            c_ns = _ns(mesh, cache_shardings(caches_s, cfg, mesh))
            tok_s = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            pos_s = jax.ShapeDtypeStruct((), jnp.int32)
            t_ns = _ns(mesh, batch_shardings(mesh, "decode", tok_s))
            if cfg.is_encdec:
                enc_s = _struct(jax.eval_shape(
                    lambda p, f: model._cross_kvs(p, model.encode(p, f)),
                    params_s, jax.ShapeDtypeStruct(
                        (b, cfg.encoder.n_frames, cfg.d_model),
                        jnp.dtype(cfg.dtype))))
                e_ns = _ns(mesh, batch_shardings(mesh, "decode", enc_s))
                step = make_serve_step(model, rules, with_enc=True)
                jitted = jax.jit(step, in_shardings=(
                    p_ns, c_ns, t_ns, None, e_ns),
                    out_shardings=(None, c_ns), donate_argnums=(1,))
                lowered = jitted.lower(params_s, caches_s, tok_s, pos_s,
                                       enc_s)
            else:
                step = make_serve_step(model, rules)
                jitted = jax.jit(step, in_shardings=(p_ns, c_ns, t_ns, None),
                                 out_shardings=(None, c_ns),
                                 donate_argnums=(1,))
                lowered = jitted.lower(params_s, caches_s, tok_s, pos_s)
            n_tokens = b  # one new token per sequence
    meta["n_tokens"] = n_tokens
    return lowered, cfg, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             remat: str | None = None, overrides: dict | None = None,
             seq_parallel: bool = False, mesh=None) -> dict:
    """``mesh``: optional explicit mesh for ablations (e.g. 32x8 for
    yi-34b's 56-head TP=8 layout — EXPERIMENTS.md §Perf); the default is
    the fixed production mesh the dry-run gate requires."""
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    lowered, cfg, meta = lower_cell(arch, shape_name, mesh, remat=remat,
                                    overrides=overrides,
                                    seq_parallel=seq_parallel)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    mc = analyze_module(hlo, default_group=16)
    mf = model_flops(cfg, meta["n_tokens"], meta["kind"]) / n_dev
    rt = roofline_terms(mc, model_flops=mf)

    rec = dict(meta)
    rec.update({
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # per-device live = args + temps (aliased args are reused)
            "live_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
        },
        "xla_cost_analysis_flops": cost.get("flops", -1.0),
        "roofline": rt.as_dict(),
        "collective_counts": dict(mc.collective_counts),
        "while_trips": mc.while_trips[:8],
        "ok": True,
    })
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"],
                    default="off")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    todo = []
    if args.all:
        for a, s, skip, reason in cells(ARCHS):
            todo.append((a, s, skip, reason))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        sk = [c for c in cells([args.arch]) if c[1] == args.shape][0]
        todo.append(sk)

    pods = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]
    done = set()
    if args.out and args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    n_fail = 0
    for arch, shape, skip, reason in todo:
        for mp in pods:
            mesh_name = "2x16x16" if mp else "16x16"
            key = (arch, shape, mesh_name)
            if key in done:
                print(f"[skip-done] {arch} x {shape} @ {mesh_name}")
                continue
            if skip:
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "ok": True, "skipped": True, "reason": reason}
                print(f"[SKIP] {arch} x {shape}: {reason}")
            else:
                print(f"[dryrun] {arch} x {shape} @ {mesh_name} ...",
                      flush=True)
                # train steps default to full remat (activations do not fit
                # HBM otherwise — see EXPERIMENTS.md §Perf iteration 0)
                remat = args.remat
                if remat is None and SHAPES[shape].kind == "train":
                    remat = "full"
                try:
                    rec = run_cell(arch, shape, multi_pod=mp, remat=remat)
                    r = rec["roofline"]
                    print(f"  ok: compile={rec['compile_s']}s "
                          f"live={rec['memory']['live_bytes']/2**30:.2f}GiB/dev "
                          f"compute={r['compute_s']*1e3:.2f}ms "
                          f"memory={r['memory_s']*1e3:.2f}ms "
                          f"collective={r['collective_s']*1e3:.2f}ms "
                          f"dominant={r['dominant']} "
                          f"useful={r['useful_ratio']:.2f}", flush=True)
                except Exception as e:
                    n_fail += 1
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "ok": False, "error": f"{type(e).__name__}: {e}"}
                    print(f"  FAIL: {type(e).__name__}: {e}")
                    traceback.print_exc()
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")
    print("all cells ok")


if __name__ == "__main__":
    main()
