"""Fault-tolerant training driver.

Config-driven: picks any assigned architecture (full or smoke-reduced),
builds the device mesh from whatever devices exist (1 CPU in tests, a pod
slice in production), applies the sharding rules, and runs the train loop
with step-atomic checkpointing, deterministic step-indexed data (exact
resume), crash retry, and optional int8 error-feedback gradient compression
on the data-parallel axis.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt --mesh 1x1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.tokens import TokenStream
from repro.distributed.sharding import (activation_rules, batch_shardings,
                                        optimizer_shardings, param_shardings)
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import build
from repro.optim import AdamWConfig, adamw_init


@dataclasses.dataclass
class TrainConfig:
    arch: str = "internlm2-1.8b"
    smoke: bool = True
    steps: int = 100
    batch: int = 8
    seq: int = 64
    lr: float = 3e-4
    seed: int = 0
    mesh: str = ""              # "DxM"; empty => all devices on 'data'
    accum_steps: int = 1        # gradient-accumulation microbatches
    ckpt_dir: str = ""
    ckpt_every: int = 50
    log_every: int = 10
    max_retries: int = 2        # crash retry-from-checkpoint budget


def build_mesh(spec: str):
    n = len(jax.devices())
    if spec:
        d, m = (int(x) for x in spec.split("x"))
    else:
        d, m = n, 1
    assert d * m <= n, f"mesh {d}x{m} needs {d * m} devices, have {n}"
    return make_mesh((d, m), ("data", "model"))


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def train(cfg: TrainConfig, *, hooks=None) -> dict:
    """Run the loop; returns final metrics. ``hooks`` (test seam): dict with
    optional ``on_step(step, metrics)`` and ``fault(step)`` callables —
    ``fault`` raising simulates a node failure mid-run."""
    hooks = hooks or {}
    mesh = build_mesh(cfg.mesh)
    mcfg = get_config(cfg.arch, smoke=cfg.smoke)
    model = build(mcfg)
    rules = activation_rules(mcfg, mesh)

    params = model.init(jax.random.key(cfg.seed))
    opt_state = adamw_init(params)
    p_spec = param_shardings(params, mcfg, mesh)
    m_spec = optimizer_shardings(p_spec, params, mesh)
    o_spec = {"m": m_spec, "v": m_spec, "step": P()}
    p_ns, o_ns = _ns(mesh, p_spec), _ns(mesh, o_spec)
    params = jax.device_put(params, p_ns)
    opt_state = jax.device_put(opt_state, o_ns)

    stream = TokenStream(mcfg.vocab, cfg.batch, cfg.seq, cfg.seed)
    b_ns = _ns(mesh, batch_shardings(mesh, "train", stream.batch_at(0)))

    step_fn = jax.jit(make_train_step(model, AdamWConfig(lr=cfg.lr), rules,
                                      accum_steps=cfg.accum_steps),
                      in_shardings=(p_ns, o_ns, b_ns),
                      out_shardings=(p_ns, o_ns, None),
                      donate_argnums=(0, 1))

    ckpt = CheckpointManager(cfg.ckpt_dir, every=cfg.ckpt_every) \
        if cfg.ckpt_dir else None
    start = 0
    if ckpt is not None:
        restored, at = ckpt.restore({"params": params, "opt": opt_state},
                                    mesh=mesh,
                                    shardings={"params": p_spec,
                                               "opt": o_spec})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start = at + 1
            print(f"[train] resumed from step {at}")

    metrics = {}
    retries = 0
    step = start
    t0 = time.time()
    with jax.set_mesh(mesh):
        while step < cfg.steps:
            try:
                if "fault" in hooks:
                    hooks["fault"](step)
                batch = jax.device_put(stream.batch_at(step), b_ns)
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch)
                if step % cfg.log_every == 0:
                    m = {k: float(v) for k, v in metrics.items()}
                    dt = (time.time() - t0) / max(step - start + 1, 1)
                    print(f"[train] step {step} loss {m['loss']:.4f} "
                          f"gnorm {m['gnorm']:.3f} {dt*1e3:.0f} ms/step",
                          flush=True)
                if "on_step" in hooks:
                    hooks["on_step"](step, metrics)
                if ckpt is not None:
                    ckpt.maybe_save(step, {"params": params,
                                           "opt": opt_state})
                step += 1
            except (RuntimeError, ValueError):
                raise
            except Exception as e:   # simulated node failure -> restart
                retries += 1
                if ckpt is None or retries > cfg.max_retries:
                    raise
                print(f"[train] step {step} failed ({e}); "
                      f"restoring (retry {retries}/{cfg.max_retries})")
                restored, at = ckpt.restore(
                    {"params": params, "opt": opt_state}, mesh=mesh,
                    shardings={"params": p_spec, "opt": o_spec})
                if restored is None:
                    params = jax.device_put(
                        model.init(jax.random.key(cfg.seed)), p_ns)
                    opt_state = jax.device_put(adamw_init(params), o_ns)
                    step = 0
                else:
                    params, opt_state = restored["params"], restored["opt"]
                    step = at + 1
    if ckpt is not None:
        ckpt.maybe_save(cfg.steps, {"params": params, "opt": opt_state})
        ckpt.finalize()
    return {k: float(v) for k, v in metrics.items()} | {"last_step": step - 1}


def main() -> None:
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        if f.type in ("bool", bool):
            ap.add_argument(f"--{f.name.replace('_', '-')}",
                            action="store_true", default=f.default)
        else:
            ap.add_argument(f"--{f.name.replace('_', '-')}",
                            type=type(f.default), default=f.default)
    args = ap.parse_args()
    cfg = TrainConfig(**{f.name: getattr(args, f.name)
                         for f in dataclasses.fields(TrainConfig)})
    out = train(cfg)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
