"""GNN inference/training in JAX on the IMA-GNN dataflow.

The model family the paper accelerates (Fig. 1): per layer,
  aggregation         Z = A_hat @ X     (traversal + aggregation cores)
  feature extraction  H = act(Z @ W + b)  (MVM crossbar core)

Both stages run through the kernel stack: aggregation via the
``csr_aggregate`` padded-sample kernel, feature extraction either ideal
(float matmul) or through the ``crossbar_mvm`` numerics — switching
``CrossbarNumerics(ideal=False)`` gives bit-accurate in-memory inference.

Backends (``GNNConfig.backend``):
  * ``jnp``    — composed path on XLA oracles (differentiable; training).
  * ``pallas`` — composed path, aggregation on the ``csr_aggregate`` kernel.
  * ``fused``  — both stages in one ``fused_gnn_layer`` kernel launch: Z
    stays resident in VMEM between aggregation and feature extraction
    (DESIGN.md §5). Inference/serving only — the fused kernel has no VJP.

``layer_step`` is the one GNN layer of the repo: the only code that
chooses between aggregate + transform and ``fused_gnn_layer``. Every
placement runs it — ``forward`` here loops it over the layers, the
decentralized and semi runtimes (``distributed.halo._layers``) loop it over
owned plus halo rows, and the streaming engine runs it on dirty rows.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels.crossbar_mvm import CrossbarNumerics, crossbar_matmul_signed_ref
from repro.kernels.csr_aggregate import aggregate
from repro.kernels.fused_layer import fused_gnn_layer

BACKENDS = ("jnp", "pallas", "fused")


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    in_dim: int
    hidden_dims: tuple = (128,)
    out_dim: int = 16
    sample: int = 16                       # padded neighbor sample size S
    numerics: CrossbarNumerics = CrossbarNumerics(ideal=True)
    backend: str = "jnp"                   # one of BACKENDS
    final_activation: bool = False
    tuned: object | None = None            # TunedKernels bundle (repro.tuning)
    #                                        — hashable, so swapping tuned
    #                                        configs retraces jitted forwards

    @property
    def dims(self) -> tuple:
        return (self.in_dim, *self.hidden_dims, self.out_dim)


def init_params(key: jax.Array, cfg: GNNConfig) -> list:
    """Glorot-initialized (W, b) per layer."""
    params = []
    dims = cfg.dims
    for i in range(len(dims) - 1):
        key, sub = jax.random.split(key)
        fan_in, fan_out = dims[i], dims[i + 1]
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32)
        w = w * jnp.sqrt(2.0 / (fan_in + fan_out))
        params.append({"w": w, "b": jnp.zeros((fan_out,), jnp.float32)})
    return params


def layer_step(table: jax.Array, nbr: jax.Array, wts: jax.Array,
               layer: dict, cfg: GNNConfig, act: bool, *,
               w_noise: jax.Array | None = None,
               interpret: bool | None = None) -> jax.Array:
    """One GNN layer, ``act((A_hat @ table) @ W + b)``, on ``cfg.backend``.

    table: [N, F] feature rows the sample indexes (owned rows, then any
    halo rows); nbr/wts: [Nd, S] padded sample. ``cfg.numerics`` holds on
    every backend. ``w_noise``: optional [F, H] conductance-code
    perturbation of the programmed weights (``devices.variation``),
    ignored with ideal numerics; ``interpret`` forces the Pallas kernels'
    mode."""
    if cfg.backend == "fused":
        return fused_gnn_layer(table, nbr, wts, layer["w"], layer["b"],
                               cfg.numerics, relu=act, tuned=cfg.tuned,
                               interpret=interpret, w_noise=w_noise)
    z = aggregate(table, nbr, wts, backend=cfg.backend, tuned=cfg.tuned,
                  interpret=interpret)                  # message + agg
    if cfg.numerics.ideal:
        h = jnp.dot(z, layer["w"], preferred_element_type=jnp.float32)
    else:
        h = crossbar_matmul_signed_ref(z, layer["w"], cfg.numerics,
                                       w_noise=w_noise)
    h = h + layer["b"]
    return jax.nn.relu(h) if act else h


@partial(jax.jit, static_argnames="cfg")
def forward(params: list, x: jax.Array, neighbors: jax.Array,
            weights: jax.Array, cfg: GNNConfig) -> jax.Array:
    """Full-graph GNN forward: ``layer_step`` per layer.

    x: [N, F_in]; neighbors/weights: [N, S] padded sample (self loops should
    be included in the sample). Returns [N, out_dim] embeddings/logits.
    """
    assert cfg.backend in BACKENDS, cfg.backend
    n_layers = len(params)
    for i, layer in enumerate(params):
        x = layer_step(x, neighbors, weights, layer, cfg,
                       i < n_layers - 1 or cfg.final_activation)
    return x


@partial(jax.jit, static_argnames="cfg")
def centralized_forward(params: list, feats: jax.Array, neighbors: jax.Array,
                        weights: jax.Array, cfg: GNNConfig) -> jax.Array:
    """``forward`` over a centralized plan's ``[1, N, ...]`` tables;
    returns ``[1, N, out_dim]``."""
    return forward(params, feats[0], neighbors[0], weights[0], cfg)[None]


@partial(jax.jit, static_argnames="cfg")
def loss_fn(params: list, x, neighbors, weights, labels, cfg: GNNConfig):
    """Cross-entropy node-classification loss (mean over labeled nodes)."""
    logits = forward(params, x, neighbors, weights, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).squeeze(-1)
    return jnp.mean(nll)


grad_fn = jax.jit(jax.value_and_grad(loss_fn), static_argnames="cfg")
