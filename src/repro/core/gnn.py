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


def _transform(z: jax.Array, w: jax.Array, cfg: GNNConfig) -> jax.Array:
    if cfg.numerics.ideal:
        return jnp.dot(z, w, preferred_element_type=jnp.float32)
    return crossbar_matmul_signed_ref(z, w, cfg.numerics)


@partial(jax.jit, static_argnames="cfg")
def forward(params: list, x: jax.Array, neighbors: jax.Array,
            weights: jax.Array, cfg: GNNConfig) -> jax.Array:
    """Full-graph GNN forward.

    x: [N, F_in]; neighbors/weights: [N, S] padded sample (self loops should
    be included in the sample). Returns [N, out_dim] embeddings/logits.
    """
    assert cfg.backend in BACKENDS, cfg.backend
    h = x
    n_layers = len(params)
    for i, layer in enumerate(params):
        act = i < n_layers - 1 or cfg.final_activation
        if cfg.backend == "fused":
            h = fused_gnn_layer(h, neighbors, weights, layer["w"],
                                layer["b"], cfg.numerics, relu=act,
                                tuned=cfg.tuned)
            continue
        z = aggregate(h, neighbors, weights, backend=cfg.backend)  # message+agg
        h = _transform(z, layer["w"], cfg) + layer["b"]
        if act:
            h = jax.nn.relu(h)
    return h


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
