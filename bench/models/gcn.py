"""Model kind ``gcn``: a GCN over the model's fixed neighbour sample
(``graphs.sample_table``), as ``bench/kinds.py`` asks of a model module.

The graph's stored weights are the GCN normalisation
``A_hat = D^-1/2 (A + I) D^-1/2``: edge (i <- j) weighs
1/sqrt((d_i + 1)(d_j + 1)), the self loop of i 1/(d_i + 1). Per layer
``Z = sum_s wts[:, s] * H[nbr[:, s]]`` (slots summed in order), then
``H = act(Z W + b)``, ReLU after every layer but the last.

The plain reference is written here in plain ``jax.numpy``, imports
nothing of the program and runs in blocks of rows, so that it fits beside
nothing else on the chip. Three precisions:

  ``stated``    what the configuration states the program computes:
                float32 storage, the aggregation in float32, the transform
                as one bfloat16 pass of the MXU (Z and W rounded to
                bfloat16, products accumulated in float32), bias and ReLU
                in float32. ``correct`` is decided against this.
  ``float32``   float32 throughout at the highest matmul precision: the
                comparison earlier chip runs reported. Printed, not judged.
  ``bfloat16``  the control: the same computation with storage and
                arithmetic in bfloat16, the nearest precision below the
                stated one. Put in the program's place, it must fail.

The reference computes what the program computes with ideal numerics
only, so ``"ideal"`` is the one ``numerics`` it can judge.

Work of one update, from shapes alone. Per layer over a padded sample of
S slots:

  model FLOPs       2 N S F_in  (aggregation)  +  2 N F_in F_out  (transform)
  compulsory bytes  the input rows, read once        (N_in F_in x 4)
                    the neighbour and weight tables  (N S x (4 + 4))
                    W                                 (F_in F_out x 4)
                    the output, written once          (N F_out x 4)

On a mesh each device counts its own rows: N is the rows it owns and N_in
adds the halo rows it receives. Counted this way the work does not depend
on what implements it (padding, chunking and tiling are not work), so no
rewrite of a kernel can push a share of the roofline past 100%.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NUMERICS = ("ideal",)
PRECISIONS = ("stated", "float32", "bfloat16")
JUDGED = "stated"   # the precision ``correct`` is decided against
BLOCK = 32768       # rows per aggregation call
ITEM = 4            # float32 features, weights and outputs; int32 indices


def widths(model: dict) -> tuple:
    return (model["in_dim"], *model["hidden_dims"], model["out_dim"])


def graph_weights(g: dict) -> tuple:
    """``(edge_weight [E] float32, self_loop [N] float32)``."""
    d1 = g["deg"].astype(np.float64) + 1.0
    inv = 1.0 / np.sqrt(d1)
    dst_inv = np.repeat(inv, g["deg"])
    w = (dst_inv * inv[g["indices"]]).astype(np.float32)
    return w, (1.0 / d1).astype(np.float32)


def params_fn(model: dict):
    """``fn(key, i) -> [{"w", "b"}] per layer``: the weights of update ``i``,
    drawn on the device in one jitted call. W is Glorot-normal as in the
    program's ``gnn.init_params``; b is 0.1 N(0, 1), so that the bias is
    exercised (the program starts it at 0)."""
    dims = widths(model)

    def fn(key, i):
        key = jax.random.fold_in(key, i)
        params = []
        for f_in, f_out in zip(dims[:-1], dims[1:]):
            key, kw, kb = jax.random.split(key, 3)
            w = jax.random.normal(kw, (f_in, f_out), jnp.float32)
            w = w * jnp.sqrt(2.0 / (f_in + f_out))
            b = 0.1 * jax.random.normal(kb, (f_out,), jnp.float32)
            params.append({"w": w, "b": b})
        return params

    return jax.jit(fn)


def program_config(model: dict, numerics: str):
    """The program's ``GNNConfig`` for this model."""
    from repro.core import gnn
    from repro.kernels.crossbar_mvm import CrossbarNumerics
    dims = widths(model)
    return gnn.GNNConfig(in_dim=dims[0], hidden_dims=tuple(dims[1:-1]),
                         out_dim=dims[-1], sample=model["sample"],
                         numerics=CrossbarNumerics(ideal=numerics == "ideal"))


@partial(jax.jit, static_argnames="dtype")
def _aggregate_block(x, nbr, wts, dtype):
    w = wts.astype(dtype)
    z = w[:, 0, None] * x[nbr[:, 0]]
    for s in range(1, nbr.shape[1]):
        z = z + w[:, s, None] * x[nbr[:, s]]
    return z


def aggregate(x, nbr: np.ndarray, wts: np.ndarray, dtype):
    """``Z = A_hat X`` over the sample, ``BLOCK`` rows per call (the last
    block padded with weight-0 rows, so every call has one shape)."""
    n = nbr.shape[0]
    block = min(BLOCK, n)
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    nbr = np.pad(nbr, ((0, pad), (0, 0)))
    wts = np.pad(wts, ((0, pad), (0, 0)))
    parts = [_aggregate_block(x, nbr[i * block:(i + 1) * block],
                              wts[i * block:(i + 1) * block], dtype)
             for i in range(n_blocks)]
    return jnp.concatenate(parts, axis=0)[:n]


@partial(jax.jit, static_argnames=("precision", "relu"))
def _transform(z, w, b, precision, relu):
    if precision == "stated":
        h = jnp.dot(z.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) + b
    elif precision == "float32":
        h = jnp.dot(z, w, precision=jax.lax.Precision.HIGHEST) + b
    else:
        h = jnp.dot(z, w.astype(jnp.bfloat16)) + b.astype(jnp.bfloat16)
    return jnp.maximum(h, 0) if relu else h


def forward(x_host: np.ndarray, nbr: np.ndarray, wts: np.ndarray,
            params_list: list, precision: str) -> list:
    """Embeddings ``[N, out] float32`` (host) for each parameter set.

    The first aggregation does not depend on the weights, so it runs once
    for all of ``params_list``."""
    assert precision in PRECISIONS, precision
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    x = jnp.asarray(x_host).astype(dtype)
    z1 = aggregate(x, nbr, wts, dtype)
    x.delete()
    outs = []
    for params in params_list:
        h = z1
        for li, layer in enumerate(params):
            if li:
                h = aggregate(h, nbr, wts, dtype)
            h = _transform(h, layer["w"], layer["b"], precision,
                           li < len(params) - 1)
        outs.append(np.asarray(h.astype(jnp.float32)))
        h.delete()
    z1.delete()
    return outs


def layer_flops(n_rows: int, sample: int, f_in: int, f_out: int) -> tuple:
    """(aggregation, transform) FLOPs of one layer over ``n_rows`` rows."""
    return 2.0 * n_rows * sample * f_in, 2.0 * n_rows * f_in * f_out


def layer_bytes(n_rows: int, n_in_rows: int, sample: int, f_in: int,
                f_out: int) -> float:
    """Compulsory HBM bytes of one layer (module docstring)."""
    return float(ITEM * (n_in_rows * f_in + n_rows * sample * 2
                         + f_in * f_out + n_rows * f_out))


def model_flops(n_rows: int, sample: int, dims) -> float:
    """Model FLOPs of one update of all ``n_rows`` embeddings."""
    return sum(sum(layer_flops(n_rows, sample, a, b))
               for a, b in zip(dims[:-1], dims[1:]))


def update_work(dims, sample: int, rows: list) -> list:
    """Per device, per layer ``{"flops", "bytes"}`` of one update.

    ``rows`` is ``[(owned, halo)]`` per device; one device with no halo is
    the centralized case.
    """
    work = []
    for owned, halo in rows:
        layers = []
        for f_in, f_out in zip(dims[:-1], dims[1:]):
            layers.append({
                "flops": sum(layer_flops(owned, sample, f_in, f_out)),
                "bytes": layer_bytes(owned, owned + halo, sample, f_in,
                                     f_out)})
        work.append(layers)
    return work


def work(model: dict, n_nodes: int, rows: list) -> tuple:
    """``(model FLOPs of one update, update_work per device)``; ``rows``
    as ``work.device_rows`` gives them."""
    dims, sample = widths(model), model["sample"]
    return (model_flops(n_nodes, sample, dims),
            update_work(dims, sample, rows))
