"""The plain reference of the served model, and its control.

A GCN over the model's fixed neighbour sample (``graphs.sample_table``):
per layer ``Z = sum_s wts[:, s] * H[nbr[:, s]]`` (slots summed in order),
then ``H = act(Z W + b)``, ReLU after every layer but the last. It is
written here in plain ``jax.numpy``, imports nothing of the program and
runs in blocks of rows, so that it fits beside nothing else on the chip.
Three precisions:

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
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("stated", "float32", "bfloat16")
JUDGED = "stated"   # the precision ``correct`` is decided against
BLOCK = 32768       # rows per aggregation call


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


def gaps(got: np.ndarray, ref: np.ndarray) -> tuple:
    """``(rms, max)``: the RMS and the largest absolute gap between ``got``
    and ``ref``, each over the same statistic of ``ref``. A shape mismatch or
    a value that is not finite reads as infinite."""
    got = np.asarray(got)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf"), float("inf")
    d = got.astype(np.float64) - ref.astype(np.float64)
    r = ref.astype(np.float64)
    rms = float(np.sqrt(np.mean(d * d)) / max(np.sqrt(np.mean(r * r)), 1e-30))
    mx = float(np.abs(d).max() / max(float(np.abs(r).max()), 1e-30))
    return rms, mx
