"""The benchmark's own graphs and features, made from seeds.

The program's ``core/graph.random_graph`` / ``dataset_like`` statistics,
with a floor under the in-degree, so that the data does not move when the
program changes:

  * ``nodes`` nodes and ``edges`` edges;
  * every node has at least ``min_in_degree`` in-edges; the destinations
    of the remaining edges are drawn zipf(1.6) modulo ``nodes`` (the
    program's heavy tail); sources are uniform;
  * features N(0, 1) float32.

The weights the graph stores on its edges are the model kind's
(``bench/models/<kind>.py``).

The floor is the graph's, not the program's: collab's mean in-degree is
66, and ``dataset_like``'s zipf profile alone leaves 85% of the nodes with
no in-edge, so most of a neighbour sample would be padding. With a floor
of ``sample - 1`` every slot of the sample is a real neighbour.

Faster than the original, with the same tail: the in-degree vector of E
destinations drawn zipf(a) modulo N is multinomial over the residues,
with P(r) proportional to the Hurwitz zeta N^-a zeta(a, r/N) (r = N for
residue 0), so it is drawn in one multinomial call instead of E zipf draws
and a sort. Sources are independent of destinations, so they are drawn
straight in destination order. The structure comes from the
configuration's ``structure_seed``; features from the run's seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ZIPF_A = 1.6


def _hurwitz_zeta(a: float, q: np.ndarray, m: int = 8) -> np.ndarray:
    """sum_{j >= 0} (j + q)^-a for q in (0, 1]: m terms, then the
    Euler-Maclaurin tail (relative error under 1e-7 at a = 1.6)."""
    j = np.arange(m)[:, None]
    head = ((j + q[None, :]) ** -a).sum(axis=0)
    x = m + q
    return (head + x ** (1 - a) / (a - 1) + x ** -a / 2
            + a * x ** (-a - 1) / 12
            - a * (a + 1) * (a + 2) * x ** (-a - 3) / 720)


def zipf_mod_pmf(nodes: int, a: float = ZIPF_A) -> np.ndarray:
    """P(X mod nodes = r) for X ~ zipf(a) on 1, 2, ..., r = 0..nodes-1."""
    q = np.arange(1, nodes + 1) / nodes        # r = 1..N-1, then N for 0
    p = np.roll(_hurwitz_zeta(a, q), 1)
    return p / p.sum()


def csr(nodes: int, edges: int, structure_seed: int,
        min_in_degree: int) -> dict:
    """CSR structure: ``indptr [N+1] int64``, ``indices [E] int32`` (the
    sources of each destination row) and in-degrees ``deg [N]``."""
    tail = edges - nodes * min_in_degree
    if tail < 0:
        raise ValueError(f"{edges} edges cannot give {nodes} nodes "
                         f"{min_in_degree} in-edges each")
    rng = np.random.default_rng(structure_seed)
    deg = min_in_degree + rng.multinomial(
        tail, zipf_mod_pmf(nodes)).astype(np.int64)
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, nodes, size=edges, dtype=np.int32)
    return {"indptr": indptr, "indices": indices, "deg": deg}


def sample_table(g: dict, edge_weight: np.ndarray, self_loop: np.ndarray,
                 sample: int) -> tuple:
    """The model's fixed neighbour sample: each row holds its first
    ``sample - 1`` CSR neighbours, then its self loop, then padding (index
    0, weight 0). Returns ``(nbr [N, S] int32, wts [N, S] float32)``."""
    n = len(g["deg"])
    cap = sample - 1
    take = np.minimum(g["deg"], cap)
    pos = np.arange(sample)[None, :]
    real = pos < take[:, None]
    e = np.where(real, g["indptr"][:-1, None] + pos, 0)
    nbr = np.where(real, g["indices"][e], 0).astype(np.int32)
    wts = np.where(real, edge_weight[e], 0.0).astype(np.float32)
    rows = np.arange(n)
    nbr[rows, take] = rows
    wts[rows, take] = self_loop
    return nbr, wts


def seeds(seed: int, n: int) -> list:
    """``n`` independent 64-bit seeds from the run's ``--seed`` (any whole
    number; negative ones wrap)."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return [int(s) for s in ss.generate_state(n, np.uint64)]


def jax_key(seed64: int):
    """A JAX PRNG key holding all 64 bits of ``seed64``."""
    return jax.random.wrap_key_data(
        np.array([seed64 >> 32, seed64 & 0xFFFFFFFF], np.uint32))


def features(key, nodes: int, width: int) -> np.ndarray:
    """N(0, 1) float32 features ``[nodes, width]``, drawn on the default
    device in one call and returned on the host."""
    x = _normal(key, (nodes, width))
    out = np.asarray(x)
    x.delete()
    return out


@partial(jax.jit, static_argnums=1)
def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)
