"""Optimizer + data-pipeline invariants (unit + hypothesis property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st

from repro.data.tokens import TokenStream, synthetic_batch
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, compressed_psum, int8_compress,
                         int8_decompress)


# ------------------------------------------------------------ AdamW
def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw_init(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0, warmup=1)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(150):
        grads = jax.grad(loss)(params)
        params, state, _ = adamw_update(params, grads, state, cfg)
    assert float(loss(params)) < 1e-3


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       max_norm=st.floats(0.1, 10.0))
def test_clip_by_global_norm_property(seed, max_norm):
    k = jax.random.key(seed)
    g = {"a": jax.random.normal(k, (7,)) * 10,
         "b": jax.random.normal(jax.random.fold_in(k, 1), (3, 2)) * 10}
    clipped, gn = clip_by_global_norm(g, max_norm)
    cn = float(jnp.sqrt(sum(jnp.sum(x ** 2)
                            for x in jax.tree.leaves(clipped))))
    assert cn <= max_norm * 1.01
    if float(gn) <= max_norm:   # below threshold: untouched
        for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(clipped)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6)


def test_adamw_step_counter_and_dtype():
    params = {"w": jnp.zeros((4,), jnp.bfloat16)}
    state = adamw_init(params)
    g = {"w": jnp.ones((4,), jnp.bfloat16)}
    params, state, _ = adamw_update(params, g, state, AdamWConfig())
    assert int(state["step"]) == 1
    assert params["w"].dtype == jnp.bfloat16
    assert state["m"]["w"].dtype == jnp.float32


# ------------------------------------------------------------ compression
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 16), scale=st.floats(1e-3, 1e3))
def test_int8_roundtrip_error_bound(seed, scale):
    g = jax.random.normal(jax.random.key(seed), (64,)) * scale
    q, s, resid = int8_compress(g, jnp.zeros_like(g))
    back = int8_decompress(q, s)
    # quantization error bounded by one step, and captured by the residual
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) * 0.5 + 1e-9
    np.testing.assert_allclose(np.asarray(back + resid), np.asarray(g),
                               rtol=1e-5, atol=1e-6)


def test_error_feedback_unbiased_over_steps():
    """With error feedback, the cumulative transmitted sum tracks the true
    cumulative gradient (bias does not accumulate)."""
    g = jnp.full((16,), 0.003)
    resid = jnp.zeros_like(g)
    sent = jnp.zeros_like(g)
    for _ in range(200):
        q, s, resid = int8_compress(g, resid)
        sent = sent + int8_decompress(q, s)
    np.testing.assert_allclose(np.asarray(sent), np.asarray(g) * 200,
                               rtol=0.02)


def test_compressed_psum_matches_mean():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    g = jnp.linspace(-1, 1, 32).reshape(4, 8)
    r = jnp.zeros_like(g)
    fn = jax.shard_map(lambda g, r: compressed_psum(g, r, "data"),
                       mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    mean, _ = fn(g, r)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g), atol=0.02)


# ------------------------------------------------------------ data
def test_token_stream_deterministic_and_step_indexed():
    s1 = TokenStream(vocab=97, batch=4, seq=16, seed=3)
    s2 = TokenStream(vocab=97, batch=4, seq=16, seed=3)
    b5 = s1.batch_at(5)
    np.testing.assert_array_equal(np.asarray(b5["tokens"]),
                                  np.asarray(s2.batch_at(5)["tokens"]))
    assert not np.array_equal(np.asarray(b5["tokens"]),
                              np.asarray(s1.batch_at(6)["tokens"]))


def test_token_labels_shifted():
    b = synthetic_batch(53, 2, 12, seed=0, step=0)
    np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                  np.asarray(b["tokens"][:, 1:]))
    assert np.all(np.asarray(b["labels"][:, -1]) == -1)


def test_graph_batches_deterministic():
    from repro.core.graph import random_graph
    from repro.data.graphs import graph_batches
    g = random_graph(64, 256, 8, seed=0)
    it1 = graph_batches(g, 16, 4, seed=1)
    it2 = graph_batches(g, 16, 4, seed=1)
    for _ in range(3):
        b1, b2 = next(it1), next(it2)
        np.testing.assert_array_equal(b1["node_ids"], b2["node_ids"])
        np.testing.assert_array_equal(b1["neighbors"], b2["neighbors"])
