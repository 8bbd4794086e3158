"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Nothing runs here: each kernel is lowered and compiled for a v5e chip that
is described, not attached (``jax.experimental.topologies``), with
``interpret=False``. That catches what the CPU interpreter cannot: blocks
the TPU tiling refuses, SMEM or VMEM overruns, and dtypes Mosaic cannot
cast. Shapes are the collab (Table 2, F=496) and taxi (§4.2, F=216) widths,
at destination-row counts above the 16,384 rows that single-call SMEM
tables overran, with S=8.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.cam_match import search
from repro.kernels.crossbar_mvm import CrossbarNumerics
from repro.kernels.crossbar_mvm.ops import crossbar_matmul
from repro.kernels.csr_aggregate import aggregate
from repro.kernels.fused_layer import fused_gnn_layer

S = 8
# (name, table rows, destination rows, feature width)
WIDTHS = [("taxi", 10_000, 20_000, 216), ("collab", 372_475, 372_475, 496)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _layer_shapes(n, nd, f, h):
    return [((n, f), jnp.float32), ((nd, S), jnp.int32),
            ((nd, S), jnp.float32), ((f, h), jnp.float32),
            ((h,), jnp.float32)]


@pytest.mark.parametrize("name,n,nd,f", WIDTHS, ids=[w[0] for w in WIDTHS])
def test_csr_aggregate_compiles(one_chip, name, n, nd, f):
    text = _compiled_text(
        lambda x, nbr, wts: aggregate(x, nbr, wts, backend="pallas", bf=128,
                                      interpret=False),
        one_chip, *_layer_shapes(n, nd, f, 64)[:3])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,n,nd,f", WIDTHS, ids=[w[0] for w in WIDTHS])
@pytest.mark.parametrize("ideal", [True, False], ids=["ideal", "quant"])
def test_fused_layer_compiles(one_chip, name, n, nd, f, ideal):
    """Ideal numerics: ``fused_ideal_layer``; bit-accurate numerics: the
    ``fused_zmax`` scale pass plus ``fused_quant_layer``."""
    cfg = CrossbarNumerics(ideal=ideal)
    text = _compiled_text(
        lambda x, nbr, wts, w, b: fused_gnn_layer(
            x, nbr, wts, w, b, cfg, relu=True, bf=128, interpret=False),
        one_chip, *_layer_shapes(n, nd, f, 64))
    assert text.count("tpu_custom_call") >= (1 if ideal else 2)


@pytest.mark.parametrize("m,k,n", [(20_000, 496, 64), (20_000, 216, 64)])
def test_crossbar_matmul_compiles(one_chip, m, k, n):
    cfg = CrossbarNumerics()
    text = _compiled_text(
        lambda x, w: crossbar_matmul(x, w, cfg, interpret=False),
        one_chip, ((m, k), jnp.float32), ((k, n), jnp.float32))
    assert "tpu_custom_call" in text


def test_cam_search_compiles(one_chip):
    text = _compiled_text(
        lambda ci, q: search(ci, q, backend="pallas", interpret=False),
        one_chip, ((65_536,), jnp.int32), ((1024,), jnp.int32))
    assert "tpu_custom_call" in text
