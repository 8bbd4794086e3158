"""Both cells' forwards compile for a TPU v5e at their full size.

Nothing runs: each forward is lowered and compiled for chips that are
described, not attached, with the Pallas kernels compiled (not
interpreted). The topology is described inside a module fixture, never at
import: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import gnn
from repro.distributed import halo
from repro.kernels.fused_layer import fused_layer

N, F, S, DIMS = 372_475, 496, 8, (496, 64, 16)
# the collab-gcn-dec4 plan (structure seed 0, partition seed 0): owned rows
# per cluster, halo rows, rows one device sends one peer
N_MAX, H_MAX, S_MAX = 93_119, 228_973, 77_020
HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def kernels_compiled(monkeypatch):
    """The kernels pick interpret mode on a CPU host; compile them."""
    monkeypatch.setattr(fused_layer, "resolve_interpret", lambda i: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(sharding):
    return [{"w": _sds((a, b), jnp.float32, sharding),
             "b": _sds((b,), jnp.float32, sharding)}
            for a, b in zip(DIMS[:-1], DIMS[1:])]


CFG = gnn.GNNConfig(in_dim=DIMS[0], hidden_dims=DIMS[1:-1],
                    out_dim=DIMS[-1], sample=S, backend="fused")


def test_collab_refresh_forward_compiles(topo, kernels_compiled):
    one = SingleDeviceSharding(topo.devices[0])
    compiled = gnn.centralized_forward.lower(
        _params(one), _sds((1, N, F), jnp.float32, one),
        _sds((1, N, S), jnp.int32, one), _sds((1, N, S), jnp.float32, one),
        CFG).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM


def test_collab_dec4_forward_compiles(topo, kernels_compiled):
    k = 4
    mesh = Mesh(np.array(topo.devices[:k]), ("data",))
    z = lambda *shape: np.zeros(shape, np.int32)        # noqa: E731
    plan = halo.HaloPlan(z(k, H_MAX), z(k, H_MAX), z(k, H_MAX) == 0,
                         z(k, k, S_MAX), z(k, k, S_MAX) == 0,
                         z(k, k, S_MAX), z(k, k, S_MAX) == 0)
    fwd = halo.make_decentralized_forward(mesh, CFG, plan, N_MAX,
                                          mode="alltoall")
    shard = NamedSharding(mesh, PartitionSpec("data"))
    compiled = fwd.lower(
        _params(NamedSharding(mesh, PartitionSpec())),
        _sds((k, N_MAX, F), jnp.float32, shard),
        _sds((k, N_MAX, S), jnp.int32, shard),
        _sds((k, N_MAX, S), jnp.float32, shard)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
