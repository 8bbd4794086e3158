"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Nothing runs here: each kernel is lowered and compiled for a v5e chip that
is described, not attached (``jax.experimental.topologies``), with
``interpret=False``. That catches what the CPU interpreter cannot: blocks
the TPU tiling refuses, SMEM or VMEM overruns, and dtypes Mosaic cannot
cast. Shapes are the collab (Table 2, F=496) and taxi (§4.2, F=216) widths,
at destination-row counts above the 16,384 rows that single-call SMEM
tables overran, with S=8. Every launch is also checked for its name and
its ``kernel_metadata``, which a profile's trace event of it carries.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""
import functools
import json
import math
import os
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import gnn
from repro.kernels.cam_match import search
from repro.kernels.crossbar_mvm import CrossbarNumerics
from repro.kernels.crossbar_mvm.ops import crossbar_matmul
from repro.kernels.csr_aggregate import aggregate
from repro.kernels.fused_layer import fused_gnn_layer, fused_layer

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


def _launch(kernel, **grid):
    return kernel, {"kernel": kernel, **{k: str(v) for k, v in grid.items()}}


# (kernel, the metadata its launch states) at small shapes: the fused and
# csr kernels gather chunks of 4,096 rows x 8 slots, F=216 padded to 128
# lanes (to a whole crossbar, 512 rows, on the bit-accurate path); the
# ideal kernel takes them 512 rows a grid step (two 4 MiB row buffers)
LAUNCHES = {
    "fused_ideal_layer": [_launch("fused_ideal_layer", rows=4096, slots=S,
                                  f_in=256, f_out=128, block_rows=512)],
    "fused_quant_layer": [_launch("fused_zmax", rows=4096, slots=S,
                                  f_in=512, f_out=2),
                          _launch("fused_quant_layer", rows=4096, slots=S,
                                  f_in=512, f_out=128)],
    "csr_aggregate": [_launch("csr_aggregate", rows=4096, slots=S, f_in=256,
                              f_out=256, f_blocks=2)],
    "crossbar_matmul_quantized": [_launch("crossbar_matmul_quantized",
                                          m_blocks=4, n_blocks=1,
                                          k_blocks=1)],
    "cam_search": [_launch("cam_search", query_blocks=16,
                           entry_blocks=64)],
}


def _compile_small(name, sharding):
    n, f, h = 10_000, 216, 64
    if name in ("fused_ideal_layer", "fused_quant_layer"):
        cfg = CrossbarNumerics(ideal=name == "fused_ideal_layer")
        return _compiled_text(
            lambda x, nbr, wts, w, b: fused_gnn_layer(
                x, nbr, wts, w, b, cfg, relu=True, bf=128, interpret=False),
            sharding, *_layer_shapes(n, n, f, h))
    if name == "csr_aggregate":
        return _compiled_text(
            lambda x, nbr, wts: aggregate(x, nbr, wts, backend="pallas",
                                          bf=128, interpret=False),
            sharding, *_layer_shapes(n, n, f, h)[:3])
    if name == "crossbar_matmul_quantized":
        return _compiled_text(
            lambda x, w: crossbar_matmul(x, w, CrossbarNumerics(),
                                         interpret=False),
            sharding, ((512, f), jnp.float32), ((f, h), jnp.float32))
    return _compiled_text(
        lambda ci, q: search(ci, q, backend="pallas", interpret=False),
        sharding, ((8192,), jnp.int32), ((128,), jnp.int32))


@pytest.mark.parametrize("name", list(LAUNCHES))
def test_launches_carry_their_name_and_metadata(one_chip, name):
    """Every launch is an instruction named after its kernel, and its
    ``kernel_metadata`` states the kernel and its grid: the trace event of
    a launch is that instruction's text. No fusion takes a launch in (a
    profile would name the fusion, and its event would carry neither)."""
    text = _compile_small(name, one_chip)
    assert not re.search(r"%[\w.]+ = [^\n]* fusion\([^\n]*kind=kCustom",
                         text)
    found = {}
    for m in re.finditer(r'%([\w.]+) = [^\n]*custom_call_target='
                         r'"tpu_custom_call"[^\n]*kernel_metadata=(\{.*?\})',
                         text, re.DOTALL):
        found[m.group(1).rsplit(".", 1)[0]] = json.loads(m.group(2))
    assert found == dict(LAUNCHES[name])


@pytest.fixture
def kernels_compiled(monkeypatch):
    """Forwards pick interpret mode for their kernels on a CPU host;
    compile them."""
    monkeypatch.setattr(fused_layer, "resolve_interpret", lambda i: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("prepared", [True, False], ids=["prepared", "raw"])
def test_prepared_feature_table_is_read_in_place(one_chip, kernels_compiled,
                                                 prepared):
    """The centralized fused forward at collab's size (372,475 x 496,
    GCN 496-64-16, S 8), compiled with the tables a plan prepares for it
    (``gnn.fused_operands``): outside the kernels' loops no copy, pad,
    reshape, transpose or fusion makes an array the size of the feature
    table, at 496 or 512 lanes; the first launch's loop reads the
    parameter through a bitcast. With the raw ``[1, N, 496]`` tables the
    same scan finds the relayout each update then pays, so the scan does
    see one."""
    n, f = 372_475, 496
    cfg = gnn.GNNConfig(in_dim=f, hidden_dims=(64,), out_dim=16, sample=S,
                        backend="fused")
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((1, n, f), jnp.float32), ((1, n, S), jnp.int32),
        ((1, n, S), jnp.float32))]
    if prepared:
        args = jax.eval_shape(functools.partial(
            gnn.fused_operands, cfg=cfg, n_rows=n, table=True), *args)
        assert args[0].shape == (1, n, 1, 512)
    dims = cfg.dims
    params = [{"w": jax.ShapeDtypeStruct((a, b), jnp.float32),
               "b": jax.ShapeDtypeStruct((b,), jnp.float32)}
              for a, b in zip(dims[:-1], dims[1:])]
    args, params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (args, params))
    text = gnn.centralized_forward.lower(params, *args, cfg).compile(
        ).as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    table = {n * f, n * 512}
    moved = [op for shape, op in re.findall(
        r"= f32\[([\d,]+)\]\S* ([\w-]+)\(", entry)
        if math.prod(int(d) for d in shape.split(",")) in table
        and op not in ("parameter", "bitcast", "get-tuple-element")]
    if prepared:
        assert moved == []
    else:
        assert moved
