"""Crossbar MVM kernel: shape/dtype sweeps vs the pure-jnp oracle, plus
properties of the quantization numerics."""
import numpy as np
import pytest
import jax.numpy as jnp
from _hyp import given, settings, st

from repro.kernels.crossbar_mvm import (
    CrossbarNumerics, crossbar_matmul, crossbar_matmul_ref,
    crossbar_matmul_signed, crossbar_matmul_signed_ref)


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (64, 200, 96), (1, 512, 128),
                                   (33, 100, 7), (128, 128, 128)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_matches_oracle_shapes(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k + n)
    cfg = CrossbarNumerics(in_bits=4, w_bits=4, adc_bits=6, rows_per_xbar=64)
    x = jnp.abs(_rand(rng, (m, k), dtype))
    w = _rand(rng, (k, n), dtype)
    ref = crossbar_matmul_ref(x, w, cfg)
    out = crossbar_matmul(x, w, cfg, bm=8, bn=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("in_bits,w_bits,adc_bits,rows", [
    (8, 8, 8, 128), (4, 8, 6, 256), (2, 2, 4, 32), (8, 4, 10, 512)])
def test_matches_oracle_numerics_sweep(in_bits, w_bits, adc_bits, rows):
    rng = np.random.default_rng(in_bits * 7 + w_bits)
    cfg = CrossbarNumerics(in_bits=in_bits, w_bits=w_bits,
                           adc_bits=adc_bits, rows_per_xbar=rows)
    x = jnp.abs(_rand(rng, (16, 300), np.float32))
    w = _rand(rng, (300, 24), np.float32)
    ref = crossbar_matmul_ref(x, w, cfg)
    out = crossbar_matmul(x, w, cfg, bm=16, bn=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_signed_variant():
    rng = np.random.default_rng(3)
    cfg = CrossbarNumerics(in_bits=6, w_bits=6, adc_bits=8, rows_per_xbar=128)
    x = _rand(rng, (12, 160), np.float32)      # signed activations
    w = _rand(rng, (160, 40), np.float32)
    ref = crossbar_matmul_signed_ref(x, w, cfg)
    out = crossbar_matmul_signed(x, w, cfg, bm=4, bn=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_ideal_mode_is_exact_matmul():
    rng = np.random.default_rng(4)
    x, w = _rand(rng, (9, 33), np.float32), _rand(rng, (33, 5), np.float32)
    out = crossbar_matmul(x, w, CrossbarNumerics(ideal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), rtol=1e-5)


def test_quantization_error_shrinks_with_resolution():
    """Property of the numerics model: more DAC/ADC bits -> closer to ideal."""
    rng = np.random.default_rng(5)
    x = jnp.abs(_rand(rng, (32, 256), np.float32))
    w = _rand(rng, (256, 32), np.float32)
    ideal = np.asarray(x @ w)
    errs = []
    for bits in (2, 4, 8):
        cfg = CrossbarNumerics(in_bits=bits, w_bits=bits, adc_bits=bits + 4,
                               rows_per_xbar=128)
        y = np.asarray(crossbar_matmul_ref(x, w, cfg))
        errs.append(np.linalg.norm(y - ideal) / np.linalg.norm(ideal))
    assert errs[0] > errs[1] > errs[2], errs
    assert errs[2] < 0.05, errs


@pytest.mark.slow          # >10s on the CI CPU (--durations=15)
@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 24), k=st.integers(1, 100), n=st.integers(1, 24),
       seed=st.integers(0, 2**31 - 1))
def test_property_oracle_kernel_equivalence(m, k, n, seed):
    rng = np.random.default_rng(seed)
    cfg = CrossbarNumerics(in_bits=3, w_bits=3, adc_bits=5, rows_per_xbar=32)
    x = jnp.abs(_rand(rng, (m, k), np.float32))
    w = _rand(rng, (k, n), np.float32)
    ref = crossbar_matmul_ref(x, w, cfg)
    out = crossbar_matmul(x, w, cfg, bm=8, bn=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_quantized_kernel_rejects_nondivisible_shapes():
    """Regression: ``crossbar_matmul_quantized`` used to assert (or, under
    ``python -O``, crash deep in Pallas) on non-divisible M/K/N. It must
    raise an early ValueError naming the offending dim and pointing at the
    mapper API that produces valid shapes."""
    from repro.kernels.crossbar_mvm.crossbar_mvm import (
        crossbar_matmul_quantized)
    cfg = CrossbarNumerics(rows_per_xbar=128)
    ok = dict(m=128, k=256, n=128)

    def codes(m, k, n):
        return (jnp.zeros((m, k), jnp.int32), jnp.zeros((k, n), jnp.float32))

    # each dim individually non-divisible -> named in the error, which
    # also points at the mapper API producing valid shapes
    for dim, shape in (("M", dict(ok, m=100)), ("K", dict(ok, k=200)),
                       ("N", dict(ok, n=70))):
        xq, wq = codes(**shape)
        with pytest.raises(ValueError, match=rf"{dim}.*divisible") as ei:
            crossbar_matmul_quantized(xq, wq, cfg, interpret=True)
        assert "repro.mapper.tiling.padded_grid" in str(ei.value)
    # mismatched contraction dims
    with pytest.raises(ValueError, match="contraction mismatch"):
        crossbar_matmul_quantized(jnp.zeros((128, 256), jnp.int32),
                                  jnp.zeros((128, 128), jnp.float32),
                                  cfg, interpret=True)
    # the ops-layer wrapper maps the same shapes fine (mapper padding)
    rng = np.random.default_rng(9)
    x = jnp.abs(_rand(rng, (100, 200), np.float32))
    w = _rand(rng, (200, 70), np.float32)
    out = crossbar_matmul(x, w, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(crossbar_matmul_ref(x, w, cfg)),
                               rtol=1e-5, atol=1e-4)


def test_scale_invariance_property():
    """Quantization is scale-calibrated: y(ax, w) ~= a*y(x, w)."""
    rng = np.random.default_rng(6)
    cfg = CrossbarNumerics()
    x = jnp.abs(_rand(rng, (8, 64), np.float32))
    w = _rand(rng, (64, 8), np.float32)
    y1 = np.asarray(crossbar_matmul_ref(x, w, cfg))
    y2 = np.asarray(crossbar_matmul_ref(4.0 * x, w, cfg))
    np.testing.assert_allclose(y2, 4.0 * y1, rtol=1e-4, atol=1e-4)
