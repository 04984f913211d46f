"""Pallas kernels in interpret mode vs their jnp references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_quant_bin_sparsify_matches_reference():
    from msrflute_tpu.ops.pallas_kernels import quant_bin_sparsify
    from msrflute_tpu.ops.quantization import quantize_array
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(5000,)), jnp.float32)
    lo, hi = jnp.min(g), jnp.max(g)
    thresh = jnp.quantile(jnp.abs(g), 0.5)
    out = quant_bin_sparsify(g, lo, hi, thresh, n_bins=16, interpret=True)
    ref = quantize_array(g, n_bins=16, quant_threshold=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_bits_to_normal_statistics():
    """CPU validation of the DP-critical Box-Muller transform with REAL
    random bits (jax.random.bits) — the same function the kernel applies
    to the on-core PRNG stream.  A wrong sigma here silently under-noises
    every global-DP update so pin the first four
    moments and the 3-sigma tail mass against N(0,1).  The on-chip test
    below then only has the PRNG plumbing left to cover."""
    from msrflute_tpu.ops.pallas_kernels import bits_to_normal
    n = 1 << 21
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    b1 = jax.random.bits(k1, (n,), jnp.uint32)
    b2 = jax.random.bits(k2, (n,), jnp.uint32)
    z = np.asarray(bits_to_normal(b1, b2), np.float64)
    assert np.isfinite(z).all()
    # standard errors at n=2^21: mean 7e-4, std 5e-4, skew 1.7e-3,
    # excess kurtosis 3.4e-3 — bounds are ~6 sigma
    assert abs(z.mean()) < 5e-3, z.mean()
    assert abs(z.std() - 1.0) < 5e-3, z.std()
    zc = z - z.mean()
    assert abs((zc ** 3).mean()) < 2e-2            # skewness
    assert abs((zc ** 4).mean() - 3.0) < 5e-2      # kurtosis
    tail = float((np.abs(z) > 3.0).mean())
    assert abs(tail - 0.0027) < 5e-4, tail         # P(|Z|>3)
    # independence across the two bit draws: u1/u2 must not correlate
    z2 = np.asarray(bits_to_normal(b2, b1), np.float64)
    assert abs(np.corrcoef(z, z2)[0, 1]) < 5e-3


def test_bits_to_normal_worst_case_bits_finite():
    """Degenerate bit patterns must stay finite: all-zero bits hit the
    log(0) guard (|z| capped ~7.43), all-one bits the u1→1 corner."""
    from msrflute_tpu.ops.pallas_kernels import bits_to_normal
    for b1 in (0, 0xFFFFFFFF):
        for b2 in (0, 0xFFFFFFFF):
            z = np.asarray(bits_to_normal(
                jnp.full((8,), b1, jnp.uint32),
                jnp.full((8,), b2, jnp.uint32)))
            assert np.isfinite(z).all()
            assert np.abs(z).max() < 7.5


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="the TPU interpreter stubs prng_random_bits to "
                           "zeros; on-chip PRNG plumbing (the transform "
                           "itself is CPU-validated above) needs a chip")
def test_fused_gaussian_noise_stats_tpu():
    from msrflute_tpu.ops.pallas_kernels import fused_gaussian_noise
    x = jnp.ones((200_000,), jnp.float32) * 3.0
    out = fused_gaussian_noise(x, scale=jnp.asarray(2.0),
                               sigma=jnp.asarray(0.5),
                               seed=jnp.asarray(42))
    arr = np.asarray(out)
    assert abs(arr.mean() - 6.0) < 0.02
    assert abs(arr.std() - 0.5) < 0.02
    out3 = fused_gaussian_noise(x, jnp.asarray(2.0), jnp.asarray(0.5),
                                jnp.asarray(43))
    assert not np.array_equal(np.asarray(out3), arr)


def test_fused_gaussian_noise_shape_roundtrip():
    """Interpret mode can still validate shapes/padding (PRNG is stubbed)."""
    from msrflute_tpu.ops.pallas_kernels import fused_gaussian_noise
    x = jnp.arange(40_000, dtype=jnp.float32)
    out = fused_gaussian_noise(x, jnp.asarray(1.0), jnp.asarray(1.0),
                               jnp.asarray(0), interpret=True)
    assert out.shape == x.shape


def test_noise_zero_sigma_is_pure_scale():
    from msrflute_tpu.ops.pallas_kernels import fused_gaussian_noise
    x = jnp.arange(1000, dtype=jnp.float32)
    out = fused_gaussian_noise(x, jnp.asarray(3.0), jnp.asarray(0.0),
                               jnp.asarray(0), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 3.0,
                               rtol=1e-6)
