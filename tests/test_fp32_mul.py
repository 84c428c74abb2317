"""Bit-exact FP32 AM emulator: structure + IEEE contract tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import booth, errors, fp32_mul, schemes


def test_booth_ppm_row_sum_equals_product(rng):
    a = rng.integers(0, 1 << 24, 256).astype(np.int64)
    b = rng.integers(0, 1 << 24, 256).astype(np.int64)
    ppm = np.asarray(booth.booth_ppm(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32)))
    w = (1 << np.arange(48, dtype=np.int64))
    total = (ppm.astype(np.int64) * w).sum(axis=(-2, -1)) % (1 << 48)
    np.testing.assert_array_equal(total, (a * b) % (1 << 48))


def test_exact_tree_matches_integer_product(rng):
    a = rng.integers(0, 1 << 24, 128).astype(np.int64)
    b = rng.integers(0, 1 << 24, 128).astype(np.int64)
    codes = jnp.asarray(schemes.scheme_map("exact"))
    bits = np.asarray(fp32_mul.mantissa_multiply_bits(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32), codes))
    w = (1 << np.arange(48, dtype=np.int64))
    np.testing.assert_array_equal((bits * w).sum(-1), a * b)


@pytest.mark.slow
def test_exact_multiplier_within_1ulp_of_rne(rng):
    a, b = errors.random_fp32_operands(5000, seed=7)
    got = fp32_mul.fp32_multiply_batch(a, b, "exact")
    true = (a.astype(np.float64) * b.astype(np.float64)).astype(np.float32)
    rel = np.abs(got.astype(np.float64) - true) / np.abs(true)
    assert rel.max() <= 1.2e-7  # truncation: <= 1 ulp below RNE


def test_ieee_specials():
    f = lambda x, y: float(fp32_mul.fp32_multiply_variant(
        jnp.float32(x), jnp.float32(y), "pm_csi"))
    assert np.isnan(f(np.nan, 1.0))
    assert np.isnan(f(np.inf, 0.0))
    assert f(np.inf, 2.0) == np.inf
    assert f(np.inf, -2.0) == -np.inf
    assert f(0.0, 5.0) == 0.0
    assert f(-0.0, 5.0) == 0.0 or f(-0.0, 5.0) == -0.0


def test_overflow_to_inf_and_ftz():
    big = np.float32(1e38)
    assert np.isinf(float(fp32_mul.fp32_multiply_variant(big, big, "exact")))
    tiny = np.float32(1e-38)
    # subnormal output flushes to zero
    assert float(fp32_mul.fp32_multiply_variant(tiny, tiny, "exact")) == 0.0


def test_subnormal_inputs_honored():
    sub = np.float32(1e-40)  # subnormal
    got = float(fp32_mul.fp32_multiply_variant(sub, np.float32(1e30), "exact"))
    true = float(np.float64(sub) * 1e30)
    assert got == pytest.approx(true, rel=2e-7)


def test_variant_ids_roundtrip():
    assert schemes.VARIANTS[0] == "exact"
    assert len(schemes.AM_VARIANTS) == 8
    stack = schemes.scheme_stack()
    assert stack.shape == (9, 3, 48)
    for i, v in enumerate(schemes.VARIANTS):
        np.testing.assert_array_equal(stack[i], schemes.scheme_map(v))


@pytest.mark.slow
def test_interleaved_multiply_matches_per_variant(rng):
    a = rng.standard_normal(64).astype(np.float32)
    b = rng.standard_normal(64).astype(np.float32)
    vids = rng.integers(0, 9, 64)
    mixed = np.asarray(fp32_mul.fp32_multiply_interleaved(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(vids, jnp.int32)))
    for v in range(9):
        mask = vids == v
        if not mask.any():
            continue
        pure = np.asarray(fp32_mul.fp32_multiply_variant(
            jnp.asarray(a[mask]), jnp.asarray(b[mask]), schemes.VARIANTS[v]))
        np.testing.assert_array_equal(mixed[mask], pure)


def _random_bits(rng, n):
    """Uniform float32 bit patterns plus the IEEE specials and subnormals."""
    sp = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-40, -1e-42, 1e38,
                   3e-39, 1.0, -1.0, 2.0 ** -126], np.float32)
    a_sp, b_sp = (g.ravel() for g in np.meshgrid(sp, sp))
    bits = rng.integers(0, 1 << 32, (2, n), dtype=np.uint64).astype(np.uint32)
    return (np.concatenate([bits[0].view(np.float32), a_sp]),
            np.concatenate([bits[1].view(np.float32), b_sp]))


@pytest.mark.parametrize("which", ["seed", "random"])
def test_packed_multiply_matches_bit_tensor(rng, which):
    """The kernels' packed (limb + column-mask) spelling is bit-identical to
    the bit-tensor emulation for every seed variant and for random maps."""
    a, b = _random_bits(rng, 2048)
    if which == "seed":
        maps = schemes.scheme_stack()
    else:
        maps = rng.integers(0, 5, (4, 3, 48)).astype(np.int32)
    masks = fp32_mul.code_masks(maps)
    for m, words in zip(maps, masks):
        want = np.asarray(fp32_mul._fp32_multiply_jit(a, b, jnp.asarray(m)))
        got = np.asarray(jax.jit(fp32_mul.fp32_multiply_packed)(
            a, b, [jnp.int32(w) for w in words]))
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
