"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import schemes, surrogate
from repro.kernels import ops, ref


@pytest.mark.slow
@pytest.mark.parametrize("m,k,n", [(8, 16, 16), (8, 32, 16), (16, 32, 32)])
def test_bitexact_matmul_kernel_vs_ref(rng, m, k, n):
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    vids = jnp.asarray(rng.integers(0, 9, (k, n)), jnp.int32)
    got = ops.am_matmul_bitexact(x, w, vids, block=(8, 16, 16))
    want = ref.am_matmul_bitexact_ref(x, w, vids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_bitexact_matmul_kernel_padding(rng):
    # Non-multiple shapes exercise the pad+crop path.
    x = jnp.asarray(rng.standard_normal((5, 19)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((19, 9)).astype(np.float32))
    vids = jnp.zeros((19, 9), jnp.int32)
    got = ops.am_matmul_bitexact(x, w, vids, block=(8, 16, 16))
    want = x @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6)


@pytest.mark.slow
@pytest.mark.parametrize("b,h,w,cin,f", [(2, 8, 8, 3, 4), (1, 10, 10, 3, 6)])
def test_bitexact_conv_kernel_vs_ref(rng, b, h, w, cin, f):
    x = jnp.asarray(rng.standard_normal((b, h, w, cin)).astype(np.float32))
    wgt = jnp.asarray(rng.standard_normal((f, 3, 3, cin)).astype(np.float32))
    sm = jnp.asarray(rng.integers(0, 9, (f, 3, 3)), jnp.int32)
    got = ops.am_conv2d_bitexact(x, wgt, sm, impl="kernel")
    want = ops.am_conv2d_bitexact(x, wgt, sm, impl="ref")
    # Kernel and oracle share the defined tap/channel summation order.
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_conv_exact_slots_match_lax_conv(rng):
    x = jnp.asarray(rng.standard_normal((2, 12, 12, 3)).astype(np.float32))
    wgt = jnp.asarray(rng.standard_normal((4, 3, 3, 3)).astype(np.float32))
    sm = jnp.zeros((4, 3, 3), jnp.int32)  # all exact
    got = ops.am_conv2d_bitexact(x, wgt, sm, impl="ref")
    want = ref.conv2d_exact_ref(x, wgt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-6, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (128, 256, 128)])
def test_surrogate_matmul_kernel_vs_ref(rng, m, k, n):
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    mu = jnp.full((k, n), 1e-6, jnp.float32)
    sg = jnp.full((k, n), 1e-7, jnp.float32)
    key = jax.random.PRNGKey(0)
    got = ops.am_surrogate_matmul(x, w, mu, sg, key, impl="kernel")
    want = ops.am_surrogate_matmul(x, w, mu, sg, key, impl="ref")
    # blocked-k accumulation order differs from the one-shot ref
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-3)


def test_surrogate_matmul_kernel_nonaligned(rng):
    x = jnp.asarray(rng.standard_normal((100, 200)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((200, 60)).astype(np.float32))
    mu = jnp.zeros((200, 60), jnp.float32)
    sg = jnp.zeros((200, 60), jnp.float32)
    key = jax.random.PRNGKey(1)
    got = ops.am_surrogate_matmul(x, w, mu, sg, key, impl="kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w), rtol=1e-5,
                               atol=1e-4)


def test_surrogate_folded_kernel_vs_formulation(rng):
    """The folded-weight Pallas kernel vs the plain-dot formulation: same
    contraction, blocked-k accumulation order."""
    m, k, n = 64, 96, 64
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    wm = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    wv = jnp.asarray((rng.standard_normal((k, n)) ** 2).astype(np.float32))
    mean_k, var_k = ops.am_surrogate_moments_folded(
        x, wm, wv, block=(32, 32, 32), impl="kernel")
    mean_r, var_r = ops.am_surrogate_moments_folded(x, wm, wv, impl="ref")
    np.testing.assert_allclose(np.asarray(mean_k), np.asarray(mean_r),
                               rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(var_k), np.asarray(var_r),
                               rtol=2e-5, atol=1e-3)


def test_surrogate_epilogue_kernel_single(rng):
    m, k, n = 48, 64, 32
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    wm = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    wv = jnp.asarray((rng.standard_normal((k, n)) ** 2).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    got = ops.am_surrogate_matmul_epilogue(
        x, wm, wv, z, block=(16, 16, 16), impl="kernel")
    want = ops.am_surrogate_matmul_epilogue(x, wm, wv, z, impl="fused_xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("pop_x", [False, True])
def test_surrogate_epilogue_kernel_population(rng, pop_x):
    """Population grid: per-genome folded weights, ONE z tile shared across
    the population axis (the engine's CRN invariant), non-aligned dims pad."""
    p, m, k, n = 3, 20, 40, 24
    xs = (p, m, k) if pop_x else (m, k)
    x = jnp.asarray(rng.standard_normal(xs).astype(np.float32))
    wm = jnp.asarray(rng.standard_normal((p, k, n)).astype(np.float32))
    wv = jnp.asarray((rng.standard_normal((p, k, n)) ** 2).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    got = ops.am_surrogate_matmul_epilogue(
        x, wm, wv, z, block=(16, 16, 16), impl="kernel")
    want = ops.am_surrogate_matmul_epilogue(x, wm, wv, z, impl="fused_xla")
    assert got.shape == (p, m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-3)


@pytest.mark.slow
def test_surrogate_moments_match_bitexact_statistics(rng):
    """Calibration: the surrogate's (mu, sigma) must reproduce the bit-exact
    AM's relative-error moments on standard-normal operands."""
    from repro.core import fp32_mul

    n = 1 << 14
    a = rng.standard_normal(n).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    exact = fp32_mul.fp32_multiply_batch(a, b, "exact")
    mu_t, sg_t = surrogate.moment_tables()
    for v in ("pm_ni", "nm_si"):
        ap = fp32_mul.fp32_multiply_batch(a, b, v)
        ok = np.isfinite(exact) & (exact != 0)
        rel = (ap[ok] - exact[ok]) / exact[ok].astype(np.float64)
        vid = schemes.VARIANT_IDS[v]
        assert abs(rel.mean() - mu_t[vid]) < 5e-8
        assert abs(rel.std() - sg_t[vid]) < 5e-8
