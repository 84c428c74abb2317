"""Cross-backend parity for the unified AM engine (core/engine.py).

Every backend must match the kernels/ref.py oracle on small shapes —
bit-equal for the bitexact_* backends, calibrated mean/var for the
surrogate_* backends — and population-axis calls must match the
corresponding per-genome calls.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def mm():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((5, 12)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((12, 7)).astype(np.float32))
    vids = rng.integers(0, 9, (12, 7)).astype(np.int32)
    return x, w, vids


@pytest.fixture(scope="module")
def cv():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 3)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((4, 3, 3, 3)).astype(np.float32))
    sm = rng.integers(0, 9, (4, 3, 3)).astype(np.int32)
    return x, w, sm


KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Matmul: every backend vs the oracle
# ---------------------------------------------------------------------------


def test_matmul_exact_backend(mm):
    x, w, _ = mm
    y = engine.am_matmul(x, w, backend="exact")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-6)


def test_matmul_bitexact_ref_is_oracle(mm):
    x, w, vids = mm
    y = engine.am_matmul(x, w, vids, backend="bitexact_ref")
    want = ref.am_matmul_bitexact_ref(x, w, vids)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


def test_matmul_bitexact_pallas_bit_equal():
    # The kernel is bit-equal to the oracle: both sum in the defined k order
    # (the chooser picks the smallest tile-legal block, (8, 8, 128)).
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    vids = rng.integers(0, 9, (8, 8)).astype(np.int32)
    assert ops.choose_block("bitexact_matmul", 4, 8, 8) == (8, 8, 128)
    y = engine.am_matmul(x, w, vids, backend="bitexact_pallas")
    want = ref.am_matmul_bitexact_ref(x, w, vids)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


def test_matmul_bitexact_pallas_padded_close(mm):
    # Non-multiple shapes pad to block multiples with exact zero slots,
    # which leave the defined-order sums unchanged: still bit-equal.
    x, w, vids = mm
    y = engine.am_matmul(x, w, vids, backend="bitexact_pallas")
    want = ref.am_matmul_bitexact_ref(x, w, vids)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


@pytest.mark.parametrize("backend", ["surrogate_xla", "surrogate_fused"])
def test_matmul_surrogate_moments_match_oracle(mm, backend):
    x, w, vids = mm
    mean, var = engine.am_matmul(x, w, vids, backend=backend, key=KEY,
                                 return_moments=True)
    mu, sg = engine.moment_maps(vids)
    want_mean, want_var = ref.am_surrogate_matmul_ref(
        x, w, jnp.asarray(mu), jnp.asarray(sg))
    np.testing.assert_allclose(np.asarray(mean), np.asarray(want_mean),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(want_var),
                               rtol=2e-3, atol=1e-12)


def test_matmul_surrogate_noise_is_deterministic(mm):
    x, w, vids = mm
    y1 = engine.am_matmul(x, w, vids, backend="surrogate_xla", key=KEY)
    y2 = engine.am_matmul(x, w, vids, backend="surrogate_xla", key=KEY)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    y3 = engine.am_matmul(x, w, vids, backend="surrogate_xla",
                          key=jax.random.PRNGKey(1))
    assert not np.array_equal(np.asarray(y1), np.asarray(y3))


def test_matmul_surrogate_requires_key(mm):
    x, w, vids = mm
    with pytest.raises(ValueError, match="PRNG key"):
        engine.am_matmul(x, w, vids, backend="surrogate_xla")


# ---------------------------------------------------------------------------
# surrogate_fused == surrogate_xla, bitwise, under CRN
# ---------------------------------------------------------------------------
#
# The fused backend folds the moment maps into the weights once and runs the
# vectorized (population-batched) formulation with the CRN draw applied as a
# GEMM epilogue. Folding and batching reorder NOTHING per output element, so
# the result must match the per-genome surrogate_xla op sequence bit for
# bit — including the shared-z CRN invariant across the population axis.


def _mm_pop(rng_seed=13, p=4):
    rng = np.random.default_rng(rng_seed)
    x = jnp.asarray(rng.standard_normal((6, 10)).astype(np.float32))
    xp = jnp.asarray(rng.standard_normal((p, 6, 10)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((10, 9)).astype(np.float32))
    pvids = rng.integers(0, 9, (p, 10, 9)).astype(np.int32)
    return x, xp, w, pvids


def test_fused_matmul_bitwise_parity_single(mm):
    x, w, vids = mm
    a = engine.am_matmul(x, w, vids, backend="surrogate_xla", key=KEY)
    b = engine.am_matmul(x, w, vids, backend="surrogate_fused", key=KEY)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_matmul_bitwise_parity_population():
    x, xp, w, pvids = _mm_pop()
    a = engine.am_matmul(x, w, pvids, backend="surrogate_xla", key=KEY)
    b = engine.am_matmul(x, w, pvids, backend="surrogate_fused", key=KEY)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # population x: one activation slab per genome
    a = engine.am_matmul(xp, w, pvids, backend="surrogate_xla", key=KEY)
    b = engine.am_matmul(xp, w, pvids, backend="surrogate_fused", key=KEY)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_matmul_bitwise_parity_moments():
    x, _, w, pvids = _mm_pop()
    ma, va = engine.am_matmul(x, w, pvids, backend="surrogate_xla", key=KEY,
                              return_moments=True)
    mb, vb = engine.am_matmul(x, w, pvids, backend="surrogate_fused", key=KEY,
                              return_moments=True)
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_fused_matmul_crn_shared_across_population():
    """z is a function of (key, single-genome output shape) ONLY: an
    all-exact genome inside a population reproduces the single-map call."""
    x, _, w, pvids = _mm_pop()
    pvids = np.asarray(pvids).copy()
    pvids[2] = 0  # genome 2 carries the all-exact map
    for backend in ("surrogate_xla", "surrogate_fused"):
        pop = engine.am_matmul(x, w, pvids, backend=backend, key=KEY)
        one = engine.am_matmul(x, w, np.zeros_like(pvids[2]),
                               backend=backend, key=KEY)
        np.testing.assert_array_equal(np.asarray(pop)[2], np.asarray(one))


def test_fold_matmul_weights_matches_xla_arithmetic():
    """Host-side folding uses exactly the surrogate_xla transform:
    w*(1+mu) and (w*w)*(sg*sg), elementwise f32."""
    _, _, w, pvids = _mm_pop()
    wm, wv = engine.fold_matmul_weights(
        w, engine.CanonicalMap(np.asarray(pvids), True))
    mu, sg = engine.moment_maps(np.asarray(pvids))
    wf = np.asarray(w, np.float32)
    np.testing.assert_array_equal(
        np.asarray(wm), (wf[None] * (1.0 + mu)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(wv), ((wf * wf)[None] * (sg * sg)).astype(np.float32))


# ---------------------------------------------------------------------------
# Conv2d: every backend vs the oracle
# ---------------------------------------------------------------------------


def test_conv_exact_backend(cv):
    x, w, _ = cv
    y = engine.am_conv2d(x, w, backend="exact")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.conv2d_exact_ref(x, w)), rtol=1e-6)


def test_conv_bitexact_ref_is_oracle(cv):
    x, w, sm = cv
    y = engine.am_conv2d(x, w, sm, backend="bitexact_ref")
    want = ref.am_conv2d_bitexact_ref(x, w, sm)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


@pytest.mark.slow
def test_conv_bitexact_pallas_close(cv):
    # 1-ulp tolerance: interpret-mode reduction trees differ from plain XLA
    # on CPU (see test_kernels.py::test_bitexact_conv_kernel_vs_ref).
    x, w, sm = cv
    y = engine.am_conv2d(x, w, sm, backend="bitexact_pallas")
    want = ref.am_conv2d_bitexact_ref(x, w, sm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=3e-6,
                               atol=2e-6)


@pytest.mark.parametrize("backend", ["surrogate_xla", "surrogate_fused"])
def test_conv_surrogate_moments_match_oracle(cv, backend):
    x, w, sm = cv
    mean, var = engine.am_conv2d(x, w, sm, backend=backend, key=KEY,
                                 return_moments=True)
    mu, sg = engine.moment_maps(sm)
    w_mu = w * (1.0 + jnp.asarray(mu)[..., None])
    w_sg2 = (w * w) * (jnp.asarray(sg) ** 2)[..., None]
    np.testing.assert_allclose(np.asarray(mean),
                               np.asarray(ref.conv2d_exact_ref(x, w_mu)),
                               rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(var),
                               np.asarray(ref.conv2d_exact_ref(x * x, w_sg2)),
                               rtol=2e-3, atol=1e-12)


def test_conv_exact_slot_map_zero_is_exact(cv):
    """All-exact variant ids through the surrogate backends degenerate to the
    exact conv (mu = sigma = 0)."""
    x, w, _ = cv
    zeros = np.zeros((4, 3, 3), np.int32)
    y = engine.am_conv2d(x, w, zeros, backend="surrogate_fused", key=KEY)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.conv2d_exact_ref(x, w)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Population axis vs per-genome calls
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend",
                         ["bitexact_ref", "surrogate_xla", "surrogate_fused"])
def test_matmul_population_vs_per_genome(mm, backend):
    x, w, _ = mm
    rng = np.random.default_rng(9)
    pop = rng.integers(0, 9, (3, 12, 7)).astype(np.int32)
    yp = engine.am_matmul(x, w, pop, backend=backend, key=KEY)
    assert yp.shape == (3, 5, 7)
    for p in range(3):
        y1 = engine.am_matmul(x, w, pop[p], backend=backend, key=KEY)
        if backend == "bitexact_ref":
            np.testing.assert_array_equal(np.asarray(yp[p]), np.asarray(y1))
        else:  # CRN: same key -> same noise realization across the population
            np.testing.assert_allclose(np.asarray(yp[p]), np.asarray(y1),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend",
                         ["bitexact_ref", "surrogate_xla", "surrogate_fused"])
def test_conv_population_vs_per_genome(cv, backend):
    x, w, _ = cv
    rng = np.random.default_rng(10)
    pop = rng.integers(0, 9, (4, 4, 3, 3)).astype(np.int32)
    yp = engine.am_conv2d(x, w, pop, backend=backend, key=KEY)
    assert yp.shape == (4, 2, 6, 6, 4)
    for p in range(4):
        y1 = engine.am_conv2d(x, w, pop[p], backend=backend, key=KEY)
        if backend == "bitexact_ref":
            np.testing.assert_array_equal(np.asarray(yp[p]), np.asarray(y1))
        else:
            np.testing.assert_allclose(np.asarray(yp[p]), np.asarray(y1),
                                       rtol=1e-5, atol=1e-6)


def test_conv_population_x_population_map(cv):
    """Layer-2 shape: both x and the slot map carry the population axis."""
    x, w, _ = cv
    rng = np.random.default_rng(11)
    pop = rng.integers(0, 9, (3, 4, 3, 3)).astype(np.int32)
    xp = jnp.asarray(rng.standard_normal((3,) + x.shape).astype(np.float32))
    yp = engine.am_conv2d(xp, w, pop, backend="surrogate_fused", key=KEY)
    assert yp.shape == (3, 2, 6, 6, 4)
    for p in range(3):
        y1 = engine.am_conv2d(xp[p], w, pop[p], backend="surrogate_fused", key=KEY)
        np.testing.assert_allclose(np.asarray(yp[p]), np.asarray(y1),
                                   rtol=1e-5, atol=1e-6)


def test_population_flat_genomes_roundtrip():
    rng = np.random.default_rng(12)
    g = rng.integers(0, 9, (5, 4 * 9)).astype(np.int32)
    cmap = engine.canonical_conv_map(g, 4, 3, 3)
    assert cmap.pop and cmap.vids.shape == (5, 4, 3, 3)
    np.testing.assert_array_equal(cmap.vids.reshape(5, -1), g)


# ---------------------------------------------------------------------------
# Canonicalization, auto-selection, block chooser
# ---------------------------------------------------------------------------


def test_matmul_map_spellings_agree():
    k = n = 16
    grid = np.array([[1, 2], [3, 4]], np.int32)
    a = engine.canonical_matmul_map(grid, k, n, tile_k=8, tile_n=8)
    b = engine.canonical_matmul_map(grid.ravel(), k, n, tile_k=8, tile_n=8)
    full = np.repeat(np.repeat(grid, 8, 0), 8, 1)
    c = engine.canonical_matmul_map(full, k, n, tile_k=8, tile_n=8)
    np.testing.assert_array_equal(a.vids, b.vids)
    np.testing.assert_array_equal(a.vids, c.vids)
    assert not a.pop


def test_policy_slot_maps():
    cm = engine.canonical_matmul_map("uniform:pm_csi", 16, 16, tile_k=8, tile_n=8)
    assert (cm.vids == cm.vids.flat[0]).all() and cm.vids.flat[0] != 0
    engine.register_sequence("eng_test", np.asarray([1, 2], np.int32))
    cm2 = engine.canonical_matmul_map("seq:eng_test", 16, 16, tile_k=8, tile_n=8)
    assert set(np.unique(cm2.vids)) == {1, 2}


def test_map_validation_errors():
    with pytest.raises(ValueError):
        engine.canonical_matmul_map(np.zeros(5, np.int32), 16, 16)
    with pytest.raises(ValueError):
        engine.canonical_conv_map(np.zeros(7, np.int32), 4, 3, 3)
    with pytest.raises(ValueError):
        engine.am_matmul(jnp.zeros((2, 4)), jnp.zeros((4, 4)),
                         np.zeros((3, 4, 4), np.int32), backend="exact",
                         x_population=True)


def test_auto_selector(mm):
    x, w, vids = mm
    # no map -> exact
    y = engine.am_matmul(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-6)
    # small + map -> bit-exact oracle
    y = engine.am_matmul(x, w, vids)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(ref.am_matmul_bitexact_ref(x, w, vids)))
    # all-exact map -> exact backend regardless of size
    assert engine.select_backend("matmul", has_map=False, work=1 << 40) == "exact"
    assert engine.select_backend("matmul", has_map=True, work=1 << 40) == \
        "surrogate_fused"


def test_block_chooser_budgets():
    # Every autotuner candidate — hence the chosen block — fits the kernel's
    # VMEM budget; divisibility of padded dims holds by construction (pow2
    # candidates over pow2-padded dims).
    for kind, m, k, n, fits in [
        ("bitexact_matmul", 1024, 1024, 1024,
         lambda b: ops._bitexact_live_bytes(*b) <= ops.BITEXACT_VMEM_BUDGET),
        ("surrogate_matmul", 512, 512, 512,
         lambda b: (b[0] * b[1] + 3 * b[1] * b[2] + 3 * b[0] * b[2]) * 4
         <= ops.VMEM_BYTES),
    ]:
        cands = ops.candidate_blocks(kind, m, k, n)
        assert cands and all(fits(b) for b in cands)
        assert ops.choose_block(kind, m, k, n) in cands
    # tighter budget shrinks the block
    big = ops.choose_block("bitexact_matmul", 1024, 1024, 1024)
    sm = ops.choose_block("bitexact_matmul", 1024, 1024, 1024,
                          vmem_bytes=4 << 20)
    assert ops._bitexact_live_bytes(*sm) <= 4 << 20
    assert np.prod(sm) < np.prod(big)
    # every bit-exact candidate is tile-legal for Mosaic
    for bm, bk, bn in ops.candidate_blocks("bitexact_matmul", 1024, 1024, 1024):
        assert bm % 8 == 0 and bk % 128 == 0 and bn % 128 == 0
    bm, bk, bn = ops.choose_block("surrogate_matmul", 512, 512, 512,
                                  vmem_bytes=256 * 1024)
    assert (bm * bk + 3 * bk * bn + 3 * bm * bn) * 4 <= 256 * 1024
    # blocks never exceed (the pow2 ceiling of) the problem dims
    bm, bk, bn = ops.choose_block("surrogate_matmul", 5, 12, 7)
    assert bm <= 8 and bk <= 16 and bn <= 8


def test_block_chooser_cache_deterministic(tmp_path, monkeypatch):
    """choose_block is a pure function of (kind, shape, budget) and its
    decisions round-trip through the on-disk tuning cache."""
    cache = tmp_path / "tuning_cache.json"
    monkeypatch.setenv(ops.TUNING_CACHE_ENV, str(cache))
    ops.clear_tuning_cache()
    try:
        first = ops.choose_block("surrogate_matmul", 300, 200, 100)
        assert cache.exists()  # autosaved on the miss
        entry = json.loads(cache.read_text())
        assert list(first) in list(entry.values())
        # A cold chooser (fresh in-memory cache) must reload the same
        # decision from disk, and re-tuning must agree with it.
        ops.clear_tuning_cache()
        assert ops.choose_block("surrogate_matmul", 300, 200, 100) == first
        assert ops.autotune_block("surrogate_matmul", 300, 200, 100) == first
    finally:
        monkeypatch.delenv(ops.TUNING_CACHE_ENV)
        ops.clear_tuning_cache()


def test_bitexact_return_moments_is_point_distribution(mm, cv):
    """Deterministic backends honor return_moments: mean = output, var = 0."""
    x, w, vids = mm
    mean, var = engine.am_matmul(x, w, vids, backend="bitexact_ref",
                                 return_moments=True)
    np.testing.assert_array_equal(
        np.asarray(mean), np.asarray(ref.am_matmul_bitexact_ref(x, w, vids)))
    assert not np.any(np.asarray(var))
    xc, wc, sm = cv
    mean, var = engine.am_conv2d(xc, wc, sm, backend="bitexact_ref",
                                 return_moments=True)
    assert mean.shape == var.shape == (2, 6, 6, 4)
    assert not np.any(np.asarray(var))


def test_fused_conv_jits_over_traced_weights(cv):
    """surrogate_fused folds in-graph when w is a jit argument (training /
    vmap consumers), matching the host-folded eager result."""
    x, w, sm = cv
    fn = jax.jit(lambda ww: engine.am_conv2d(
        x, ww, sm, backend="surrogate_fused", key=KEY))
    np.testing.assert_allclose(
        np.asarray(fn(w)),
        np.asarray(engine.am_conv2d(x, w, sm, backend="surrogate_fused", key=KEY)),
        rtol=1e-6, atol=1e-6)


def test_engine_matmul_batched_x(mm):
    """(B, S, K) inputs flatten through the backends and restore shape."""
    _, w, vids = mm
    rng = np.random.default_rng(13)
    x3 = jnp.asarray(rng.standard_normal((2, 3, 12)).astype(np.float32))
    y = engine.am_matmul(x3, w, vids, backend="surrogate_xla", key=KEY)
    assert y.shape == (2, 3, 7)
    y2 = engine.am_matmul(x3.reshape(6, 12), w, vids, backend="surrogate_xla",
                          key=KEY)
    np.testing.assert_allclose(np.asarray(y.reshape(6, 7)), np.asarray(y2),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Canonicalization properties — shared property bodies
#
# The checks live here as plain helpers so they run everywhere on fixed cases;
# tests/test_engine_property.py sweeps the same bodies under hypothesis
# (skipped where hypothesis is not installed, like test_compressors_property).
# ---------------------------------------------------------------------------


def check_matmul_map_property(gk, gn, tk, tn, seed):
    """Flat sequence, tile grid, and expanded full map are one canonical map
    sharing the byte-level memo key, and canonicalization is idempotent."""
    k, n = gk * tk, gn * tn
    grid = np.random.default_rng(seed).integers(0, 9, (gk, gn)).astype(np.int32)
    full = np.repeat(np.repeat(grid, tk, 0), tn, 1)
    maps = [
        engine.canonical_matmul_map(m, k, n, tile_k=tk, tile_n=tn)
        for m in (grid, grid.ravel(), full)
    ]
    for m in maps[1:]:
        np.testing.assert_array_equal(maps[0].vids, m.vids)
        assert maps[0].vids.tobytes() == m.vids.tobytes()  # same memo key
    assert not any(m.pop for m in maps)
    twice = engine.canonical_matmul_map(maps[0].vids, k, n, tile_k=tk, tile_n=tn)
    np.testing.assert_array_equal(maps[0].vids, twice.vids)
    assert not twice.pop


def check_policy_map_property(policy, gk, gn):
    """A policy string canonicalizes identically on every call (the cached
    sequence), and re-canonicalizing its vids is the identity."""
    k, n = gk * 2, gn * 2
    a = engine.canonical_matmul_map(policy, k, n, tile_k=2, tile_n=2)
    b = engine.canonical_matmul_map(policy, k, n, tile_k=2, tile_n=2)
    np.testing.assert_array_equal(a.vids, b.vids)
    c = engine.canonical_matmul_map(a.vids, k, n, tile_k=2, tile_n=2)
    np.testing.assert_array_equal(a.vids, c.vids)


def check_conv_map_property(f, kh, kw, pop, seed):
    """Flat and full conv spellings agree (with and without a population
    axis), share the memo key, and canonicalize idempotently."""
    rng = np.random.default_rng(seed)
    if pop == 0:
        vids = rng.integers(0, 9, (f, kh, kw)).astype(np.int32)
        flat = vids.ravel()
    else:
        vids = rng.integers(0, 9, (pop, f, kh, kw)).astype(np.int32)
        flat = vids.reshape(pop, -1)
    a = engine.canonical_conv_map(vids, f, kh, kw)
    b = engine.canonical_conv_map(flat, f, kh, kw)
    np.testing.assert_array_equal(a.vids, b.vids)
    assert a.pop == b.pop == (pop > 0)
    assert a.vids.tobytes() == b.vids.tobytes()  # same memo key
    c = engine.canonical_conv_map(a.vids, f, kh, kw)
    np.testing.assert_array_equal(a.vids, c.vids)


def check_multiset_memo_property(length, seed):
    """Position-agnostic memo keys alias all permutations of one multiset —
    the paper's multiset fitness: one evaluation, identical objectives."""
    from repro.core import nsga2

    rng = np.random.default_rng(seed)
    g = rng.integers(0, 9, length).astype(np.int32)
    perm = rng.permutation(g).astype(np.int32)
    calls = []

    def fn(batch):
        calls.append(batch.shape[0])
        return batch.sum(1, keepdims=True).astype(float)

    ev = nsga2.BatchEvaluator(fn, position_agnostic=True)
    o1, o2 = ev([g, perm])
    assert sum(calls) == 1  # one multiset -> one evaluation
    np.testing.assert_array_equal(o1, o2)


@pytest.mark.parametrize("gk,gn,tk,tn,seed",
                         [(2, 2, 1, 1, 0), (3, 5, 2, 3, 1), (5, 2, 3, 1, 2)])
def test_matmul_map_property_fixed(gk, gn, tk, tn, seed):
    check_matmul_map_property(gk, gn, tk, tn, seed)


@pytest.mark.parametrize("policy", ["uniform:pm_csi", "uniform:exact", "rr:4"])
def test_policy_map_property_fixed(policy):
    check_policy_map_property(policy, 3, 2)


@pytest.mark.parametrize("f,kh,kw,pop,seed",
                         [(4, 3, 3, 0, 0), (1, 1, 2, 0, 1), (6, 2, 3, 3, 2),
                          (2, 3, 3, 1, 3)])
def test_conv_map_property_fixed(f, kh, kw, pop, seed):
    check_conv_map_property(f, kh, kw, pop, seed)


@pytest.mark.parametrize("length,seed", [(1, 0), (8, 1), (198, 2)])
def test_multiset_memo_property_fixed(length, seed):
    check_multiset_memo_property(length, seed)
