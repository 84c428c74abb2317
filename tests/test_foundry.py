"""Variant foundry: spec grammar, cost calibration, registry, engine flow."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import foundry
from repro.core import compressors as C
from repro.core import engine, fp32_mul, hwmodel, nsga2, schemes, surrogate
from repro.kernels import ref

CHAR_N = 1 << 12  # characterization sample size for fast tests


@pytest.fixture()
def scoped_registry():
    with foundry.temporary_variants():
        yield


# ---------------------------------------------------------------------------
# Spec grammar
# ---------------------------------------------------------------------------


def test_spec_validation_errors():
    with pytest.raises(ValueError):
        foundry.Region(code=99).validate()
    with pytest.raises(ValueError):
        foundry.Region(code="pc9").validate()
    with pytest.raises(ValueError):
        foundry.Region(code=C.PC1, stages=(3,)).validate()
    with pytest.raises(ValueError):
        foundry.Region(code=C.PC1, cols=(5, 5)).validate()
    with pytest.raises(ValueError):  # approximate beyond the safe envelope
        foundry.PlacementSpec("bad", (foundry.Region(code=C.PC1, cols=(0, 32)),))
    # ... unless max_col explicitly relaxes it.
    foundry.PlacementSpec(
        "ok", (foundry.Region(code=C.PC1, cols=(0, 32)),), max_col=32
    )
    with pytest.raises(ValueError):
        foundry.PlacementSpec("", ())


def test_empty_spec_is_exact_map():
    m = foundry.PlacementSpec("noop", ()).to_map()
    np.testing.assert_array_equal(m, schemes.scheme_map("exact"))


def test_paper_patterns_expressible():
    """The grammar covers the paper's NI and CI patterns exactly."""
    ni = foundry.PlacementSpec(
        "ni", (foundry.Region(code=C.PC1, cols=(0, 24)),))
    np.testing.assert_array_equal(ni.to_map(), schemes.scheme_map("pm_ni"))
    ci = foundry.PlacementSpec("ci", (
        foundry.Region(code=C.PC1, cols=(0, 24), step=2, phase=0),
        foundry.Region(code=C.NC1, cols=(0, 24), step=2, phase=1),
    ))
    np.testing.assert_array_equal(ci.to_map(), schemes.scheme_map("pm_ci"))


def test_spec_from_map_roundtrip():
    want = schemes.scheme_map("nm_csi")
    spec = foundry.spec_from_map("rt", want)
    np.testing.assert_array_equal(spec.to_map(), want)


def test_default_family_distinct_and_valid():
    specs = foundry.default_family(8)
    assert len(specs) >= 8
    names = [s.name for s in specs]
    assert len(set(names)) == len(names)
    seen = set()
    for s in specs:
        key = s.to_map().tobytes()
        assert key not in seen, f"duplicate map: {s.name}"
        seen.add(key)
        assert s.n_approx > 0
        # No synthesized map may collide with a seed variant's map.
        for v in schemes.SEED_VARIANTS:
            assert not np.array_equal(s.to_map(), schemes.scheme_map(v)), (
                s.name, v)


# ---------------------------------------------------------------------------
# Hardware-cost calibration
# ---------------------------------------------------------------------------


def test_hwcost_reproduces_table1():
    assert foundry.calibrate().max_table_residual() < 1e-6


def test_hwcost_predictions_sane():
    model = foundry.calibrate()
    exact = hwmodel.TABLE_I["exact"]
    for s in foundry.default_family():
        pred = model.predict(s.to_map())
        for metric in ("area_um2", "power_uw", "delay_ps"):
            v = getattr(pred, metric)
            assert 0.5 * getattr(exact, metric) <= v <= getattr(exact, metric), (
                s.name, metric, v)
        assert pred.pdp_pj < exact.pdp_pj  # every approximation saves energy


def test_hwcost_depth_monotone():
    """Deeper single-code placements save monotonically more power."""
    model = foundry.calibrate()
    powers = []
    for d in (6, 12, 18, 24):
        spec = foundry.PlacementSpec(
            f"d{d}", (foundry.Region(code=C.PC1, cols=(0, d)),))
        powers.append(model.predict(spec.to_map()).power_uw)
    assert all(a > b for a, b in zip(powers, powers[1:])), powers


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------


def test_register_collision_contract(scoped_registry):
    spec = foundry.PlacementSpec(
        "fnd_t1", (foundry.Region(code=C.NC1, cols=(0, 8)),))
    r1 = foundry.register(spec, n=CHAR_N)
    assert r1.name in foundry.list_variants()
    with pytest.raises(ValueError, match="already registered"):
        foundry.register(spec, n=CHAR_N)
    r2 = foundry.register(spec, n=CHAR_N, overwrite=True)
    assert r2.variant_id == r1.variant_id  # append-only ids


def test_register_rolls_back_partial_state_on_failure(scoped_registry):
    """A failing register() must leave no orphaned moments/hw entries: the
    same name must be registerable immediately afterwards."""
    spec = foundry.PlacementSpec(
        "fnd_rollback", (foundry.Region(code=C.PC1, cols=(0, 8)),))
    with pytest.raises(TypeError):  # hw spec validated after moments landed
        foundry.register(spec, n=CHAR_N, hw="not-an-HwSpec")
    assert "fnd_rollback" not in foundry.list_variants()
    r = foundry.register(spec, n=CHAR_N)  # retry succeeds — no orphan
    assert r.name == "fnd_rollback"


def test_register_seed_names_always_rejected(scoped_registry):
    with pytest.raises(ValueError, match="seed variant"):
        foundry.register(
            foundry.PlacementSpec(
                "pm_ni", (foundry.Region(code=C.PC1, cols=(0, 8)),)),
            n=CHAR_N, overwrite=True)
    with pytest.raises(ValueError, match="seed variant"):
        schemes.register_variant(
            "exact", schemes.scheme_map("exact"), overwrite=True)


def test_temporary_variants_restores_alphabet():
    before = (schemes.variant_names(), len(hwmodel.PDP_PJ),
              len(surrogate.moment_tables()[0]))
    with foundry.temporary_variants():
        foundry.register(
            foundry.PlacementSpec(
                "fnd_scoped", (foundry.Region(code=C.PC1, cols=(0, 8)),)),
            n=CHAR_N)
        assert "fnd_scoped" in schemes.variant_names()
        assert len(hwmodel.PDP_PJ) == len(before[0]) + 1
    after = (schemes.variant_names(), len(hwmodel.PDP_PJ),
             len(surrogate.moment_tables()[0]))
    assert before == after


def test_engine_sequence_registry_contract():
    engine.register_sequence("fnd_seq_contract", np.asarray([1, 2], np.int32))
    assert "fnd_seq_contract" in engine.list_sequences()
    with pytest.raises(ValueError, match="already registered"):
        engine.register_sequence("fnd_seq_contract", np.asarray([3], np.int32))
    engine.register_sequence(
        "fnd_seq_contract", np.asarray([3], np.int32), overwrite=True)
    assert engine._REGISTERED_SEQUENCES["fnd_seq_contract"].tolist() == [3]


# ---------------------------------------------------------------------------
# Registered variants flow through the engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def registered():
    """Two registered foundry variants (PC-only and mixed), module-scoped so
    the characterization sweeps run once; restored after the module."""
    with foundry.temporary_variants():
        specs = (
            foundry.PlacementSpec(
                "fnd_flow_pc", (foundry.Region(code=C.PC1, cols=(0, 16)),)),
            foundry.PlacementSpec("fnd_flow_mix", (
                foundry.Region(code=C.NC2, cols=(0, 10)),
                foundry.Region(code=C.PC2, cols=(10, 20)),
            )),
        )
        yield foundry.register_family(specs, n=CHAR_N)


def test_surrogate_moments_calibrated(registered):
    mu, sg = surrogate.moment_tables()
    for r in registered:
        assert mu.shape[0] == len(schemes.VARIANTS)
        assert mu[r.variant_id] == np.float32(r.characterization.mre_normal)
        want_sg = np.sqrt(max(
            r.characterization.rmsre_normal ** 2
            - r.characterization.mre_normal ** 2, 0.0))
        assert np.isclose(sg[r.variant_id], want_sg, rtol=1e-6)


def test_hwmodel_tables_extended(registered):
    for r in registered:
        assert hwmodel.spec(r.name) == r.hw
        assert np.isclose(hwmodel.PDP_PJ[r.variant_id], r.hw.pdp_pj)
    cost = hwmodel.sequence_cost(
        np.array([0, registered[0].variant_id, registered[1].variant_id]))
    assert cost["pdp_benefit_pct"] > 0


def test_bitexact_backends_match_oracle(registered):
    """bitexact_ref / bitexact_pallas on a new variant == fp32_mul oracle."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 6)).astype(np.float32)
    w = rng.standard_normal((6, 5)).astype(np.float32)
    for r in registered:
        m = r.spec.to_map()
        prods = fp32_mul.fp32_multiply(
            jnp.asarray(x[:, :, None]), jnp.asarray(w[None, :, :]),
            jnp.asarray(m))
        want = np.asarray(ref.lane_sum(prods))  # the defined k order
        vids = np.full((6, 5), r.variant_id, np.int32)
        for backend in ("bitexact_ref", "bitexact_pallas"):
            got = np.asarray(engine.am_matmul(x, w, vids, backend=backend))
            assert (got.view(np.uint32) == want.view(np.uint32)).all(), (
                r.name, backend)


def test_all_backends_accept_expanded_maps(registered):
    rng = np.random.default_rng(4)
    x = rng.standard_normal((3, 8)).astype(np.float32)
    w = rng.standard_normal((8, 4)).astype(np.float32)
    vids = rng.integers(0, len(schemes.VARIANTS), (8, 4)).astype(np.int32)
    vids[0, 0] = registered[0].variant_id  # ensure a foundry id is present
    key = jax.random.PRNGKey(0)
    for backend in engine.backends():
        y = engine.am_matmul(x, w, vids, backend=backend, key=key)
        assert np.asarray(y).shape == (3, 4)
        assert np.isfinite(np.asarray(y)).all(), backend


def test_pallas_jit_cache_not_stale_across_registration():
    """Regression: the Pallas bit-exact kernels must not serve an executable
    with a pre-registration scheme stack baked in. Trace at a shape with the
    seed alphabet, register, then re-call the same shape with a foundry id —
    the stack is an operand whose shape keys the jit cache, so this must
    retrace and agree with the oracle."""
    rng = np.random.default_rng(11)
    x = rng.standard_normal((4, 7)).astype(np.float32)
    w = rng.standard_normal((7, 3)).astype(np.float32)
    seed_vids = np.full((7, 3), schemes.VARIANT_IDS["pm_csi"], np.int32)
    engine.am_matmul(x, w, seed_vids, backend="bitexact_pallas")  # warm trace
    with foundry.temporary_variants():
        r = foundry.register(
            foundry.PlacementSpec(
                "fnd_stale_chk", (foundry.Region(code=C.NC1, cols=(0, 20)),)),
            n=CHAR_N)
        m = r.spec.to_map()
        prods = fp32_mul.fp32_multiply(
            jnp.asarray(x[:, :, None]), jnp.asarray(w[None, :, :]),
            jnp.asarray(m))
        want = np.asarray(ref.lane_sum(prods))  # the defined k order
        got = np.asarray(engine.am_matmul(
            x, w, np.full((7, 3), r.variant_id, np.int32),
            backend="bitexact_pallas"))
        assert (got.view(np.uint32) == want.view(np.uint32)).all()


def test_register_unregister_cycles_bounded_recompiles_no_leaks():
    """Regression (codesign workload): thousands of transient registrations.

    A jitted consumer that takes the registry-backed tables as traced
    operands (the make_fast_evaluator pattern — their registry-sized shapes
    key the jit cache) must retrace once per distinct alphabet SIZE, not
    once per register/rollback cycle: 50 cycles through the same K must
    reuse two traces (K=9, K=10). Afterwards every registry and derived
    cache must be exactly at the seed state — no leaked names, moments,
    hardware rows or stale id-indexed tables.
    """
    import jax.numpy as jnp

    traces = []

    @jax.jit
    def consume(mu_t, sg_t, stack):
        traces.append(1)  # python side effect: runs only when tracing
        return mu_t.sum() + sg_t.sum() + stack.sum()

    def call():
        mu_t, sg_t = surrogate.moment_tables()
        consume(jnp.asarray(mu_t), jnp.asarray(sg_t),
                jnp.asarray(schemes.scheme_stack()))

    names0 = schemes.variant_names()
    spec = foundry.PlacementSpec(
        "fnd_churn", (foundry.Region(code=C.NC1, cols=(0, 12)),))
    char = foundry.characterize(spec, n=1 << 8)  # once; cycles reuse it
    hw = foundry.calibrate().predict(spec.to_map())

    call()  # K = 9 trace
    jit_cache0 = getattr(consume, "_cache_size", lambda: None)()
    for _ in range(50):
        with foundry.temporary_variants():
            foundry.register(spec, characterization=char, hw=hw)
            call()  # K = 10
        call()  # restored: K = 9
    assert len(traces) == 2, f"recompiled {len(traces)} times over 50 cycles"
    if jit_cache0 is not None:
        assert consume._cache_size() == jit_cache0 + 1
    # No leaked registry state in any of the three module registries.
    assert schemes.variant_names() == names0
    assert len(surrogate.moment_tables()[0]) == len(names0)
    assert len(surrogate.variant_stats()) == len(names0)
    assert hwmodel.PDP_PJ.shape == (len(names0),)
    assert schemes.scheme_stack().shape[0] == len(names0)
    with pytest.raises(KeyError):
        hwmodel.spec("fnd_churn")


def test_population_conv_with_expanded_alphabet(registered):
    """The NSGA-II population path (fused conv, CRN) accepts foundry ids and
    stays consistent with per-genome surrogate_xla calls."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 6, 6, 3)).astype(np.float32)
    w = rng.standard_normal((4, 3, 3, 3)).astype(np.float32)
    genomes = rng.integers(0, len(schemes.VARIANTS), (3, 4, 3, 3)).astype(np.int32)
    genomes[0] = registered[1].variant_id
    key = jax.random.PRNGKey(1)
    pop = np.asarray(engine.am_conv2d(
        x, w, genomes, backend="surrogate_fused", key=key, return_moments=True)[0])
    for p in range(3):
        one = np.asarray(engine.am_conv2d(
            x, w, genomes[p], backend="surrogate_fused", key=key,
            return_moments=True)[0])
        np.testing.assert_allclose(pop[p], one, rtol=1e-6)


# ---------------------------------------------------------------------------
# Dominance predicate + expanded-alphabet study (smoke)
# ---------------------------------------------------------------------------


def test_front_weakly_dominates():
    a = np.array([[1.0, 2.0], [2.0, 1.0]])
    b = np.array([[1.5, 2.5], [2.0, 1.0]])
    assert nsga2.front_weakly_dominates(a, b)
    assert not nsga2.front_weakly_dominates(b, a)
    assert nsga2.front_weakly_dominates(a, a)


def test_foundry_study_smoke():
    """Tiny-budget foundry_study: K >= 16 alphabet, expanded front weakly
    dominates the K=9 baseline front (guaranteed by the warm-started
    archive under a deterministic evaluator)."""
    from repro.experiments import paper_cnn

    params = paper_cnn.load_params()
    with foundry.temporary_variants():
        res = paper_cnn.foundry_study(
            params, k_target=16, n_images=64, pop_size=8, generations=2,
            char_n=CHAR_N, out_name=None, log=lambda s: None,
        )
    assert res["k_expanded"] >= 16
    assert res["weakly_dominates_baseline"]
    assert len(res["front"]) >= 1
    # Every registered variant is characterized and costed.
    for row in res["variants"]:
        assert row["hw"]["area_um2"] > 0
        assert row["characterization"]["n"] == CHAR_N
