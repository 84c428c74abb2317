"""HLO cost-extraction parser: exact flops/collectives on known graphs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.roofline import analysis, hlo_costs


def test_scan_trip_count_multiplies_flops():
    def f(w, x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile()
    res = hlo_costs.analyze(co.as_text())
    assert res.flops == pytest.approx(5 * 2 * 8 * 64 * 64, rel=0.01)
    assert any(t == 5 for _, t in res.while_trips)


def test_nested_scan_trips_compose():
    def f(w, x):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None

        out, _ = jax.lax.scan(outer, x, None, length=4)
        return out

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
        jax.ShapeDtypeStruct((4, 16), jnp.float32)).compile()
    res = hlo_costs.analyze(co.as_text())
    assert res.flops == pytest.approx(12 * 2 * 4 * 16 * 16, rel=0.01)


def test_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((3, 8, 16), jnp.float32),
        jax.ShapeDtypeStruct((3, 16, 4), jnp.float32)).compile()
    res = hlo_costs.analyze(co.as_text())
    assert res.flops == pytest.approx(2 * 3 * 8 * 16 * 4, rel=0.01)


def test_xla_cost_analysis_undercounts_scans():
    """Documents WHY we parse HLO ourselves: XLA:CPU cost_analysis counts a
    scanned matmul once, not trip_count times."""
    def f(w, x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64), jnp.float32)).compile()
    raw = co.cost_analysis()
    if isinstance(raw, list):
        raw = raw[0]
    ours = hlo_costs.analyze(co.as_text()).flops
    assert ours >= 9 * float(raw.get("flops", 0.0))


def test_collective_bytes_on_sharded_matmul():
    """hlo_costs counts AG/AR payloads on a TP-sharded matmul (subprocess
    with forced host devices; main pytest keeps the single real device)."""
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(
        __import__("pathlib").Path(__file__).resolve().parents[1] / "src")
    snippet = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.parallel import sharding as shd
        from repro.roofline import hlo_costs
        mesh = shd.make_mesh((2, 2), ("data", "model"))
        ns = lambda spec: jax.sharding.NamedSharding(mesh, spec)
        def f(x, w):
            y = x @ w                       # w row-sharded -> partial sums
            return jax.lax.with_sharding_constraint(y, ns(P("data", None)))
        with jax.set_mesh(mesh):
            # NamedSharding works on every jax version (bare PartitionSpecs
            # in in_shardings require newer jax).
            co = jax.jit(f, in_shardings=(ns(P("data", "model")),
                                          ns(P("model", None))),
                         out_shardings=ns(P("data", None))).lower(
                jax.ShapeDtypeStruct((64, 64), jnp.float32),
                jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
        res = hlo_costs.analyze(co.as_text())
        assert res.coll_bytes > 0, res.coll_breakdown
        # all-reduce of the (32, 32) partial output: >= 2x payload
        assert res.coll_bytes >= 32 * 32 * 4, res.coll_bytes
        print("coll ok", res.coll_breakdown)
    """)
    proc = subprocess.run([sys.executable, "-c", snippet], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_traffic_excludes_scan_slice_inflation():
    """A 100-step scan slicing a big stacked input must NOT charge 100 full
    reads of the stacked tensor."""
    def f(xs):
        def body(c, x):
            return c + jnp.sum(x), None
        out, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return out

    big = jax.ShapeDtypeStruct((100, 1000), jnp.float32)
    co = jax.jit(f).lower(big).compile()
    res = hlo_costs.analyze(co.as_text())
    full = 100 * 1000 * 4
    assert res.traffic_bytes < 8 * full  # not 100x


def test_model_flops_accounting():
    from repro.models import registry as R

    cfg = R.get("llama3-8b").config
    n = analysis.active_param_count(cfg)
    assert 7.5e9 < n < 9e9  # llama3-8b ~8.03B
    moe = R.get("phi3.5-moe-42b-a6.6b").config
    n_all = analysis._total_params(moe)
    n_act = analysis.active_param_count(moe)
    assert 38e9 < n_all < 46e9  # ~42B total
    assert 5.5e9 < n_act < 8e9  # ~6.6B active


def test_roofline_terms_and_bottleneck():
    r = analysis.Roofline(
        arch="a", shape="s", mesh="pod", chips=256,
        hlo_flops=1.97e14, hlo_bytes=8.19e11, hlo_bytes_fused=8.19e11,
        coll_bytes=5e10, coll_breakdown={}, model_flops=1.97e14 * 256,
        bytes_per_device=0)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(1.0)
    assert r.useful_flops_frac == pytest.approx(1.0)
    assert r.roofline_frac == pytest.approx(1.0)
