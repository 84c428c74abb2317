"""Sharding rules, ZeRO specs, pipeline & collective building blocks,
gradient compression, and training-convergence integration tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as meshlib
from repro.models import registry as R
from repro.optim import adamw, grad_compress
from repro.parallel import collectives, pipeline, sharding as shd


import os
import subprocess
import sys
import textwrap


def _run_multidevice(snippet: str, n_devices: int = 4) -> None:
    """Run a test body in a subprocess with forced host devices (the main
    pytest process keeps the single real CPU device per the assignment)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(
        __import__("pathlib").Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.fixture(scope="module")
def mesh4():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (run under forced host device count)")
    return jax.make_mesh((2, 2), ("data", "model"))


def _fake_mesh(shape_dict):
    class FakeMesh:
        shape = shape_dict
    return FakeMesh()


def test_rules_drop_nondivisible_axes():
    mesh = _fake_mesh({"data": 16, "model": 16})
    # smollm: 15 heads do not divide 16 -> replicated
    spec = shd.DEFAULT.spec(("embed", "heads", "head_dim"), (960, 15, 64), mesh)
    assert spec == P(None, None, None)
    spec2 = shd.DEFAULT.spec(("embed", "mlp"), (960, 2560), mesh)
    assert spec2 == P(None, "model")


def test_rules_no_duplicate_mesh_axis():
    mesh = _fake_mesh({"data": 16, "model": 16})
    spec = shd.DEFAULT.spec(
        ("layers", "batch", "seq_kv", "kv_heads", "head_dim"),
        (24, 32, 4096, 16, 64), mesh)
    used = [a for p in spec for a in ((p,) if isinstance(p, str) else (p or ()))]
    assert len(used) == len(set(used))


def test_rules_multi_axis_batch():
    mesh = _fake_mesh({"pod": 2, "data": 16, "model": 16})
    spec = shd.DEFAULT.spec(("batch", "seq"), (256, 4096), mesh)
    assert spec[0] == ("pod", "data")


def test_zero1_spec_extends_divisible_dim():
    mesh = _fake_mesh({"data": 16, "model": 16})
    out = adamw.zero1_spec(P(None, "model"), (4096, 14336), mesh,
                           extra_axes=("data",))
    # the impl picks the LARGEST divisible dim (14336): composite sharding
    used = [a for p in out for a in
            ((p,) if isinstance(p, str) else (p or ()))]
    assert "data" in used and "model" in used
    # already-used axis not duplicated
    out2 = adamw.zero1_spec(P("data", "model"), (4096, 14336), mesh,
                            extra_axes=("data",))
    assert out2 == P("data", "model")


def test_param_specs_shard_every_big_tensor():
    mesh = _fake_mesh({"data": 16, "model": 16})
    cfg = R.get("llama3-8b").config
    specs = R.param_specs(cfg, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    ap = jax.tree.leaves(R.abstract_params(cfg))
    for ((path, spec), a) in zip(flat, ap):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if "wk" in name or "wv" in name:
            continue  # GQA kv=8 does not divide the 16-way model axis
        if np.prod(a.shape) > 1e7:  # every big tensor must be sharded
            assert any(p is not None for p in spec), (path, a.shape, spec)


def test_pipeline_matches_reference():
    """GPipe shard_map pipeline == sequential reference (4 forced devices)."""
    _run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel import pipeline
        mesh = jax.make_mesh((4,), ("stage",))
        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])
        rng = np.random.default_rng(0)
        sp = {"w": jnp.asarray(rng.standard_normal((4, 8, 8)), jnp.float32) * 0.5,
              "b": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)}
        x = jnp.asarray(rng.standard_normal((6, 3, 8)), jnp.float32)
        got = pipeline.pipelined_apply(stage_fn, sp, x, mesh=mesh)
        want = pipeline.reference_apply(stage_fn, sp, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    """)


def test_ring_allgather_and_overlapped_matmul():
    """Overlapped ring all-gather matmul == plain matmul (4 forced devices)."""
    _run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel import collectives
        mesh = jax.make_mesh((4,), ("x",))
        rng = np.random.default_rng(1)
        xs = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
        def f(x_shard, w):
            return collectives.overlapped_matmul_allgather(x_shard, w, "x")
        got = jax.shard_map(f, mesh=mesh, in_specs=(P("x"), P()),
                            out_specs=P(), check_vma=False)(xs, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(xs @ w), atol=1e-5)

        def g(x_shard):
            return collectives.ring_allgather(x_shard, "x")
        gathered = jax.shard_map(g, mesh=mesh, in_specs=(P("x"),),
                                 out_specs=P("x"), check_vma=False)(xs)
        assert gathered.shape == (16, 2, 16)
    """)


def test_grad_compression_error_feedback_converges():
    """int8-compressed grads with error feedback reach the same optimum on a
    quadratic as uncompressed SGD (within tolerance)."""
    rng = np.random.default_rng(0)
    target = jnp.asarray(rng.standard_normal(32), jnp.float32)

    def loss(w):
        return jnp.sum((w - target) ** 2)

    w1 = jnp.zeros(32)
    w2 = jnp.zeros(32)
    ebuf = {"w": jnp.zeros(32)}
    for _ in range(200):
        g1 = jax.grad(loss)(w1)
        w1 = w1 - 0.05 * g1
        g2 = jax.grad(loss)(w2)
        deq, ebuf = grad_compress.compress_grads({"w": g2}, ebuf)
        w2 = w2 - 0.05 * deq["w"]
    assert float(loss(w1)) < 1e-6
    assert float(loss(w2)) < 1e-4  # compressed path converges too


def test_adamw_factored_close_to_full():
    rng = np.random.default_rng(0)
    target = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)

    def run(factored):
        cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0, factored=factored)
        w = {"w": jnp.zeros((8, 8))}
        st = adamw.init(w, cfg)
        for _ in range(150):
            g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(w)
            w, st = adamw.update(g, st, cfg, jnp.float32)
        return float(jnp.sum((w["w"] - target) ** 2))

    assert run(True) < 1e-2
    assert run(False) < 1e-2


def test_training_loss_decreases_integration(tmp_path):
    """End-to-end smoke train on synthetic data: loss must drop.

    Runs the full 60-step schedule horizon (warmup + cosine decay declared
    by total_steps): stopping at 40 leaves the decay phase unfinished and
    the drop just under threshold on CPU."""
    from repro.launch.train import TrainRun

    cfg = dataclasses.replace(R.get("smollm-360m").smoke, microbatches=2,
                              remat=False)
    run = TrainRun(cfg=cfg, opt_cfg=adamw.AdamWConfig(lr=3e-3),
                   mesh=meshlib.make_host_mesh(), global_batch=8, seq=32,
                   total_steps=60)
    _, _, hist = run.run(60, log_every=0)
    assert np.mean(hist[-5:]) < np.mean(hist[:5]) - 0.2, hist[:3] + hist[-3:]
