"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e,
at real widths, with no chip attached.

The v5e topology is described inside a module-scoped fixture (never at
import), so every pytest-xdist worker collects the same tests and only the
worker given this file loads the TPU compiler. The kernel wrappers ask
kernels/platform.py where a call runs; each test points that at the
described chip, so the wrappers pick compiled (not interpret-mode) kernels
and the chip's blocks. Each compile must lower every kernel to Mosaic: a
`tpu_custom_call` in the compiled HLO.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitexact_emulator, ops, platform

K = N = 768  # xlstm-125m d_model
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def chip(topo, monkeypatch):
    """Shape factory on one described v5e chip; kernel calls target it."""
    dev = topo.devices[0]
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    monkeypatch.setattr(platform, "device_kind", lambda: dev.device_kind)
    one = SingleDeviceSharding(dev)
    return lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype,
                                                         sharding=one)


def _assert_lowers(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [8, 512])
def test_surrogate_moments_kernel(chip, m):
    _assert_lowers(lambda x, w, mu, sg: ops.am_surrogate_moments(
        x, w, mu, sg, impl="kernel"),
        chip((m, K)), chip((K, N)), chip((K, N)), chip((K, N)))


@pytest.mark.parametrize("m", [8, 512])
def test_surrogate_folded_kernel(chip, m):
    _assert_lowers(lambda x, wm, wv: ops.am_surrogate_moments_folded(
        x, wm, wv, impl="kernel"), chip((m, K)), chip((K, N)), chip((K, N)))


@pytest.mark.parametrize("pop,pop_x", [(0, False), (4, False), (4, True)])
def test_surrogate_epilogue_kernel(chip, pop, pop_x):
    m = 8
    x = chip((pop, m, K) if pop_x else (m, K))
    w = chip((pop, K, N) if pop else (K, N))
    _assert_lowers(lambda x, wm, wv, z: ops.am_surrogate_matmul_epilogue(
        x, wm, wv, z, impl="kernel"), x, w, w, chip((m, N)))


@pytest.mark.parametrize("m", [8, 512])
def test_bitexact_matmul_kernel(chip, m):
    _assert_lowers(lambda x, w, v: ops.am_matmul_bitexact(x, w, v),
                   chip((m, K)), chip((K, N)), chip((K, N), I32))


@pytest.mark.parametrize("b,hw,cin,f", [(64, 32, 3, 10), (64, 15, 10, 12)],
                         ids=["conv1", "conv2"])
def test_bitexact_conv_kernel(chip, b, hw, cin, f):
    """The paper CNN's two conv layers (32x32x3 -> 10, pooled 15x15x10 -> 12)."""
    _assert_lowers(lambda x, w, s: ops.am_conv2d_bitexact(x, w, s),
                   chip((b, hw, hw, cin)), chip((f, 3, 3, cin)),
                   chip((f, 3, 3), I32))


def test_bitexact_emulator_kernel(chip):
    """The foundry's stacked sweep: 16 variants x 2^16 operands."""
    rows = (1 << 16) // bitexact_emulator.LANES
    _assert_lowers(
        lambda a, b, m: bitexact_emulator._stacked_jit(
            a, b, m, variant_block=8, row_block=32,
            interpret=platform.interpret_mode()),
        chip((rows, bitexact_emulator.LANES)),
        chip((rows, bitexact_emulator.LANES)), chip((16, 24), I32))
