"""Bit-exact interleaved conv2d Pallas kernel (the paper's CNN compute).

NHWC, VALID, stride 1. Each (filter, ky, kx) tap carries its own multiplier
variant (slot map shared across input channels, exactly the paper's 198-slot
scheme for the 22x3x3 CNN).

Layout: the wrapper lays the input out as im2col planes, one per (tap,
input channel), with every output position of the batch flattened and
padded onto (rows, 128) lanes. A grid program takes ``row_block`` rows of
every plane and, for each filter, tap and channel, runs the packed
emulation (core/fp32_mul.py::fp32_multiply_packed) of the plane against the
scalar tap weight — elementwise int32 work on one (row_block, 128) tile.
Tap weights and the per-(filter, tap) column masks sit in SMEM. The paper
CNN's planes are 900 (layer 1) and 169 (layer 2) positions per image, so
the lanes are dense whatever the layer.

Accumulation order (shared with kernels/ref.py::am_conv2d_bitexact_ref):
per output, taps in (ky, kx) order, each tap adding the sequential left fold
of its channel products from +0.0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fp32_mul
from repro.kernels import platform

LANES = 128
ROW_BLOCK = 8  # one (8, 128) int32 vreg per plane tile


def _kernel(w_ref, m_ref, x_ref, o_ref):
    taps, cin = x_ref.shape[:2]
    tile = x_ref.shape[2:]
    zeros = jnp.zeros(tile, jnp.float32)
    words = fp32_mul.N_MASK_WORDS

    def per_filter(fi, carry):
        def per_tap(t, acc):
            slot = fi * taps + t
            masks = [m_ref[slot * words + i] for i in range(words)]

            def per_channel(ci, s):
                b = jnp.full(tile, w_ref[slot * cin + ci], jnp.float32)
                return s + fp32_mul.fp32_multiply_packed(x_ref[t, ci], b, masks)

            return acc + jax.lax.fori_loop(0, cin, per_channel, zeros)

        o_ref[fi] = jax.lax.fori_loop(0, taps, per_tap, zeros)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], per_filter, 0)


def am_conv2d_bitexact_kernel(x, w, slot_map, *, row_block=ROW_BLOCK):
    """x (B,H,W,Cin) f32, w (F,kh,kw,Cin) f32, slot_map (F,kh,kw) int32.

    The code masks are fetched from the live registry OUTSIDE the jit
    boundary, so growing the variant registry (repro.foundry) is seen on
    the next call instead of serving a stale baked-in table.
    """
    table = jnp.asarray(fp32_mul.mask_stack())
    return _am_conv2d_bitexact_jit(x, w, jnp.asarray(slot_map, jnp.int32),
                                   table, row_block=row_block,
                                   interpret=platform.interpret_mode())


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def _am_conv2d_bitexact_jit(x, w, slot_map, table, *, row_block, interpret):
    b, h, wd, cin = x.shape
    f, kh, kw, _ = w.shape
    ho, wo = h - kh + 1, wd - kw + 1
    taps, npos = kh * kw, b * ho * wo
    planes = jnp.stack([x[:, ky:ky + ho, kx:kx + wo, :]
                        for ky in range(kh) for kx in range(kw)])
    planes = planes.transpose(0, 4, 1, 2, 3).reshape(taps, cin, npos)
    tile = row_block * LANES
    planes = jnp.pad(planes, ((0, 0), (0, 0), (0, (-npos) % tile)))
    planes = planes.reshape(taps, cin, -1, LANES)
    rows = planes.shape[2]
    masks = table[slot_map.reshape(f * taps)].reshape(-1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _kernel,
        grid=(rows // row_block,),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((taps, cin, row_block, LANES), lambda i: (0, 0, i, 0)),
        ],
        out_specs=pl.BlockSpec((f, row_block, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((f, rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w.reshape(-1), masks, planes)
    out = out.reshape(f, -1)[:, :npos].reshape(f, b, ho, wo)
    return out.transpose(1, 2, 3, 0)
