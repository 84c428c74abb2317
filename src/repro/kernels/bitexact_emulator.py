"""Batched bit-exact FP32-multiplier emulation Pallas kernel.

The foundry's characterization sweeps emulate the same operand stream under
many compressor-code variants (foundry.characterize_batch). The operand half
of the emulation — IEEE fields and the radix-8 Booth partial-product rows —
does not read the scheme; only the compressor stages and what follows do.
So each grid program takes one (row_block, 128) tile of operands, builds its
Booth rows once (core/fp32_mul.py::booth_packed) and runs the scheme half
(multiply_packed) for each variant of its block, reading that variant's 24
column-mask words from SMEM.

Bit-identical per variant to scalar `fp32_mul.fp32_multiply_batch` sweeps
(asserted against the golden fixtures in tests/test_emulator_batch.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fp32_mul
from repro.kernels import platform

LANES = 128
# (variant block, operand chunk) defaults: matches the host batched sweep's
# 2^15-element per-group budget (foundry.characterize_batch).
DEFAULT_VARIANT_BLOCK = 8
DEFAULT_CHUNK = 1 << 12


def _kernel(m_ref, a_ref, b_ref, out_ref):
    gv = out_ref.shape[0]
    words = fp32_mul.N_MASK_WORDS
    fields, rows = fp32_mul.booth_packed(a_ref[...], b_ref[...])
    v0 = pl.program_id(0) * gv

    def per_variant(j, carry):
        base = (v0 + j) * words
        masks = [m_ref[base + i] for i in range(words)]
        out_ref[j] = fp32_mul.multiply_packed(fields, rows, masks)
        return carry

    jax.lax.fori_loop(0, gv, per_variant, 0)


@functools.partial(jax.jit,
                   static_argnames=("variant_block", "row_block", "interpret"))
def _stacked_jit(a, b, masks, *, variant_block, row_block, interpret):
    v, rows = masks.shape[0], a.shape[0]
    return pl.pallas_call(
        _kernel,
        grid=(v // variant_block, rows // row_block),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((row_block, LANES), lambda vi, ri: (ri, 0)),
            pl.BlockSpec((row_block, LANES), lambda vi, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((variant_block, row_block, LANES),
                               lambda vi, ri: (vi, ri, 0)),
        out_shape=jax.ShapeDtypeStruct((v, rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(masks.reshape(-1), a, b)


def fp32_multiply_stacked_kernel(a, b, scheme_maps, *,
                                 variant_block: int = DEFAULT_VARIANT_BLOCK,
                                 chunk: int = DEFAULT_CHUNK) -> np.ndarray:
    """(V, n) emulated products of one operand stream under V scheme maps.

    a, b: float32 (n,); scheme_maps: int32 (V, 3, 48). ``chunk`` operands
    per program, rounded up to whole (8, 128) tiles. Operands pad with zeros
    and the variant axis pads by repeating map 0 (a valid compressor
    config); both pads are cropped from the result. Returns np.float32 (V, n).
    """
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    masks = fp32_mul.code_masks(scheme_maps)
    v, n = masks.shape[0], a.size
    if n == 0 or v == 0:
        return np.zeros((v, n), np.float32)
    gv = min(variant_block, v)
    tile = 8 * LANES
    rb = 8 * max(1, -(-min(chunk, n) // tile))
    pad_n = (-n) % (rb * LANES)
    pad_v = (-v) % gv
    if pad_n:
        a = np.concatenate([a, np.zeros(pad_n, np.float32)])
        b = np.concatenate([b, np.zeros(pad_n, np.float32)])
    if pad_v:
        masks = np.concatenate([masks, np.repeat(masks[:1], pad_v, axis=0)])
    out = _stacked_jit(jnp.asarray(a.reshape(-1, LANES)),
                       jnp.asarray(b.reshape(-1, LANES)), jnp.asarray(masks),
                       variant_block=gv, row_block=rb,
                       interpret=platform.interpret_mode())
    return np.asarray(out).reshape(out.shape[0], -1)[:v, :n]
