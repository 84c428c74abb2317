"""Bit-exact approximate-multiplier matmul Pallas kernel.

Each scalar product x[m,k] * w[k,n] is computed through the emulated
approximate FP32 multiplier of the slot's variant (per-(k,n) variant map —
the paper's interleaving at matmul granularity); accumulation is exact f32.

This is the fidelity kernel: it runs the paper's numerics on-device at CNN
scale, not to win FLOPs — the emulation is integer work on the VPU (a few
hundred int32 ops per multiply). The kernel body uses the packed emulation
(core/fp32_mul.py::fp32_multiply_packed): elementwise int32 ops on
(bm, bn) tiles, with each slot's compressor choice given as 24 column-mask
words that the wrapper gathers per (k, n) outside the kernel. Blocks are
tile-aligned — bm a multiple of 8, bn of 128, bk of 128 or all of K — and
the kernel walks its k slice one row at a time, so the live set is a few
dozen (bm, bn) int32 tiles whatever bk is.

Accumulation order (shared with kernels/ref.py::am_matmul_bitexact_ref):
four strided lane accumulators, lane j summing the products of k = j, j+4,
j+8, ... in order from +0.0, combined as (l0 + l2) + (l1 + l3). The lanes
persist across k blocks in VMEM scratch, so the result does not depend on
the block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fp32_mul
from repro.kernels import platform
from repro.kernels.ref import N_LANES, combine_lanes


def _kernel(x_ref, w_ref, m_ref, o_ref, acc_ref):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xbits = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)  # (bm, bk)
    lane = jax.lax.broadcasted_iota(jnp.int32, xbits.shape, 1)
    floor = jnp.iinfo(jnp.int32).min

    def body(kk, carry):
        # Column kk of the x tile as (bm, 1): an exact max-select over lanes
        # (a dynamic lane slice does not lower).
        col = jnp.max(jnp.where(lane == kk, xbits, floor), axis=1,
                      keepdims=True)
        a = jax.lax.bitcast_convert_type(col, jnp.float32)
        b = w_ref[pl.ds(kk, 1), :]  # (1, bn)
        masks = [m_ref[i, pl.ds(kk, 1), :] for i in range(fp32_mul.N_MASK_WORDS)]
        j = kk % N_LANES
        acc_ref[j] = acc_ref[j] + fp32_mul.fp32_multiply_packed(a, b, masks)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1], body, 0)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = combine_lanes([acc_ref[j] for j in range(N_LANES)])


def am_matmul_bitexact_kernel(x, w, masks, *, block):
    """x (M, K) f32 @ w (K, N) f32 under per-slot column masks (24, K, N).

    ``masks`` is fp32_mul.mask_stack()[variant_ids] with the word axis first
    (kernels/ops.py::am_matmul_bitexact builds it). Dims must be multiples
    of ``block = (bm, bk, bn)``; bk must be a multiple of 8.
    """
    return _am_matmul_bitexact_jit(x, w, masks, block=tuple(block),
                                   interpret=platform.interpret_mode())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _am_matmul_bitexact_jit(x, w, masks, *, block, interpret):
    m, k = x.shape
    n = w.shape[1]
    bm, bk, bn = block
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (x.shape, w.shape, block)
    assert bk % 8 == 0, block
    return pl.pallas_call(
        _kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((fp32_mul.N_MASK_WORDS, bk, bn),
                         lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N_LANES, bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, masks)
