"""Fused statistical-AM matmul Pallas kernels (the LM-scale hot spot).

The surrogate numerics (core/surrogate.py) needs two matmuls over the same
operands: ``mean = x @ (w(1+mu))`` and ``var = x^2 @ (w^2 sg^2)``. Composed
naively that is 2 HBM reads of x and w plus two materialized weight
transforms, and the noise application ``mean + z*sqrt(max(var, 0))`` is a
third full pass over the outputs. Three kernels fuse the pipeline in one
walk over (M/bm, N/bn, K/bk) tiles:

  * am_surrogate_matmul_kernel — unfolded (w, mu, sg) operands, returns the
    (mean, var) pair; the weight transforms are computed in-register.
  * am_surrogate_matmul_folded_kernel — pre-folded (w_mean, w_var) weights
    (the engine folds the moment maps once per step on the host), returns
    (mean, var).
  * am_surrogate_matmul_epilogue_kernel — folded weights plus the caller's
    CRN noise tile z; the noise application runs as an epilogue on the last
    k step while the output tile is still resident, so the surrogate's full
    forward is ONE kernel launch. Supports a leading population axis on the
    weights (P genomes, z shared across P — the engine's CRN invariant) and
    optionally on x.

HBM traffic: 1x x + 1x w(+var) + z (vs 2x x + 2x w + transformed weights +
an extra read-modify-write of the outputs); FLOPs unchanged (2 MXU matmuls —
the cost of the technique itself).

VMEM budget per program (f32): x bm*bk + folded weights 2*bk*bn + z/out/var
3*bm*bn (the unfolded kernel's w/mu/sg + two accumulators is the same size).
Default (bm, bk, bn) = (128, 128, 128): 6 * 64 KiB = 384 KiB, well under the
~16 MiB/core VMEM of TPU v5e; MXU dims are 128-aligned. Callers should take
blocks from the autotuned chooser (kernels/ops.py choose_block,
kind="surrogate_matmul").

The kernels are deterministic (z is an operand, never drawn inside) and
oracle-comparable; see ops.am_surrogate_moments / am_surrogate_matmul_epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import platform

# (bm, bk, bn) fallback; callers should take blocks from the shared chooser
# (kernels/ops.py choose_block, kind="surrogate_matmul").
DEFAULT_BLOCK = (128, 128, 128)


def _kernel(x_ref, w_ref, mu_ref, sg_ref, mean_ref, var_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        mean_ref[...] = jnp.zeros_like(mean_ref)
        var_ref[...] = jnp.zeros_like(var_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    sg = sg_ref[...]

    w_mean = w * (1.0 + mu)
    w_var = (w * w) * (sg * sg)
    mean_ref[...] += jax.lax.dot(x, w_mean, preferred_element_type=jnp.float32)
    var_ref[...] += jax.lax.dot(x * x, w_var, preferred_element_type=jnp.float32)


def am_surrogate_matmul_kernel(x, w, mu, sg, *, block=DEFAULT_BLOCK):
    """Fused (mean, var) AM matmul.

    x: (M, K); w, mu, sg: (K, N). M, K, N must divide by the block shape.
    Returns (mean, var), both (M, N) f32.
    """
    return _am_surrogate_matmul_jit(x, w, mu, sg, block=tuple(block),
                                    interpret=platform.interpret_mode())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _am_surrogate_matmul_jit(x, w, mu, sg, *, block, interpret):
    m, k = x.shape
    n = w.shape[1]
    bm, bk, bn = block
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (x.shape, w.shape, block)

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, mu, sg)


def _folded_kernel(x_ref, wm_ref, wv_ref, mean_ref, var_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        mean_ref[...] = jnp.zeros_like(mean_ref)
        var_ref[...] = jnp.zeros_like(var_ref)

    x = x_ref[...].astype(jnp.float32)
    mean_ref[...] += jax.lax.dot(x, wm_ref[...],
                                 preferred_element_type=jnp.float32)
    var_ref[...] += jax.lax.dot(x * x, wv_ref[...],
                                preferred_element_type=jnp.float32)


def am_surrogate_matmul_folded_kernel(x, w_mean, w_var, *, block=DEFAULT_BLOCK):
    """(mean, var) AM matmul over pre-folded weights.

    x: (M, K); w_mean, w_var: (K, N), already carrying the moment transforms
    (engine.fold_matmul_weights). Dims must divide by the block shape.
    """
    return _am_surrogate_matmul_folded_jit(
        x, w_mean, w_var, block=tuple(block),
        interpret=platform.interpret_mode())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _am_surrogate_matmul_folded_jit(x, w_mean, w_var, *, block, interpret):
    m, k = x.shape
    n = w_mean.shape[1]
    bm, bk, bn = block
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (x.shape, block)

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _folded_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, w_mean, w_var)


def _epilogue_kernel(x_ref, wm_ref, wv_ref, z_ref, out_ref, var_ref):
    """Grid (M/bm, N/bn, K/bk): accumulate both contractions; on the last k
    step apply the noise epilogue while the output tile is resident."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        var_ref[...] = jnp.zeros_like(var_ref)

    x = x_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot(x, wm_ref[...],
                                preferred_element_type=jnp.float32)
    var_ref[...] += jax.lax.dot(x * x, wv_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        out_ref[...] += z_ref[...] * jnp.sqrt(
            jnp.maximum(var_ref[...], 0.0))


def _epilogue_kernel_pop(x_ref, wm_ref, wv_ref, z_ref, out_ref, var_ref,
                         *, pop_x: bool):
    """Population variant: grid (P, M/bm, N/bn, K/bk); weight/output blocks
    carry a leading size-1 population dim, z is shared across P (CRN)."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        var_ref[...] = jnp.zeros_like(var_ref)

    x = (x_ref[0] if pop_x else x_ref[...]).astype(jnp.float32)
    out_ref[0] += jax.lax.dot(x, wm_ref[0],
                              preferred_element_type=jnp.float32)
    var_ref[0] += jax.lax.dot(x * x, wv_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _epilogue():
        out_ref[0] += z_ref[...] * jnp.sqrt(jnp.maximum(var_ref[0], 0.0))


def am_surrogate_matmul_epilogue_kernel(x, w_mean, w_var, z, *,
                                        block=DEFAULT_BLOCK):
    """One-launch surrogate matmul: out = x@wm + z*sqrt(max(x^2@wv, 0)).

    x: (M, K) or (P, M, K); w_mean, w_var: (K, N) or (P, K, N); z: (M, N),
    shared across the population axis (the engine's CRN invariant). Dims
    must divide by the block shape. Returns (P?, M, N) f32.
    """
    return _am_surrogate_matmul_epilogue_jit(
        x, w_mean, w_var, z, block=tuple(block),
        interpret=platform.interpret_mode())


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _am_surrogate_matmul_epilogue_jit(x, w_mean, w_var, z, *, block,
                                      interpret):
    pop = w_mean.ndim == 3
    pop_x = x.ndim == 3
    m, k = x.shape[-2:]
    n = w_mean.shape[-1]
    bm, bk, bn = block
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (x.shape, block)

    if not pop:
        grid = (m // bm, n // bn, k // bk)
        out, _ = pl.pallas_call(
            _epilogue_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
                pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, n), jnp.float32),
                jax.ShapeDtypeStruct((m, n), jnp.float32),
            ],
            interpret=interpret,
        )(x, w_mean, w_var, z)
        return out

    p = w_mean.shape[0]
    grid = (p, m // bm, n // bn, k // bk)
    if pop_x:
        x_spec = pl.BlockSpec((1, bm, bk), lambda pp, i, j, kk: (pp, i, kk))
    else:
        x_spec = pl.BlockSpec((bm, bk), lambda pp, i, j, kk: (i, kk))
    out, _ = pl.pallas_call(
        functools.partial(_epilogue_kernel_pop, pop_x=pop_x),
        grid=grid,
        in_specs=[
            x_spec,
            pl.BlockSpec((1, bk, bn), lambda pp, i, j, kk: (pp, kk, j)),
            pl.BlockSpec((1, bk, bn), lambda pp, i, j, kk: (pp, kk, j)),
            pl.BlockSpec((bm, bn), lambda pp, i, j, kk: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda pp, i, j, kk: (pp, i, j)),
            pl.BlockSpec((1, bm, bn), lambda pp, i, j, kk: (pp, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, m, n), jnp.float32),
            jax.ShapeDtypeStruct((p, m, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, w_mean, w_var, z)
    return out
