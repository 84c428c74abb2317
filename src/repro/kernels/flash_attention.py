"""Blocked flash-attention Pallas kernel (TPU target, interpret-validated).

The §Roofline analysis shows the dense train/prefill cells are memory-bound
on score traffic: the pure-JAX streaming softmax (models/layers.py) still
round-trips (B, Sq, H, block_kv) score tiles through HBM — O(S²) bytes. This
kernel keeps the (bq, bk) score tile, the online-softmax statistics and the
output accumulator in VMEM; HBM traffic drops to O(S·d) reads of q/k/v plus
one write of o — the roofline fix for llama3/starcoder2/internvl2 prefill.

Grid: (B·H, Sq/bq, Skv/bk), kv innermost. The running (m, l, acc) state
lives in *output* refs whose index_map ignores the kv axis, so it persists
across kv steps (portable across interpret/TPU without scratch shapes).
GQA is handled by the k/v index_map (kv_head = head // rep — no repeated
K/V materialization). Causal / sliding / chunked masks from program ids.

VMEM per program (f32, bq=bk=512, dh=128): q 256K + k/v 512K + scores 1M +
acc 256K ≈ 2.1 MB — comfortably inside the v5e ~16 MB envelope; matmul dims
are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import platform

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            *, scale, causal, window, chunked, bq, bk, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, dh)
    k = k_ref[0].astype(jnp.float32)  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(  # (bq, bk) score tile, stays in VMEM
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= kv_pos
    if window and chunked:
        mask &= (q_pos // window) == (kv_pos // window)
    elif window:
        mask &= q_pos - kv_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[0]  # (bq,)
    l_prev = l_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = (l_prev * corr + jnp.sum(p, axis=1))[None]
    m_ref[...] = m_new[None]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = o_ref[...] * corr[None, :, None] + pv[None]

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]


def flash_attention_kernel(q, k, v, *, causal=True, window=0, chunked=False,
                           block_q=DEFAULT_BLOCK_Q, block_kv=DEFAULT_BLOCK_KV):
    """q: (B, Sq, H, Dh); k, v: (B, Skv, KV, Dh), H % KV == 0.

    Returns (B, Sq, H, Dh) in q.dtype. Sq % block_q == Skv % block_kv == 0.
    """
    return _flash_attention_jit(q, k, v, causal=causal, window=window,
                                chunked=chunked, block_q=block_q,
                                block_kv=block_kv,
                                interpret=platform.interpret_mode())


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunked", "block_q", "block_kv",
                     "interpret"))
def _flash_attention_jit(q, k, v, *, causal, window, chunked, block_q,
                         block_kv, interpret):
    b, sq, h, dh = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    bq = min(block_q, sq)
    bk = min(block_kv, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    nq, nk = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(dh)

    # (B, S, H, Dh) -> (B*H, S, Dh) program-major layout
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dh)

    def kv_index(bh, qi, ki):
        return (bh // h) * kvh + (bh % h) // rep, ki, 0

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, chunked=chunked,
        bq=bq, bk=bk, nk=nk)
    out, _, _ = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq), lambda bh, qi, ki: (bh, qi)),
            pl.BlockSpec((1, bq), lambda bh, qi, ki: (bh, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, dh), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, dh).transpose(0, 2, 1, 3).astype(q.dtype)
