"""Collective helpers + overlap utilities on top of jax.lax primitives.

GSPMD inserts most collectives automatically from sharding constraints; the
helpers here cover the places where we want *explicit* control:

  * `psum_scatter_grads`: reduce-scatter gradients over the data axis for the
    ZeRO-1 update (each shard updates only its optimizer slice) instead of a
    full all-reduce — halves DP gradient traffic.
  * `ring_allgather`: all-gather built from collective_permute; on TPU this
    lowers to neighbor ICI hops that XLA can overlap with compute (the
    building block of the overlapped TP matmul below).
  * `overlapped_matmul_allgather`: computes x @ W_shard while the next x
    shard is in flight — the classic comm/compute overlap pattern, usable
    inside shard_map when XLA's automatic latency hiding isn't enough.

These are exercised by tests/test_collectives.py on a host mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def psum_scatter_grads(grads, axis: str, *, tiled: bool = True):
    """Reduce-scatter every leaf over `axis` along its largest divisible dim."""
    n = jax.lax.axis_size(axis)

    def leaf(g):
        for d, size in enumerate(g.shape):
            if size % n == 0:
                return jax.lax.psum_scatter(g, axis, scatter_dimension=d,
                                            tiled=tiled)
        return jax.lax.psum(g, axis)  # no divisible dim: fall back

    return jax.tree.map(leaf, grads)


def ring_allgather(x, axis: str):
    """All-gather along `axis` via ring collective_permute (N-1 hops).

    Returns concat of shards along a new leading axis, rolled so index 0 is
    this device's own shard (matches lax.all_gather(..., tiled=False) up to
    known rotation; tests compare against the roll).
    """
    n = jax.lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        buf = jax.lax.ppermute(carry, axis, perm)
        return buf, buf

    _, received = jax.lax.scan(step, x, None, length=n - 1)
    return jnp.concatenate([x[None], received], axis=0)


def overlapped_matmul_allgather(x_shard, w, axis: str):
    """y = allgather(x) @ w with the gather pipelined against the matmul.

    x_shard: (m/n, k) this device's row shard; w: (k, p) replicated (or the
    TP shard of a larger W). Each of the n ring steps multiplies the shard
    that just arrived while the next hop is in flight — XLA overlaps the
    ppermute with the dot because there is no data dependence.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    m = x_shard.shape[0]

    def step(carry, t):
        buf, acc = carry
        y = buf @ w  # compute on the shard we hold
        src = (idx - t) % n  # whose shard we just multiplied
        acc = jax.lax.dynamic_update_slice(acc, y, (src * m, jnp.int32(0)))
        buf = jax.lax.ppermute(buf, axis, perm)  # overlaps with next dot
        return (buf, acc), None

    acc0 = jnp.zeros((m * n, w.shape[1]), x_shard.dtype)
    (_, acc), _ = jax.lax.scan(step, (x_shard, acc0), jnp.arange(n))
    return acc
