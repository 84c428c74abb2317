"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The primary dry-run distribution is DP x TP (+EP/SP) — the right fit for a
16x16 v5e pod. This module supplies the PP building block for deeper-than-TP
scaling (e.g. 1000+ nodes where a (pp, data, model) mesh amortizes weight
memory): layers are split into S stages laid out on a mesh axis; microbatches
stream through with jax.lax.ppermute handoffs; bubbles = (S-1)/(M+S-1).

`pipelined_apply` is deliberately model-agnostic: it pipelines any
`stage_fn(stage_params, x) -> x` where stage params are stacked on a leading
stage axis and sharded over the "stage" mesh axis. Tested on a host mesh in
tests/test_pipeline.py against the unpipelined reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipelined_apply(stage_fn, stage_params, x, *, mesh: Mesh, axis: str = "stage"):
    """Run S pipeline stages over M microbatches.

    Args:
      stage_fn: (params_for_stage, activations (mb, ...)) -> activations.
      stage_params: pytree with leading stage axis S, sharded over `axis`.
      x: (M, mb, ...) microbatched input, replicated over `axis`.
    Returns:
      (M, mb, ...) outputs (as if stages were applied sequentially).
    """
    s = mesh.shape[axis]
    m = x.shape[0]
    total = m + s - 1  # pipeline ticks incl. drain

    def per_stage(params, xs):
        # params: (1, ...) this stage's slice; xs: (M, mb, ...) full stream.
        params = jax.tree.map(lambda t: t[0], params)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry  # buf: (mb, ...) activation entering this stage
            # Stage 0 injects microbatch t (if still filling).
            inject = jnp.where(t < m, t, m - 1)
            x_in = jnp.where(stage_id == 0, xs[inject], buf)
            y = stage_fn(params, x_in)
            # Last stage writes result for microbatch (t - (s-1)).
            # (select, not lax.cond: branch outputs would differ in shard_map
            # varying-axis type.)
            out_idx = t - (s - 1)
            write = (stage_id == s - 1) & (out_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.maximum(out_idx, 0), 0)
            outs = jnp.where(write, updated, outs)
            # Hand activations to the next stage (ring; last->first unused).
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return (nxt, outs), None

        # carries become device-varying after the first ppermute: mark the
        # initial values as varying so the scan carry type is stable.
        buf0 = jax.lax.pcast(jnp.zeros(mb_shape, xs.dtype), (axis,),
                         to="varying")
        outs0 = jax.lax.pcast(jnp.zeros((m,) + mb_shape, xs.dtype), (axis,),
                          to="varying")
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(total))
        # outs is valid only on the last stage; psum of masked copies
        # broadcasts it (other stages contribute zeros).
        outs = jax.lax.psum(
            outs * (stage_id == s - 1).astype(outs.dtype), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
    )(stage_params, x)


def reference_apply(stage_fn, stage_params, x):
    """Sequential oracle for pipelined_apply (same results, no pipeline)."""
    s = jax.tree.leaves(stage_params)[0].shape[0]
    m = x.shape[0]

    def run_mb(xmb):
        h = xmb
        for i in range(s):
            params_i = jax.tree.map(lambda t: t[i], stage_params)
            h = stage_fn(params_i, h)
        return h

    return jax.vmap(run_mb)(x)
