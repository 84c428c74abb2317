"""Logical-axis sharding rules (GSPMD / pjit).

Every parameter and activation in the model zoo is annotated with *logical*
axis names; a rule table maps logical names to mesh axes. Rules silently drop
a mesh axis when the dimension size does not divide it (e.g. smollm's 15
query heads on a 16-way model axis), so one rule set serves all ten
architectures.

Mesh conventions (launch/mesh.py):
  single-pod: (data=16, model=16)          multi-pod: (pod=2, data=16, model=16)

Default rules (Megatron TP + DP batch + EP over data + SP for long ctx):
  batch        -> ("pod", "data")     tokens/requests
  seq_kv       -> "model"             decode KV-cache length (context parallel)
  heads/mlp/vocab -> "model"          column/row-sharded projections
  experts      -> "data"              expert parallelism
  embed        -> None                replicated feature dim
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-propagated sharding)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_pop_mesh(n_devices: int | None = None, axis_name: str = "pop") -> Mesh:
    """1-D mesh over the first ``n_devices`` devices for NSGA-II population
    sharding (core/engine.py ``AMEngine(mesh=...)``,
    experiments/paper_cnn.py::make_batched_evaluator ``mesh=``).

    Built with the raw Mesh constructor (not make_mesh) so a mesh over a
    device subset works — e.g. a 2-way population mesh on a 4-device host.
    On CPU hosts, force placeholder devices per process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* any jax
    import (the repo's tests/benchmarks do this via subprocesses).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, host has {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def island_meshes(
    mesh: Mesh | None, n_islands: int, axis_name: str = "pop"
) -> list[Mesh | None]:
    """Split a population mesh into per-island sub-meshes (codesign async).

    Round-robin over the mesh's devices so island i owns ``devs[i::n]`` —
    every island gets a contiguous share of the host's compute and the
    device counts differ by at most one. When the mesh has fewer devices
    than islands, islands share devices (``devs[i % len]``, a 1-device
    mesh each); when ``mesh`` is None (unsharded evaluators), every island
    gets None and the evaluators run unsharded side by side.
    """
    if n_islands < 1:
        raise ValueError(f"n_islands must be >= 1, got {n_islands}")
    if mesh is None:
        return [None] * n_islands
    devs = list(mesh.devices.ravel())
    out = []
    for i in range(n_islands):
        share = devs[i::n_islands] or [devs[i % len(devs)]]
        out.append(Mesh(np.asarray(share), (axis_name,)))
    return out

# logical axis -> mesh axis (or tuple of mesh axes, tried jointly)
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,  # activations keep seq unsharded in train (DP over batch)
    "seq_kv": "model",  # decode caches: context parallel over model axis
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "data",
    "expert_mlp": "model",
    "moe_tokens": ("pod", "data"),  # dispatched-token grid, token-major side
    "moe_pod": "pod",  # group dim while experts own the data axis
    "layers": None,
    "conv": None,
    "state": None,
    "unsharded": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: tuple[tuple[str, Any], ...] = tuple(DEFAULT_RULES.items())

    def table(self) -> dict[str, Any]:
        return dict(self.rules)

    def spec(self, logical_axes: tuple[str | None, ...], shape: tuple[int, ...],
             mesh: Mesh) -> P:
        """Resolve logical axes to a PartitionSpec, dropping non-divisible axes."""
        assert len(logical_axes) == len(shape), (logical_axes, shape)
        table = self.table()
        out = []
        used: set[str] = set()  # a mesh axis may appear once per spec
        for name, dim in zip(logical_axes, shape):
            if name is None:
                out.append(None)
                continue
            mesh_axes = table.get(name)
            if mesh_axes is None:
                out.append(None)
                continue
            if isinstance(mesh_axes, str):
                mesh_axes = (mesh_axes,)
            picked = []
            rem = dim
            for ax in mesh_axes:
                if ax in mesh.shape and ax not in used and rem % mesh.shape[ax] == 0:
                    picked.append(ax)
                    used.add(ax)
                    rem //= mesh.shape[ax]
            if not picked:
                out.append(None)
            elif len(picked) == 1:
                out.append(picked[0])
            else:
                out.append(tuple(picked))
        return P(*out)

    def sharding(self, logical_axes, shape, mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes, shape, mesh))


DEFAULT = ShardingRules()


def with_rules(**overrides) -> ShardingRules:
    table = dict(DEFAULT_RULES)
    table.update(overrides)
    return ShardingRules(tuple(table.items()))


def logical_constraint(x, logical_axes, mesh: Mesh | None = None,
                       rules: ShardingRules = DEFAULT):
    """with_sharding_constraint via logical names (no-op outside a mesh)."""
    if mesh is None:
        mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = rules.spec(tuple(logical_axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def get_abstract_mesh() -> Mesh | None:
    """The mesh from the innermost `jax.set_mesh(...)` / `with mesh:` context."""
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def tree_specs(schema_tree, shape_tree, mesh, rules: ShardingRules = DEFAULT):
    """Map a pytree of logical-axis tuples + shapes to PartitionSpecs."""
    return jax.tree.map(
        lambda axes, shape: rules.spec(axes, shape, mesh),
        schema_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x
        ),
    )


def tree_shardings(schema_tree, shape_tree, mesh, rules: ShardingRules = DEFAULT):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs(schema_tree, shape_tree, mesh, rules),
        is_leaf=lambda x: isinstance(x, P),
    )


def device_put_tree(tree, schema_tree, mesh, rules: ShardingRules = DEFAULT):
    shapes = jax.tree.map(lambda x: np.shape(x), tree)
    shardings = tree_shardings(schema_tree, shapes, mesh, rules)
    return jax.tree.map(jax.device_put, tree, shardings)
