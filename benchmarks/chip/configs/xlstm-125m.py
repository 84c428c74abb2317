"""xlstm-125m: the system under test, the program's continuous-batching
server (`launch/serve.Server`) with per-request AM tiers, built from the
configuration file; and the work of one token counted from the shapes.
"""
from __future__ import annotations


def model_config(cfg: dict):
    from repro.models.transformer import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="ssm", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], d_head=cfg["d_head"], d_ff=cfg["d_ff"],
        vocab=cfg["vocab"], pattern=tuple((k, "none") for k in cfg["pattern"]),
        scan_chunk=cfg["scan_chunk"], subquadratic=True, dtype=cfg["dtype"])


def _fan_in(path: tuple[str, ...], shape: tuple[int, ...], stacked: bool,
            cfg: dict) -> int:
    """Inputs each output of a weight leaf sums over (the configuration's
    rule); the embedding table takes its row count."""
    name = path[-1]
    if name == "embed":
        return shape[0]
    if name == "wo" and "mixer" in path:  # mLSTM (heads, d_head, d_model)
        return cfg["n_heads"] * cfg["d_head"]
    return shape[1] if stacked else shape[0]


def make_weights(cfg: dict, params, seed: int):
    """Weights of the same tree, shapes and dtypes as `params`, made from
    the seed by the configuration's rule in one jitted call."""
    import math

    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        keys = tuple(getattr(k, "key", str(k)) for k in path)
        if keys[-1] in ("ln1", "ln2", "norm_f"):
            specs.append(None)
        else:
            stacked = keys[0] == "blocks"
            specs.append(1.0 / math.sqrt(
                _fan_in(keys, leaf.shape, stacked, cfg)))
    shapes = [(leaf.shape, leaf.dtype) for _, leaf in flat]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(flat))
        out = []
        for k, scale, (shape, dtype) in zip(ks, specs, shapes):
            if scale is None:
                out.append(jnp.zeros(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * scale).astype(dtype))
        return out

    return jax.tree_util.tree_unflatten(treedef,
                                        make(jax.random.PRNGKey(seed)))


# The server's own seed. Its programs close over a noise key made from it,
# so a seed that changed from run to run would compile them anew each time.
SERVER_SEED = 0


def build_server(cfg: dict, seed: int):
    """The tiered server, batched mode, with the configuration's weights
    made from `seed` (the server's own initial weights replaced)."""
    import jax

    from repro.launch import mesh as meshlib
    from repro.launch.serve import Server

    server = Server(model_config(cfg), meshlib.make_host_mesh(),
                    slots=cfg["slots"], ctx=cfg["ctx"], seed=SERVER_SEED,
                    tiers=dict(cfg["tiers"]), mode="batched",
                    prefill_chunk=cfg["prefill_chunk"])
    server.params = jax.block_until_ready(
        make_weights(cfg, server.params, seed))
    return server


def approximate(cfg: dict, tier: str) -> bool:
    return cfg["tiers"][tier] is not None


def flops_per_token(cfg: dict, tier: str) -> float:
    """FLOPs (2 per multiply-add) to advance one token of one request: the
    forward pass through every block and the head, plus, for a row on an
    approximate tier, the variance contraction of every AM projection."""
    d, h, dh, v = cfg["d_model"], cfg["n_heads"], cfg["d_head"], cfg["vocab"]
    reps = cfg["n_layers"] // len(cfg["pattern"])
    kinds = list(cfg["pattern"]) * reps
    hd = h * dh
    mac = d * v  # head
    am_mac = d * v
    for kind in kinds:
        if kind == "mlstm":
            proj = 4 * d * hd + hd * d  # q, k, v, output gate; out proj
            # gates; matrix-memory update (4 dh^2) and readout (2 dh^2)
            mac += proj + 2 * d * h + h * 3 * dh * dh
            am_mac += proj
        elif kind == "slstm":
            proj = 4 * d * d + d * d  # input projections; out proj
            mac += proj + 4 * d * d  # recurrent matrices
            am_mac += proj
        else:
            raise ValueError(f"unknown block kind {kind!r}")
    return 2.0 * (mac + (am_mac if approximate(cfg, tier) else 0))
