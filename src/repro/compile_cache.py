"""JAX's persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

Every entry point (chip_smoke.py, launch/serve.py, launch/loadgen.py,
benchmarks/run.py) calls `enable()` before it compiles anything. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and `enable`
sets no other path. Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in .gitignore): a fixed path, because the path is part of what a
later run must find again.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
