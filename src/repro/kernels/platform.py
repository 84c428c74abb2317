"""Where a kernel call runs, decided at call time and never at import.

Pallas kernels run compiled on a TPU and in interpret mode on any other
backend. Every kernel entry point asks `interpret_mode()` when it is called,
so no caller can run the interpreter on a TPU, and a process that picks its
backend after importing this package still gets the right answer.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend — where a jitted call runs — is a TPU."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas interpret flag for a call made now."""
    return not on_tpu()


def device_kind() -> str:
    """``device_kind`` of the default backend's first device."""
    return jax.devices()[0].device_kind
