"""Unified observability: spans + metrics + jit-retrace watchdog.

  from repro import obs
  with obs.span("engine.am_matmul", backend=name):
      ...
  obs.metrics.counter_inc("serve.tokens", tier=tier)
  step = obs.watchdog.watch_jit(step, name="serve.step")

Everything except the watchdog is gated on `REPRO_OBS` (default off, see
`repro.obs.config`) and costs one branch when disabled. While it is on,
every span also enters `jax.profiler.TraceAnnotation` under its bare name,
so a profiler session records the spans on the device trace's clock (see
`repro.obs.trace`). Submodules stay import-light: `trace`/`metrics`/
`numerics` are stdlib+numpy at import (`trace` imports `jax.profiler` with
the first span it records), `watchdog` is the single eager jax importer
(`drift` pulls the foundry in and is therefore NOT imported at package
level — `from repro.obs import drift` explicitly).
"""
from repro.obs import config, metrics, numerics, trace  # noqa: F401
from repro.obs.config import enabled, enabled_scope, set_enabled  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    async_begin, async_end, async_instant, export_trace, instant, span,
)
from repro.obs.metrics import export_metrics  # noqa: F401
