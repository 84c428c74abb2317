"""Shared machinery of the chip benchmark.

Everything a cell needs is found by name, so a later change adds a
configuration, a traffic mix, a metric or a cell by adding files:

  BENCHMARK.json             the cells, their metrics and bounds
  configs/<config>.json      the configuration as it is run
  configs/<config>.py        builds the system under test through the
                             program's own entry; work per unit from shapes
  configs/<config>.ref.py    the plain reference (and the control)
  traffic/<mix>.json         parameters of one traffic mix; its "driver"
                             key names drivers/<driver>.py, the general
                             generator that reads it
  metrics/<metric>.py        reduces a run's record to one number
  cells/<workload>.json      the limits of the comparison that decides
                             `correct`, and the readings they were set from
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Any

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
SRC = CHECKOUT / "src"
TRACE_DIR = HERE / ".trace"
# The least part of a --trace 1 run's window that the profiler records:
# traces of a whole window are large, and reading them costs host time.
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


_MODULES: dict[str, Any] = {}


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (file names may hold '-' and '.')."""
    key = str(path)
    if key not in _MODULES:
        name = "bench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(HERE)))
        spec = importlib.util.spec_from_file_location(name, path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # configs/<config>.json
    traffic_name: str
    traffic: dict  # traffic/<mix>.json
    limits: dict  # cells/<workload>.json "limits"
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def config_module(self):
        return load_module(HERE / "configs" / f"{self.config_name}.py")

    @property
    def reference(self):
        return load_module(HERE / "configs" / f"{self.config_name}.ref.py")

    @property
    def driver(self):
        return load_module(HERE / "drivers" / f"{self.traffic['driver']}.py")

    def metrics(self, trace: bool) -> list[dict]:
        group = self.per_layer if trace else self.end_to_end
        return [m for m in group
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_json(
        CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(CHECKOUT / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(HERE / "cells" / f"{name}.json")["limits"],
        end_to_end=bench["end_to_end"],
        per_layer=bench["per_layer"],
    )


def subseeds(seed: int, n: int) -> list[int]:
    """n independent seeds below 2**31 from any whole-number seed."""
    import numpy as np

    state = np.random.SeedSequence(abs(int(seed))).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


def require_chips(n: int):
    """The first n devices; raises NoChip unless JAX sees n or more TPUs."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform if devs else "none"
    if platform != "tpu":
        raise NoChip(f"JAX found platform {platform!r} "
                     f"({len(devs)} device(s)), not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int | None:
    peaks = []
    for d in devs:
        try:
            st = d.memory_stats() or {}
        except Exception:  # backends without memory stats
            st = {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """The program's persistent compile cache (JAX_COMPILATION_CACHE_DIR
    where set, else <checkout>/.jax_cache), caching every program."""
    import jax

    from repro import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces and backend compiles while active (JAX's monitoring
    events): what runs inside the measured window must count 0 compiles."""

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.traces = 0
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if not self.active:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    @contextlib.contextmanager
    def counting(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """With --trace 1, records the window from its last arrival, or its
    last `seconds` where they begin earlier, to its close: a part that
    always holds device work. The trace is stopped and written after the
    window has closed. Device metrics are read over the traced part,
    host-clock metrics over the whole window."""

    def __init__(self, enabled: bool, tag: str, seconds: float = TRACE_SECONDS):
        self.enabled = enabled
        self.dir = TRACE_DIR / tag
        self.seconds = seconds
        self.on = False
        self.t_begin = math.inf
        self._window = None

    def arm(self, t0: float, seconds: float, last_arrival: float) -> None:
        """`last_arrival`: seconds from the window's start."""
        if self.enabled:
            self.t_begin = t0 + max(0.0, min(seconds - self.seconds,
                                             last_arrival))
            self.poll(time.perf_counter())

    def poll(self, now: float) -> None:
        """Start tracing when its part of the window comes."""
        if self.on or now < self.t_begin:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self._window = span("bench.window")
        self._window.__enter__()
        self.on = True
        self.t_begin = math.inf

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def summary(self):
        if not self.enabled:
            return None
        self.stop()
        xplane = load_module(HERE / "xplane.py")
        try:
            return xplane.summarize(str(self.dir))
        except ValueError as e:  # a trace with no device plane (the CPU)
            print(f"[bench] trace not read: {e}", file=sys.stderr)
            return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Check:
    """One number of the comparison that decides `correct`."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Record:
    """What a driver hands the metric readers."""

    cell: Cell
    setup_s: float
    window: tuple[float, float]  # the measured window, perf_counter seconds
    events: dict[str, list]
    counters: dict[str, Any]
    attempted: int
    failed: int
    checks: list[Check]
    device: dict
    memory_peak: int | None = None
    trace: Any = None  # xplane.Summary in a --trace 1 run

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def peak(self) -> dict:
        return load_module(HERE / "peaks.py").peak(self.device["kind"])

    @property
    def chips(self) -> int:
        return int(self.device["count"])


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, float), q))


def read_metrics(rec: Record, trace: bool) -> dict:
    out = {}
    for m in rec.cell.metrics(trace):
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(rec: Record, metrics: dict) -> dict:
    device = dict(rec.device)
    device["memory_peak_bytes"] = rec.memory_peak
    out: dict[str, Any] = {
        "correct": bool(rec.checks) and all(c.ok for c in rec.checks),
        "attempted": int(rec.attempted),
        "failed": int(rec.failed),
        "metrics": metrics,
        "device": device,
    }
    if rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in rec.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in rec.trace.idle_gaps],
        }
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in rec.checks}
    return out


def print_result(out: dict, rec: Record) -> None:
    info = {"setup_s": rec.setup_s, "window_s": rec.window_s, **rec.counters}
    for k, v in info.items():
        print(f"[bench] {k}: {json.dumps(v)}", flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
