"""Nestable, thread-aware spans exporting Chrome/Perfetto trace-event JSON.

  from repro.obs import trace
  with trace.span("engine.am_matmul", backend=name, m=m, k=k, n=n):
      ...
  trace.export_trace("artifacts/trace_engine.json")

Spans record complete ("ph": "X") events — wall-clock microseconds since
the process trace origin, per-thread track via the OS thread id — so the
exported file drops straight into Perfetto (https://ui.perfetto.dev) or
chrome://tracing. Request lifecycles that span many host calls use the
async event triple (`async_begin` / `async_instant` / `async_end`, one
track per request id). With observability disabled (`REPRO_OBS` off, the
default) `span()` returns a shared no-op object: no allocation, no
recording, nothing exported.

Convention (enforced by review, asserted in tests where cheap): spans wrap
HOST-side work only — never the inside of a jitted body, where the Python
code runs once at trace time and the recorded duration would be
compilation, not execution. Instrument the call site of the jitted
function instead.

One rule puts spans on the profiler's timeline: while observability is on,
every span also enters `jax.profiler.TraceAnnotation` under its bare name
(no args, so one phase keeps one label from call to call). Whenever a JAX
profiler session records, the spans then share the device trace's clock;
with no session the annotation costs one TraceMe check. While observability
is off, `span()` imports nothing and enters nothing. The recorded events
stay on `time.perf_counter`: `origin()` + ts x 1e-6 is a perf_counter time.

`python -m repro.obs.trace --validate f.json ...` validates files against
the Chrome trace-event schema (the CI gate for exported artifacts).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import threading
import time

from repro.obs import config

_lock = threading.Lock()
_events: list[dict] = []
_named_threads: set[int] = set()
_t0 = time.perf_counter()


def origin() -> float:
    """The perf_counter time (seconds) at which event timestamps are 0."""
    return _t0


def _now_us() -> float:
    return (time.perf_counter() - _t0) * 1e6


def _annotation(name: str):
    """An entered jax.profiler.TraceAnnotation named `name` (jax is
    imported here, by the first span recorded, never by an off span)."""
    import jax.profiler

    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


def _thread_meta(tid: int) -> list[dict]:
    if tid in _named_threads:
        return []
    _named_threads.add(tid)
    return [{
        "name": "thread_name", "ph": "M", "pid": os.getpid(), "tid": tid,
        "args": {"name": threading.current_thread().name},
    }]


class _NoopSpan:
    """Shared disabled span: __enter__/__exit__ do nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "args", "_ts", "_ann")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self._ts = 0.0
        self._ann = None

    def __enter__(self):
        self._ann = _annotation(self.name)
        self._ts = _now_us()
        return self

    def __exit__(self, *exc):
        end = _now_us()
        self._ann.__exit__(*exc)
        tid = threading.get_ident()
        ev = {
            "name": self.name, "ph": "X", "ts": self._ts,
            "dur": end - self._ts, "pid": os.getpid(), "tid": tid,
        }
        if self.args:
            ev["args"] = self.args
        with _lock:
            _events.extend(_thread_meta(tid))
            _events.append(ev)
        return False


def span(name: str, **args):
    """A context manager timing one host-side operation, also entered as a
    profiler annotation named `name` (the shared no-op when off)."""
    if not config.enabled():
        return _NOOP
    return _Span(name, args)


def instant(name: str, **args) -> None:
    """A zero-duration marker event on the current thread's track."""
    if not config.enabled():
        return
    tid = threading.get_ident()
    ev = {"name": name, "ph": "i", "s": "t", "ts": _now_us(),
          "pid": os.getpid(), "tid": tid}
    if args:
        ev["args"] = args
    with _lock:
        _events.extend(_thread_meta(tid))
        _events.append(ev)


def _async_event(ph: str, name: str, aid, args: dict) -> None:
    if not config.enabled():
        return
    tid = threading.get_ident()
    ev = {"name": name, "cat": name, "ph": ph, "id": str(aid),
          "ts": _now_us(), "pid": os.getpid(), "tid": tid}
    if args:
        ev["args"] = args
    with _lock:
        _events.extend(_thread_meta(tid))
        _events.append(ev)


def async_begin(name: str, aid, **args) -> None:
    """Open an async track (e.g. one serving request's lifecycle)."""
    _async_event("b", name, aid, args)


def async_instant(name: str, aid, phase: str, **args) -> None:
    """Mark a phase transition on an open async track."""
    _async_event("n", name, aid, dict(args, phase=phase))


def async_end(name: str, aid, **args) -> None:
    _async_event("e", name, aid, args)


def events() -> list[dict]:
    """Snapshot of the recorded events (copies the list, not the dicts)."""
    with _lock:
        return list(_events)


def reset() -> None:
    with _lock:
        _events.clear()
        _named_threads.clear()


def _json_default(o):
    """Coerce numpy scalars (span args come from np loops) to plain JSON."""
    item = getattr(o, "item", None)
    if callable(item):
        return item()
    return str(o)


def export_trace(path, tag: str | None = None) -> pathlib.Path:
    """Write the recorded events as a Chrome trace-event JSON document.

    The filename is pid-uniquified by default (``trace_x.json`` →
    ``trace_x_<pid>.json``) so concurrent writers (e.g. the sharded-parity
    subprocesses) never collide; pass ``tag=""`` to keep the exact name,
    or a string tag to substitute for the pid. ``trace_*.json`` globs
    still match either way.
    """
    path = config.tagged_path(path, tag)
    path.parent.mkdir(parents=True, exist_ok=True)
    doc = {"traceEvents": events(), "displayTimeUnit": "ms"}
    path.write_text(json.dumps(doc, indent=1, default=_json_default))
    return path


# --- schema validation (the CI artifact gate) -------------------------------

_REQUIRED = {"name", "ph", "ts", "pid", "tid"}
_KNOWN_PH = {"X", "B", "E", "i", "I", "M", "b", "n", "e", "C"}


def validate_chrome_trace(doc) -> list[str]:
    """Return schema problems (empty list = a loadable Chrome trace)."""
    problems: list[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document must be an object with a 'traceEvents' list"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return ["'traceEvents' must be a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph == "M":
            required = {"name", "ph", "pid"}
        else:
            required = _REQUIRED
        missing = required - ev.keys()
        if missing:
            problems.append(f"event {i}: missing {sorted(missing)}")
        if ph not in _KNOWN_PH:
            problems.append(f"event {i}: unknown phase {ph!r}")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            problems.append(f"event {i}: 'X' event needs a numeric 'dur'")
        if ph in ("b", "n", "e") and "id" not in ev:
            problems.append(f"event {i}: async event needs an 'id'")
        if "ts" in required and not isinstance(ev.get("ts"), (int, float)):
            problems.append(f"event {i}: 'ts' must be numeric")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Validate trace-event and metrics-snapshot JSON files")
    ap.add_argument("--validate", nargs="+", required=True, metavar="FILE")
    args = ap.parse_args(argv)
    rc = 0
    for f in args.validate:
        p = pathlib.Path(f)
        try:
            doc = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError) as e:
            print(f"FAIL  {p}: {e}")
            rc = 1
            continue
        # Dispatch by schema sniff so one CLI covers both artifact kinds:
        # trace_*.json carries 'traceEvents', metrics_*.json the flat
        # counters/gauges/histograms snapshot.
        if isinstance(doc, dict) and "traceEvents" in doc:
            problems = validate_chrome_trace(doc)
            kind = f"{len(doc['traceEvents'])} events, Chrome trace-event"
        elif isinstance(doc, dict) and {"counters", "gauges"} <= set(doc):
            from repro.obs import metrics as obs_metrics

            problems = obs_metrics.validate_metrics_snapshot(doc)
            n = sum(len(doc.get(k, {}))
                    for k in ("counters", "gauges", "histograms"))
            kind = f"{n} series, metrics-snapshot"
        else:
            problems = ["unrecognized document: neither a Chrome trace "
                        "('traceEvents') nor a metrics snapshot "
                        "('counters'/'gauges')"]
            kind = ""
        if problems:
            rc = 1
            print(f"FAIL  {p}: {len(problems)} problem(s)")
            for msg in problems[:20]:
                print(f"      {msg}")
        else:
            print(f"ok    {p}: {kind} schema valid")
    return rc


if __name__ == "__main__":
    sys.exit(main())
