"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy time, the
idle share, the top device operations and the longest idle gaps, each gap
labelled with the benchmark's host span that was open over it.

The traced window is the host span named ``WINDOW_SPAN`` (the harness opens
it with ``jax.profiler.TraceAnnotation`` around the traced part of a run);
without one, it runs from the first to the last device event. Busy time is
the union of the intervals in which an operation ran on a device, clipped to
the window, averaged over the devices that ran anything.

    python benchmarks/chip/xplane.py <trace dir or .xplane.pb>
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
# Ops that only hold other ops: counted in busy time, left out of the top ops.
CONTAINERS = ("while", "conditional", "call")
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over devices
    devices: list[str]
    busy_s_by_device: list[float]
    device_ops: list[tuple[str, float]]  # per-device average seconds
    module_s: dict[str, float]  # per-device average seconds by program
    idle_gaps: list[tuple[str, float]]  # summed seconds by host span
    n_gaps: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def module_seconds(self, substring: str) -> float | None:
        hits = [s for name, s in self.module_s.items() if substring in name]
        return sum(hits) if hits else None


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_label(hlo: str) -> str:
    """Short name of an 'XLA Ops' event: its HLO instruction name, opcode and
    result shape ('%copy.98 copy f32[2,10,9,64,12,12]'), not the whole text."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120]
    m = re.search(r"[})]\s+([a-z][a-z0-9-]*)\(", rest)
    opcode = m.group(1) if m else ""
    shape = rest.split("{", 1)[0] if not rest.startswith("(") else "(tuple)"
    return " ".join(x for x in (name, opcode, shape) if x)[:120]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def summarize(path: str, *, top: int = 10) -> Summary:
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(path))
    spans: list[tuple[float, float, str]] = []
    window = None
    dev_ops: dict[str, list[tuple[float, float, str]]] = {}
    dev_modules: dict[str, list[tuple[float, float, str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if not name.startswith(SPAN_PREFIX):
                        continue
                    s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                    if name == WINDOW_SPAN:
                        window = (s, e) if window is None else (
                            min(window[0], s), max(window[1], e))
                    else:
                        spans.append((s, e, name))
        elif _is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops_line = lines.get(OPS_LINE)
            if ops_line is None:
                continue
            labels: dict[str, str] = {}
            ops = []
            for ev in ops_line.events:
                name = ev.name
                label = labels.get(name)
                if label is None:
                    label = labels[name] = op_label(name)
                ops.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, label))
            dev_ops[plane.name] = ops
            mod_line = lines.get(MODULES_LINE)
            dev_modules[plane.name] = [] if mod_line is None else [
                (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                for ev in mod_line.events]
    devices = sorted(d for d, evs in dev_ops.items() if evs)
    if not devices:
        raise ValueError("the trace holds no device operations")
    if window is None:
        starts = [min(a for a, _, _ in dev_ops[d]) for d in devices]
        ends = [max(b for _, b, _ in dev_ops[d]) for d in devices]
        window = (min(starts), max(ends))
    lo, hi = window
    n = len(devices)

    busy_by_dev: list[float] = []
    op_s: dict[str, float] = {}
    mod_s: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for d in devices:
        evs = [(max(a, lo), min(b, hi), nm) for a, b, nm in dev_ops[d]
               if b > lo and a < hi]
        for a, b, nm in evs:
            if nm.split(" ")[1:2] not in ([c] for c in CONTAINERS):
                op_s[nm] = op_s.get(nm, 0.0) + (b - a) / n
        for a, b, nm in _clip_named(dev_modules[d], lo, hi):
            mod_s[nm] = mod_s.get(nm, 0.0) + (b - a) / n
        busy = _union([(a, b) for a, b, _ in evs])
        busy_by_dev.append(sum(b - a for a, b in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    spans.sort(key=lambda t: t[1] - t[0])  # innermost (shortest) first
    by_label: dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        label = next((nm for s, e, nm in spans if s <= mid <= e), "no span")
        by_label[label] = by_label.get(label, 0.0) + (b - a) / n
    return Summary(
        window_s=hi - lo,
        busy_s=sum(busy_by_dev) / n,
        devices=devices,
        busy_s_by_device=busy_by_dev,
        device_ops=sorted(op_s.items(), key=lambda t: -t[1])[:top],
        module_s=mod_s,
        idle_gaps=sorted(by_label.items(), key=lambda t: -t[1])[:top],
        n_gaps=len(gaps),
    )


def _clip_named(evs, lo, hi):
    return [(max(a, lo), min(b, hi), nm) for a, b, nm in evs
            if b > lo and a < hi]


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    s = summarize(argv[0])
    print(json.dumps(dataclasses.asdict(s) | {"idle_share": s.idle_share},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
