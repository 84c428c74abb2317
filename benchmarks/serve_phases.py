#!/usr/bin/env python3
"""Where a serving round's host time and a request's wait go, read from the
server's own spans (repro.obs) in a run of a chip benchmark serving cell.

  python3 benchmarks/serve_phases.py --workload xlstm125m-chat.tiers \
      --seed <n> --seconds 45

Runs the cell once as `benchmarks/chip/run.py --trace 1` does, except that
observability is on over the measured window (the span buffer is cleared
at its open, so warm-up is not recorded) and the device trace's idle gaps
may also be labelled by the server's `serve.*` spans (innermost wins).
Prints the run's result line, then one JSON object:

  round_host_ms    mean over the window's serve.round spans of their
                   duration less their serve.sync children: host time in
                   a round not spent waiting for the step
  queue_wait_ms    p50 over the requests due in the window of admit -
                   submit on their serve.request tracks
  prefill_wait_ms  p50 over the same requests of prefill_done - admit
  round_ms         mean serve.round duration; harness_round_ms is the
                   harness's mean of the same rounds timed from outside
  spans_per_round  spans recorded per serve.round
  phase_ms         per serve.* span name, its time in the window's rounds
                   over their count ("between" is the rest of a round)
  idle_gaps        the breakdown's gaps, relabelled

A wait still open at the window's close counts to the close.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

CHIP = pathlib.Path(__file__).resolve().parent / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))
import harness  # noqa: E402

SPAN_PREFIXES = ("bench.", "serve.")


def spans_on_clock(events: list[dict], origin: float) -> list[tuple]:
    """(name, start, end) of each complete span, perf_counter seconds."""
    return [(e["name"], origin + e["ts"] * 1e-6,
             origin + (e["ts"] + e["dur"]) * 1e-6)
            for e in events if e["ph"] == "X"]


def request_marks(events: list[dict], origin: float) -> dict[int, dict]:
    """Per request id, the perf_counter time of each mark on its
    serve.request track: submit (its begin), admit, prefill_done, end."""
    marks: dict[int, dict] = {}
    for e in events:
        if e.get("cat") != "serve.request":
            continue
        phase = {"b": "submit", "e": "end"}.get(e["ph"])
        if phase is None:
            phase = e["args"]["phase"]
        marks.setdefault(int(e["id"]), {})[phase] = origin + e["ts"] * 1e-6
    return marks


def round_host_ms(spans: list[tuple], lo: float, hi: float) -> float | None:
    rounds = [(a, b) for n, a, b in spans
              if n == "serve.round" and lo <= a and b <= hi]
    if not rounds:
        return None
    syncs = [(a, b) for n, a, b in spans if n == "serve.sync"]
    host = [b - a - sum(sb - sa for sa, sb in syncs if a <= sa and sb <= b)
            for a, b in rounds]
    return 1e3 * statistics.fmean(host)


def request_waits(marks: dict[int, dict], requests: list[dict], lo: float,
                  hi: float) -> list[tuple[float, float]] | None:
    """(queue wait, prefill wait) in seconds of each request due in the
    window; `requests[rid]` is the harness's record of request `rid`."""
    out = []
    for rid, r in enumerate(requests):
        m = marks.get(rid, {})
        if not lo <= r["due"] <= hi or "submit" not in m:
            continue
        admit = min(m.get("admit", hi), hi)
        first = min(m.get("prefill_done", hi), hi)
        out.append((admit - m["submit"], first - admit))
    return out or None


def phases(rec, events: list[dict], origin: float) -> dict:
    """The numbers this script prints, from a run's Record and the events
    the window recorded."""
    lo, hi = rec.window
    spans = spans_on_clock(events, origin)
    waits = request_waits(request_marks(events, origin),
                          rec.events.get("requests", []), lo, hi)
    rounds = [b - a for n, a, b in spans
              if n == "serve.round" and lo <= a and b <= hi]
    phase_ms: dict[str, float] = {}
    if rounds:
        for n, a, b in spans:
            if n.startswith("serve.") and lo <= a and b <= hi:
                phase_ms[n] = phase_ms.get(n, 0.0) + 1e3 * (b - a) / len(
                    rounds)
        phase_ms["between"] = phase_ms["serve.round"] - sum(
            v for n, v in phase_ms.items() if n != "serve.round")
    outside = [b - a for a, b, *_ in rec.events.get("rounds", ())
               if a >= lo and b <= hi]
    out = {
        "round_host_ms": round_host_ms(spans, lo, hi),
        "queue_wait_ms": 1e3 * statistics.median(q for q, _ in waits)
        if waits else None,
        "prefill_wait_ms": 1e3 * statistics.median(p for _, p in waits)
        if waits else None,
        "round_ms": 1e3 * statistics.fmean(rounds) if rounds else None,
        "harness_round_ms": 1e3 * statistics.fmean(outside)
        if outside else None,
        "spans_per_round": len(spans) / len(rounds) if rounds else None,
        "phase_ms": phase_ms or None,
    }
    if rec.trace is not None:
        out["idle_gaps"] = [[n, s] for n, s in rec.trace.idle_gaps]
    return out


def run_traced(cell, devs, *, seed: int, seconds: float, trace: bool,
               t_start: float):
    """One run of `cell` with observability on over its window; returns
    (the Record, the window's events, the trace origin)."""
    from repro import obs

    driver = cell.driver
    window = driver.window
    events: list[dict] = []

    def traced_window(*args, **kwargs):
        with obs.enabled_scope(True):
            obs.trace.reset()
            try:
                return window(*args, **kwargs)
            finally:
                events.extend(obs.trace.events())
                obs.trace.reset()

    xplane = harness.load_module(harness.HERE / "xplane.py")
    prefix = xplane.SPAN_PREFIX
    driver.window, xplane.SPAN_PREFIX = traced_window, SPAN_PREFIXES
    try:
        rec = driver.run(cell, devs, seed=seed, seconds=seconds, trace=trace,
                         t_start=t_start)
    finally:
        driver.window, xplane.SPAN_PREFIX = window, prefix
    return rec, events, obs.trace.origin()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.SRC))
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"serve_phases.py: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    rec, events, origin = run_traced(cell, devs, seed=args.seed,
                                     seconds=args.seconds, trace=True,
                                     t_start=T_PROCESS)
    out = harness.result_line(rec, harness.read_metrics(rec, True))
    harness.print_result(out, rec)
    print(json.dumps(phases(rec, events, origin)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
