#!/usr/bin/env python3
"""Sweep offered rates for a serving cell, in one process after one set-up,
to find the knee: the highest rate at which the backlog does not grow
across the window.

  python3 benchmarks/chip/knee.py --workload <name> --seed <n> \
      --seconds <s> --rates 1,2,3,4

For each rate, the cell's traffic (with only its rate changed) drives the
same warmed server for `--seconds`; the server then drains and its counters
reset. Each rate prints one JSON line: completed tokens/s, requests due and
finished, the mean backlog (requests queued or in a slot) in the first and
the last third of the window, and TTFT p50/p90. The benchmark's own runs do
not run this; its result is recorded in the traffic file and PERF.md.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.SRC))
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    serve = cell.driver
    server, flops, (_, s_traffic, _) = serve.setup(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(cell.traffic, rate_per_s=rate)
        plan = serve.schedule(tr, args.seconds, s_traffic, cell.config["vocab"])
        w = serve.window(server, plan, args.seconds, flops)
        t0, t1 = w["t0"], w["t1"]
        third = (t1 - t0) / 3

        def mean_backlog(lo, hi):
            xs = [n for t, n in w["backlog"] if lo <= t < hi]
            return sum(xs) / len(xs) if xs else 0.0

        toks = sum(1 for r in w["requests"] for t in r["tokens"] if t <= t1)
        ttft = [(r["tokens"][0] if r["tokens"] else t1) - r["due"]
                for r in w["requests"]]
        print(json.dumps({
            "rate_per_s": rate, "tokens_per_s": toks / (t1 - t0),
            "due": len(w["reqs"]),
            "done": sum(r.status == "done" for _, r, _ in w["reqs"]),
            "backlog_first_third": mean_backlog(t0, t0 + third),
            "backlog_last_third": mean_backlog(t1 - third, t1 + 1),
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * harness.percentile(ttft, 90),
            "rounds": {k: sum(1 for x in w["rounds"] if x[2] == k)
                       for k in ("prefill", "decode")},
        }), flush=True)
        server.queue.clear()  # drop the backlog, finish what holds a slot
        server.run()
        server.reset_metrics()
    return 0


if __name__ == "__main__":
    sys.exit(main())
