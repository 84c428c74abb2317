#!/usr/bin/env python3
"""Readings of a cell's comparison for the program and for its control.

  python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \
      --seconds <s>

Runs the cell as run.py does, once per seed in one process, and prints for
each seed the numbers the timed path reads against the reference and the
numbers the control reads (the reference one precision lower, in the
program's place). The limits in cells/<workload>.json are set between the
two. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.SRC))
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        rec = cell.driver.run(cell, devs, seed=seed, seconds=args.seconds,
                              trace=False, t_start=t, control=True)
        print(json.dumps({"seed": seed, "program": rec.counters["compared"],
                          "control": rec.counters["control"],
                          "attempted": rec.attempted,
                          "setup_s": rec.setup_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
