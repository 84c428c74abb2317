#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

Set-up (building the system, warming every shape the cell's traffic uses,
compiling or loading from the persistent cache) is timed as `setup_s`; then
the traffic drives the program for `--seconds`; then what the window
produced is compared with the plain reference. `--trace 0` reports the
cell's end-to-end metrics, `--trace 1` its per-layer metrics from a run
whose first seconds are traced by the profiler.

The last line of stdout is one JSON object: correct, attempted, failed,
metrics, device (and breakdown with --trace 1), then the numbers compared
with their limits under "checks". The run exits non-zero and prints no
result when JAX finds no TPU or fewer chips than the cell asks for, or when
the program's sources are not beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, cell=None, t_start=None) -> dict:
    """Run one cell; returns the result object (tests pass `cell` and
    require_chip=False to drive the same path at a small size on the CPU)."""
    t_start = T_PROCESS if t_start is None else t_start
    if not (harness.SRC / "repro").is_dir():
        raise FileNotFoundError(
            f"the program's sources are not at {harness.SRC}: run from a "
            "checkout of the repository")
    if str(harness.SRC) not in sys.path:
        sys.path.insert(0, str(harness.SRC))
    cell = cell if cell is not None else harness.load_cell(workload)
    if require_chip:
        devs = harness.require_chips(cell.chips)
    else:
        import jax

        devs = jax.devices()[:cell.chips]
    harness.enable_compile_cache()
    rec = cell.driver.run(cell, devs, seed=seed, seconds=seconds,
                          trace=trace, t_start=t_start)
    metrics = harness.read_metrics(rec, trace)
    out = harness.result_line(rec, metrics)
    harness.print_result(out, rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    except FileNotFoundError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
