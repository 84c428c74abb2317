"""Benchmark aggregator: one section per paper table/figure + framework perf.

  PYTHONPATH=src python -m benchmarks.run            # full sweep
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI smoke subset

Besides the printed sections, machine-readable metrics persist under
artifacts/ (or --out DIR) so the perf trajectory is trackable across PRs
(CI uploads them as workflow artifacts and gates them with
benchmarks.check_regression): BENCH_nsga2.json (search throughput:
genomes/sec, wall-clock per generation, memo-cache hit rate, plus the
"sharded" section — genomes/sec per forced-host-device count and the
2-device speedup), BENCH_engine.json (per-backend AM engine matmul/conv
timings plus the batched bit-exact emulator rows), BENCH_foundry.json
(variant-foundry synthesis/characterization throughput plus
seed-vs-expanded alphabet evaluator rows), BENCH_codesign.json
(two-level placement+interleaving search: specs characterized/sec,
inner-evals/sec, memo hit rates at every level) and BENCH_serve.json
(continuous-batching serving tier: batched vs per-slot tokens/sec,
p50/p99 request latency, dispatch counts under mixed-tier load, plus the
audit pass: shadow-exact audit overhead, per-tier token agreement, and
calibration z). audit_drift.json re-characterizes the AM error models on
an independent draw against the committed artifacts/audit_baseline.json
(a fresh baseline lands next to it for --update adoption).

--smoke runs the runner-sized subset the PR gate measures (engine,
foundry, codesign, the sharded-search sweep over the devices present —
written to BENCH_nsga2_sharded.json — and the serving load bench) and skips
the paper-table sections.

Every section runs; the process exits non-zero if any section raised.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import traceback

_FAILED: list[str] = []


def _section(title: str, fn):
    """Run one section; a failure is printed, recorded, and the run goes on
    (main exits non-zero at the end)."""
    print(f"\n{'=' * 72}\n{title}\n{'=' * 72}")
    try:
        return fn()
    except Exception:
        traceback.print_exc()
        _FAILED.append(title)
        return None


def _write(out_dir: pathlib.Path, name: str, metrics) -> None:
    if metrics is None:
        return
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / name
    path.write_text(json.dumps(metrics, indent=1))
    print(f"wrote {path}")


def smoke(out_dir: pathlib.Path) -> None:
    """The PR-gate subset: what the CI runner can measure in minutes.

    The codesign and serving sections run traced (see _codesign_bench_traced
    and loadgen.bench's obs pass) and drop trace_*.json + metrics_*.json
    Perfetto-loadable artifacts next to the BENCH files; CI validates them
    against the Chrome trace-event schema and uploads them.
    """
    from benchmarks import kernel_bench

    _write(out_dir, "BENCH_engine.json", _section(
        "AM engine — per-backend matmul/conv throughput (smoke)",
        lambda: kernel_bench.engine_bench(iters=5, pop=8)))
    _write(out_dir, "BENCH_foundry.json", _section(
        "Variant foundry — synthesis/characterization/expanded-alphabet eval",
        kernel_bench.foundry_bench))
    _write(out_dir, "BENCH_codesign.json", _section(
        "Codesign — two-level placement+interleaving search throughput",
        lambda: _codesign_bench_traced(out_dir)))
    _write(out_dir, "BENCH_nsga2_sharded.json", _section(
        "NSGA-II sharded search — genomes/sec per device count",
        kernel_bench.nsga2_sharded_bench))
    _write(out_dir, "BENCH_serve.json", _section(
        "Serving — batched vs per-slot mixed-tier load (smoke)",
        lambda: _serve_bench(requests=8, max_new=24, slots=4,
                             out_dir=out_dir)))
    _write(out_dir, "audit_drift.json", _section(
        "AM error-model drift — re-characterization vs committed baseline",
        lambda: _drift_check(out_dir, check_n=1 << 13)))


def _serve_bench(**kw):
    from repro.launch import loadgen

    return loadgen.bench(**kw)


def _drift_check(out_dir: pathlib.Path, build_n=None, check_n=None):
    """Re-characterize the variant registry against the committed
    artifacts/audit_baseline.json (independent operand draw — see
    repro/obs/drift.py) and drop a fresh baseline next to the report so
    `check_regression --update` can adopt it. With no committed baseline
    yet, the report carries alert_count=0 and flags the bootstrap."""
    from repro.obs import drift

    fresh = drift.build_baseline(n=build_n)
    drift.save_baseline(fresh, out_dir / "audit_baseline.json")
    base_path = (pathlib.Path(__file__).resolve().parent.parent
                 / "artifacts" / "audit_baseline.json")
    if not base_path.exists():
        print("no committed audit_baseline.json — bootstrap: adopt the "
              "bench_fresh copy via check_regression --update")
        return {"alert_count": 0, "bootstrap": True,
                "variants_checked": len(fresh["variants"])}
    report = drift.check_baseline(drift.load_baseline(base_path), n=check_n)
    print(f"{report['variants_checked']} variants, "
          f"max |mu z| {report['max_abs_mu_z']:.2f}, "
          f"{report['alert_count']} alert(s)")
    for a in report["alerts"]:
        print(f"  ALERT {a}")
    return report


def _codesign_bench_traced(out_dir: pathlib.Path):
    """codesign_bench with observability forced on, exporting the sweep's
    spans (characterization waves, per-candidate evals, SpecMemo traffic)
    as trace_codesign.json + metrics_codesign.json."""
    from benchmarks import kernel_bench
    from repro import obs

    obs.trace.reset()
    obs.metrics.reset()
    with obs.enabled_scope(True):
        res = kernel_bench.codesign_bench()
        obs.export_trace(out_dir / "trace_codesign.json")
        obs.export_metrics(out_dir / "metrics_codesign.json")
    return res


def full(out_dir: pathlib.Path) -> None:
    from benchmarks import (fig2_cnn, kernel_bench, roofline_summary,
                            table1_hw, table2_errors)

    _section("Table I — hardware characteristics (paper cost model)",
             table1_hw.main)
    _section("Table II — FP32 AM error characteristics (N=400k)",
             table2_errors.main)
    _section("Fig 2/4/5 — CNN: uniform AMs, NSGA-II interleaving, displacement",
             fig2_cnn.main)
    _section("Kernel micro-benchmarks (host)", kernel_bench.main)
    _write(out_dir, "BENCH_engine.json", _section(
        "AM engine — per-backend matmul/conv throughput",
        kernel_bench.engine_bench))
    _write(out_dir, "BENCH_foundry.json", _section(
        "Variant foundry — synthesis/characterization/expanded-alphabet eval",
        kernel_bench.foundry_bench))
    _write(out_dir, "BENCH_codesign.json", _section(
        "Codesign — two-level placement+interleaving search throughput",
        kernel_bench.codesign_bench))
    nsga2_metrics = _section(
        "NSGA-II search throughput — batched vs per-individual evaluation",
        kernel_bench.nsga2_bench)
    sharded_metrics = _section(
        "NSGA-II sharded search — genomes/sec per device count",
        kernel_bench.nsga2_sharded_bench)
    if nsga2_metrics is not None:
        if sharded_metrics is not None:
            nsga2_metrics["sharded"] = sharded_metrics
        _write(out_dir, "BENCH_nsga2.json", nsga2_metrics)
    _write(out_dir, "BENCH_serve.json", _section(
        "Serving — batched vs per-slot mixed-tier load",
        lambda: _serve_bench(requests=12, max_new=24, slots=4,
                             out_dir=out_dir)))
    _write(out_dir, "audit_drift.json", _section(
        "AM error-model drift — re-characterization vs committed baseline",
        lambda: _drift_check(out_dir)))
    _section("Roofline — dry-run derived, per (arch x shape x mesh)",
             roofline_summary.main)


def main(argv=None) -> int:
    from repro import compile_cache

    compile_cache.enable()
    default_out = pathlib.Path(__file__).resolve().parent.parent / "artifacts"
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="runner-sized PR-gate subset only")
    ap.add_argument("--out", type=pathlib.Path, default=default_out,
                    help="directory for BENCH_*.json (default: artifacts/)")
    ap.add_argument("--obs", dest="obs", action="store_true", default=None,
                    help="trace/meter every section, not just the dedicated "
                         "traced passes (default: env REPRO_OBS)")
    ap.add_argument("--no-obs", dest="obs", action="store_false")
    args = ap.parse_args(argv)
    if args.obs is not None:
        from repro import obs

        obs.set_enabled(args.obs)
    if args.smoke:
        smoke(args.out)
    else:
        full(args.out)
    if _FAILED:
        print(f"\n{len(_FAILED)} section(s) failed: {_FAILED}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
