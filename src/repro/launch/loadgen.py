"""Mixed-tier load generator for the continuous-batching serving tier.

Drives a deterministic stream of requests — random prompt lengths, tiers
cycled across the DEFAULT_TIER_POLICIES menu — through launch/serve.Server
and measures throughput (generated tokens/sec), request latency (p50/p99
from submit to finish), and dispatch counts. `bench()` runs the same load
twice, batched vs per_slot (the one-dispatch-per-busy-row reference with
token-at-a-time prefill — the pre-batching serving loop's schedule), and
reports the speedup; benchmarks/run.py writes it to BENCH_serve.json where
check_regression.py gates `serve.tokens_per_sec` and the batched-over-
per_slot speedup floor.

A fourth pass (``out["audit"]``) re-runs the traced load with shadow-exact
audits sampling every request, measuring the audits' hot-path overhead
(gated <= 5%: the deferred-audit design means only the sampling hash rides
the serving loop), per-tier exact-vs-served token agreement, and — via a
handful of eager engine probes — the realized calibration z of the
surrogate error model, drift-checked against artifacts/audit_baseline.json.

  PYTHONPATH=src python -m repro.launch.loadgen --out artifacts
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro import compile_cache, obs
from repro.launch import mesh as meshlib
from repro.launch.serve import DEFAULT_TIER_POLICIES, Request, Server
from repro.models import registry as R
from repro.obs import numerics as obs_numerics
from repro.obs import watchdog

_BASELINE_PATH = (pathlib.Path(__file__).resolve().parents[3]
                  / "artifacts" / "audit_baseline.json")


def make_requests(cfg, n: int, max_new: int, seed: int = 0,
                  tiers=tuple(DEFAULT_TIER_POLICIES),
                  prompt_lens=(3, 5, 8)) -> list[Request]:
    """Deterministic mixed-tier request stream (tiers cycle round-robin)."""
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab,
                                    prompt_lens[i % len(prompt_lens)]
                                    ).astype(np.int32),
                max_new=max_new, tier=tiers[i % len(tiers)])
        for i in range(n)
    ]


def run_load(server: Server, requests: list[Request]) -> dict:
    """Submit all requests up front, drain the server, measure."""
    t0 = time.perf_counter()
    for r in requests:
        server.submit(r)
    finished = server.run()
    wall = time.perf_counter() - t0
    done = [r for r in finished if r.status == "done"]
    lat = np.array([r.latency for r in done]) if done else np.zeros(1)
    return {
        "wall_s": wall,
        "tokens_per_sec": server.stats["generated"] / max(wall, 1e-9),
        "generated": server.stats["generated"],
        "dispatches": server.stats["dispatches"],
        "decode_ticks": server.stats["decode_ticks"],
        "prefill_rounds": server.stats["prefill_rounds"],
        "completed": len(done),
        "rejected": sum(r.status == "rejected" for r in finished),
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
    }


def _server(cfg, mesh, mode: str, slots: int, ctx: int, tiers,
            audit_fraction: float = 0.0) -> Server:
    # per_slot is the pre-batching baseline: one dispatch per busy slot,
    # token-at-a-time prefill (prefill_chunk=1).
    chunk = 4 if mode == "batched" else 1
    return Server(cfg, mesh, slots=slots, ctx=ctx, tiers=tiers, mode=mode,
                  prefill_chunk=chunk, audit_fraction=audit_fraction)


def _calibration_probes(n_keys: int = 4, seed: int = 7) -> dict:
    """Eager AM matmuls with fixed CRN keys through the engine audit hook.

    Serving steps are jitted, so the engine's eager-only audit sampler never
    fires inside the load itself; these probes are the realized-error source
    feeding the numerics accumulators (and the drift check). Keys are fixed
    fold_ins of a constant, so the surrogate draws — and hence the measured
    calibration z — are deterministic run to run.
    """
    import jax

    from repro.core import engine

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((24, 64)).astype(np.float32)
    w = rng.standard_normal((64, 32)).astype(np.float32)
    eng = engine.AMEngine()
    base = jax.random.PRNGKey(seed)
    prev = obs_numerics.audit_fraction()
    obs_numerics.configure(fraction=1.0)
    try:
        for i in range(n_keys):
            key = jax.random.fold_in(base, i)
            for backend in ("surrogate_fused", "surrogate_xla"):
                eng.matmul(x, w, "uniform:pm_csi", backend=backend, key=key,
                           site="loadgen.probe")
            if i == 0:
                # Bit-exact output is key-independent; one emulated probe
                # checks the characterized moments against realized bits.
                eng.matmul(x[:8], w, "rr:8", backend="bitexact_ref", key=key,
                           site="loadgen.probe")
    finally:
        obs_numerics.configure(fraction=prev)
    z_abs = 0.0
    sampled = 0
    for _, acc in obs_numerics.AUDIT.items():
        z_abs = max(z_abs, acc.z_max_abs)
        sampled += 1
    return {"probe_keys": n_keys, "probe_sites": sampled,
            "calibration_z_abs": z_abs}


def bench(arch: str = "xlstm-125m", requests: int = 8, max_new: int = 24,
          slots: int = 4, ctx: int = 64, seed: int = 0,
          out_dir=None) -> dict:
    """Batched vs per_slot under identical mixed-tier load. One warmup pass
    per mode pays compilation before the timed pass.

    A third pass re-runs the batched load with observability forced ON and
    reports ``out["obs"]``: the traced-vs-untraced throughput overhead
    fraction (gated <= 5% by check_regression.py) and the step/reset
    retrace counts (serve.step must trace exactly twice: the prefill-chunk
    shape and the decode shape). The untraced passes are untouched — their
    numbers stay comparable to historical baselines. With ``out_dir`` set,
    the traced pass also exports trace_serve.json + metrics_serve.json.

    A fourth pass (``out["audit"]``, see ``_audit_pass``) re-runs the
    traced load with shadow-exact audits on every request and reports the
    audit hot-path overhead, per-tier token agreement, calibration z, and
    the drift check against artifacts/audit_baseline.json.
    """
    cfg = R.get(arch).smoke
    mesh = meshlib.make_host_mesh()
    tiers = dict(DEFAULT_TIER_POLICIES)
    out: dict = {"config": {"arch": arch, "requests": requests,
                            "max_new": max_new, "slots": slots, "ctx": ctx,
                            "tiers": sorted(tiers)}}
    for mode in ("batched", "per_slot"):
        sv = _server(cfg, mesh, mode, slots, ctx, tiers)
        # Warm up THIS instance (the jitted step caches per Server), then
        # zero the counters for the timed pass.
        run_load(sv, make_requests(cfg, min(3, requests), 2, seed=seed + 1))
        sv.reset_metrics()
        out[mode] = run_load(sv, make_requests(cfg, requests, max_new, seed=seed))
    speedup = out["batched"]["tokens_per_sec"] / max(
        out["per_slot"]["tokens_per_sec"], 1e-9)
    out["serve"] = {
        "tokens_per_sec": out["batched"]["tokens_per_sec"],
        "speedup_batched_vs_per_slot": speedup,
        "p50_latency_s": out["batched"]["p50_latency_s"],
        "p99_latency_s": out["batched"]["p99_latency_s"],
    }
    with obs.enabled_scope(True):
        obs.trace.reset()
        obs.metrics.reset()
        sv = _server(cfg, mesh, "batched", slots, ctx, tiers)
        run_load(sv, make_requests(cfg, min(3, requests), 2, seed=seed + 1))
        sv.reset_metrics()
        traced = run_load(sv, make_requests(cfg, requests, max_new, seed=seed))
        if out_dir is not None:
            out_dir = pathlib.Path(out_dir)
            obs.export_trace(out_dir / "trace_serve.json")
            obs.export_metrics(out_dir / "metrics_serve.json")
    out["obs"] = {
        "traced_tokens_per_sec": traced["tokens_per_sec"],
        "overhead_fraction": max(
            0.0, 1.0 - traced["tokens_per_sec"]
            / max(out["batched"]["tokens_per_sec"], 1e-9)),
        "retraces": {
            "serve_step": watchdog.retrace_count(sv._jit_step),
            "serve_reset": watchdog.retrace_count(sv._jit_reset),
        },
    }
    out["audit"] = _audit_pass(cfg, mesh, tiers, requests, max_new, slots,
                               ctx, seed, traced["tokens_per_sec"], out_dir)
    return out


def _audit_pass(cfg, mesh, tiers, requests, max_new, slots, ctx, seed,
                traced_tps, out_dir) -> dict:
    """Audit-enabled re-run of the traced load (audit_fraction=1.0).

    The hot-path timing covers run_load() only — shadow rescoring is
    deferred, so ``overhead_fraction`` (vs the plain traced pass) isolates
    exactly what auditing adds to the serving loop: the per-finish sampling
    hash and the pending-list append. run_audits() is timed separately as
    ``shadow_seconds``. Calibration probes and the observed-vs-baseline
    drift check ride the same pass so one BENCH_serve.json carries every
    audit gate check_regression.py reads.
    """
    with obs.enabled_scope(True):
        obs.trace.reset()
        obs.metrics.reset()
        obs_numerics.reset()
        sv = _server(cfg, mesh, "batched", slots, ctx, tiers,
                     audit_fraction=1.0)
        run_load(sv, make_requests(cfg, min(3, requests), 2, seed=seed + 1))
        sv.reset_metrics()  # drop the short warmup requests' pending audits
        # Pay the audit-step compiles outside the timings: one request whose
        # replay pads to the same pow2 length as the timed load's replays.
        run_load(sv, make_requests(cfg, 1, max_new - 2, seed=seed + 2))
        sv.run_audits()
        sv.reset_metrics()
        audited = run_load(sv, make_requests(cfg, requests, max_new,
                                             seed=seed))
        t0 = time.perf_counter()
        sv.run_audits()
        shadow_s = time.perf_counter() - t0
        summary = sv.audit_summary()
        probes = _calibration_probes()
        obs_numerics.publish()
        drift_report = None
        if _BASELINE_PATH.exists():
            from repro.obs import drift

            drift_report = drift.check_observed(
                obs_numerics.snapshot(), drift.load_baseline(_BASELINE_PATH))
        if out_dir is not None:
            doc = {"summary": summary, "probes": probes,
                   "numerics": obs_numerics.snapshot(),
                   "drift": drift_report}
            p = pathlib.Path(out_dir) / "audit_serve.json"
            p.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    tiers_out = summary["tiers"]
    return {
        "audited_requests": summary["audited_requests"],
        "audited_tokens_per_sec": audited["tokens_per_sec"],
        "overhead_fraction": max(
            0.0, 1.0 - audited["tokens_per_sec"] / max(traced_tps, 1e-9)),
        "shadow_seconds": shadow_s,
        "token_agreement": {t: v["token_agreement"]
                            for t, v in tiers_out.items()},
        "max_logit_divergence": {t: v["max_logit_divergence"]
                                 for t, v in tiers_out.items()},
        "replay_mismatches": sum(v["replay_mismatches"]
                                 for v in tiers_out.values()),
        "calibration_z_abs": probes["calibration_z_abs"],
        "drift_alerts": (drift_report["alert_count"]
                         if drift_report is not None else 0),
        "drift_baseline_found": drift_report is not None,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description="Mixed-tier serving load benchmark")
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=64)
    ap.add_argument("--out", default=None,
                    help="directory to write BENCH_serve.json (and the "
                         "traced pass's trace/metrics artifacts) into")
    ap.add_argument("--obs", dest="obs", action="store_true", default=None,
                    help="enable tracing/metrics for the untraced passes too "
                         "(default: env REPRO_OBS; the obs-overhead pass "
                         "always runs traced)")
    ap.add_argument("--no-obs", dest="obs", action="store_false")
    args = ap.parse_args()
    compile_cache.enable()
    if args.obs is not None:
        obs.set_enabled(args.obs)
    res = bench(arch=args.arch, requests=args.requests, max_new=args.max_new,
                slots=args.slots, ctx=args.ctx, out_dir=args.out)
    s = res["serve"]
    print(f"[loadgen] batched {s['tokens_per_sec']:.1f} tok/s "
          f"({res['batched']['dispatches']} dispatches) vs per_slot "
          f"{res['per_slot']['tokens_per_sec']:.1f} tok/s "
          f"({res['per_slot']['dispatches']} dispatches) -> "
          f"{s['speedup_batched_vs_per_slot']:.2f}x; "
          f"p50 {s['p50_latency_s'] * 1e3:.0f}ms p99 {s['p99_latency_s'] * 1e3:.0f}ms; "
          f"obs overhead {res['obs']['overhead_fraction'] * 100:.1f}% "
          f"(step traces: {res['obs']['retraces']['serve_step']})")
    a = res["audit"]
    agree = " ".join(f"{t}={v:.3f}" for t, v in a["token_agreement"].items())
    print(f"[loadgen] audit: {a['audited_requests']} requests, "
          f"hot-path overhead {a['overhead_fraction'] * 100:.1f}%, "
          f"shadow {a['shadow_seconds']:.1f}s; agreement {agree}; "
          f"|z| {a['calibration_z_abs']:.2f}; "
          f"drift alerts {a['drift_alerts']}")
    if args.out:
        out_dir = pathlib.Path(args.out)
        out_dir.mkdir(parents=True, exist_ok=True)
        path = out_dir / "BENCH_serve.json"
        path.write_text(json.dumps(res, indent=2, sort_keys=True) + "\n")
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
