"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, record memory/cost/roofline terms.

MUST be run as its own process: the ``XLA_FLAGS`` mutation below executes at
import time, before any jax import, to provide 512 placeholder host devices —
importing this module into a process that already initialized jax (e.g. the
pytest runner) will NOT change the device count. A pre-set ``XLA_FLAGS`` env
var is respected: the forced-device-count flag is appended only when the
caller has not already set one, so wrappers (CI, benchmarks, tests) can pin
their own device count or extra XLA options without being clobbered.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all --jobs 6   # parallel procs

Results: artifacts/dryrun/<arch>__<shape>__<mesh>.json
"""
import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512"
    ).strip()

import argparse
import json
import pathlib
import subprocess
import sys
import time

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape: str, mesh_name: str, verbose: bool = True) -> dict:
    import jax

    from repro.launch import mesh as meshlib
    from repro.launch import steps
    from repro.models import registry as R
    from repro.optim import adamw
    from repro.roofline import analysis

    spec = R.get(arch)
    cfg = spec.config
    sh = R.SHAPES[shape]
    mesh = meshlib.make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = meshlib.mesh_info(mesh)["n_devices"]
    kind = sh["kind"]

    t0 = time.time()
    with jax.set_mesh(mesh):
        if kind == "train":
            opt_cfg = adamw.AdamWConfig()
            # NOTE (§Perf iteration 5, REFUTED): passing param_specs here to
            # pin grad-accumulator sharding made llama4 WORSE (+15% flops,
            # +20% coll) — GSPMD does not propagate the constraint backward
            # through the scanned wgrad stacking. Left off by default.
            fn = steps.build_train_step(cfg, opt_cfg)
            in_specs, out_specs, args = steps.train_step_shardings(
                cfg, shape, mesh, opt_cfg)
            donate = (0, 1)  # params, opt state
        elif kind == "prefill":
            fn = steps.build_prefill_step(cfg)
            in_specs, out_specs, args = steps.prefill_shardings(cfg, shape, mesh)
            donate = ()
        else:
            fn = steps.build_decode_step(cfg)
            in_specs, out_specs, args = steps.decode_shardings(cfg, shape, mesh)
            donate = (1,)  # KV cache / recurrent state

        jitted = jax.jit(fn, in_shardings=in_specs, out_shardings=out_specs,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        roof, extras = analysis.from_compiled(
            compiled, arch=arch, shape=shape, mesh_name=mesh_name, chips=chips,
            cfg=cfg, shape_kind=kind, batch=sh["batch"], seq=sh["seq"])

    result = roof.to_json()
    result.update(extras)
    result.update({
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory_analysis": {
            k: int(getattr(mem, k, 0)) for k in (
                "temp_size_in_bytes", "argument_size_in_bytes",
                "output_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        },
        "kind": kind,
    })
    if verbose:
        ma = result["memory_analysis"]
        hbm_gb = (ma["temp_size_in_bytes"] + ma["argument_size_in_bytes"]
                  + ma["output_size_in_bytes"] - ma["alias_size_in_bytes"]) / 2**30
        print(f"[{arch} x {shape} x {mesh_name}] compiled in {t_compile:.0f}s; "
              f"~{hbm_gb:.2f} GiB/device; "
              f"flops/dev={result['hlo_flops']:.3e} bytes/dev={result['hlo_bytes']:.3e} "
              f"coll/dev={result['coll_bytes']:.3e}", flush=True)
        print("  " + roof.row(), flush=True)
    return result


def save_cell(arch: str, shape: str, mesh_name: str) -> dict:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    out = ARTIFACTS / f"{arch}__{shape}__{mesh_name}.json"
    res = run_cell(arch, shape, mesh_name)
    out.write_text(json.dumps(res, indent=1))
    return res


def all_cells(mesh_names):
    from repro.models import registry as R

    return [(a, s, m) for (a, s) in R.cells() for m in mesh_names]


def run_parallel(cells, jobs: int, force: bool = False) -> None:
    """Fan cells out over worker subprocesses (compiles are CPU-heavy)."""
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    pending = []
    for a, s, m in cells:
        out = ARTIFACTS / f"{a}__{s}__{m}.json"
        if out.exists() and not force:
            print(f"skip (cached): {a} x {s} x {m}")
            continue
        pending.append((a, s, m))
    running: list[tuple[subprocess.Popen, tuple]] = []
    while pending or running:
        while pending and len(running) < jobs:
            a, s, m = pending.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--mesh", m]
            env = dict(os.environ)
            log = open(ARTIFACTS / f"{a}__{s}__{m}.log", "w")
            proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                    env=env)
            running.append((proc, (a, s, m)))
            print(f"launch: {a} x {s} x {m} (pid {proc.pid})", flush=True)
        time.sleep(2)
        still = []
        for proc, cell in running:
            if proc.poll() is None:
                still.append((proc, cell))
            else:
                status = "ok" if proc.returncode == 0 else f"FAIL rc={proc.returncode}"
                print(f"done: {cell[0]} x {cell[1]} x {cell[2]} [{status}]",
                      flush=True)
        running = still


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = all_cells(meshes)
        if args.jobs > 1:
            run_parallel(cells, args.jobs, force=args.force)
        else:
            for a, s, m in cells:
                save_cell(a, s, m)
        return
    assert args.arch and args.shape
    for m in meshes:
        save_cell(args.arch, args.shape, m)


if __name__ == "__main__":
    main()
