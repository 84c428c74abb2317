#!/usr/bin/env python3
"""Chip smoke test: the system's main path, once, on a TPU, through the
entry points a user calls, with every result checked.

  python chip_smoke.py             # one chip: phases a-d below
  python chip_smoke.py --chips 4   # the population-sharded CNN evaluator
                                   # and search on 4 chips vs 1 chip

Phases (one chip):
  a. AM engine at xlstm-125m's published width (K = N = 768), decode- and
     prefill-shaped M: exact, surrogate_fused (single and population maps)
     and bitexact_pallas; bitexact_pallas vs bitexact_ref bit for bit where
     the oracle fits in HBM, and the committed goldens through both.
  b. The paper CNN search: the population evaluator on the chip vs the
     same evaluator on the host CPU backend, a few NSGA-II generations, and
     the bit-exact conv kernel at both conv layers vs the oracle.
  c. Foundry: the compiled stacked emulator vs fp32_mul's XLA path.
  d. Serving xlstm-125m at its published widths (nothing cut): a tiered
     server and a surrogate_fused server, batched vs per-slot tokens.

Each phase prints one line per check: what ran, what it was compared with,
the largest difference, and seconds (the first call includes compilation;
the repeat is the run alone). The last line of stdout is one JSON object
naming the device. The script exits non-zero, and prints no JSON, when it
finds no TPU, when the repository is not next to it, or when any check
fails.

Tolerances where results are not bit-exact: f32 dots at the default
precision run on the MXU with each operand rounded to bf16 (relative error
2^-8 each), so a dot product may differ from float64 by up to
(2^-7 + K * 2^-22) * sum_k |x_k| |w_kn|. The script checks every element
against that bound and prints the largest ratio.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
K = N = 768  # xlstm-125m d_model
M_DECODE, M_PREFILL = 8, 512
CNN_POP, CNN_IMAGES = 64, 512
# A CNN accuracy is a count of argmax hits over CNN_IMAGES images; bf16
# rounding on the MXU moves logits by ~1e-3 relative and flips the images
# that sit that close to a decision boundary.
CNN_ACC_TOL = 0.05


def _fail_early(msg: str, code: int) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


class Smoke:
    """Runs checks, prints one line each, remembers failures."""

    def __init__(self):
        self.failures: list[str] = []

    def line(self, phase: str, what: str, vs: str, diff: str, secs: str,
             ok: bool) -> None:
        status = "ok" if ok else "FAIL"
        print(f"[{phase}] {status} | {what} | vs {vs} | {diff} | {secs}",
              flush=True)
        if not ok:
            self.failures.append(f"{phase}: {what}")

    def run(self, phase: str, fn) -> None:
        try:
            fn(self)
        except Exception:
            traceback.print_exc()
            self.line(phase, "phase raised", "-", "-", "-", False)


def timed(fn):
    """(result, first-call seconds, repeat seconds): the first call of a
    fresh shape compiles, the repeat is the run alone."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out2 = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    return out, out2, t1 - t0, t2 - t1


def secs(first: float, rep: float) -> str:
    return f"first call {first:.3f}s (compile+run), repeat {rep:.4f}s"


def bits_equal(a, b) -> tuple[bool, int]:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if a.shape != b.shape:
        return False, -1
    diff = a.view(np.uint32) != b.view(np.uint32)
    return not diff.any(), int(diff.sum())


def mxu_bound(xa, wa):
    """The stated f32-dot tolerance for x (M, K) @ w (K, N), float64."""
    import numpy as np

    k = xa.shape[-1]
    return (2.0 ** -7 + k * 2.0 ** -22) * (np.abs(xa) @ np.abs(wa))


def within(got, want, bound) -> tuple[bool, float, float]:
    import numpy as np

    err = np.abs(np.asarray(got, np.float64) - want)
    ok = bool(np.all(np.isfinite(got)) and np.all(err <= bound))
    return ok, float(err.max()), float((err / np.maximum(bound, 1e-300)).max())


# ---------------------------------------------------------------------------
# a. engine
# ---------------------------------------------------------------------------


def phase_engine(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine, schemes, surrogate

    rng = np.random.default_rng(SEED)
    key = jax.random.PRNGKey(SEED)
    w = (rng.standard_normal((K, N)) / np.sqrt(K)).astype(np.float32)
    vids = rng.integers(0, schemes.N_SEED_VARIANTS, (K, N)).astype(np.int32)
    vids_pop = rng.integers(0, schemes.N_SEED_VARIANTS,
                            (4, K, N)).astype(np.int32)
    w64 = w.astype(np.float64)

    def folded(v):
        mu, sg = engine.moment_maps(v)
        return w64 * (1.0 + mu), (w64 * w64) * (sg.astype(np.float64) ** 2)

    wm64, wv64 = folded(vids)
    # The decode rows are the first rows of the prefill batch.
    x_all = rng.standard_normal((M_PREFILL, K)).astype(np.float32)
    for m in (M_DECODE, M_PREFILL):
        x = x_all[:m]
        x64 = x.astype(np.float64)
        xj, wj = jnp.asarray(x), jnp.asarray(w)
        shape = f"M={m} K=N={K}"

        y, _, t1, t2 = timed(lambda: engine.am_matmul(xj, wj,
                                                      backend="exact"))
        ok, err, ratio = within(y, x64 @ w64, mxu_bound(x64, w64))
        s.line("a.engine", f"exact {shape}", "float64 host matmul",
               f"max|err| {err:.3e}, max err/bound {ratio:.3f}",
               secs(t1, t2), ok)

        (mean, var), _, t1, t2 = timed(lambda: engine.am_matmul(
            xj, wj, vids, backend="surrogate_fused", key=key,
            return_moments=True))
        bm = mxu_bound(x64, wm64)
        bv = mxu_bound(x64 * x64, wv64)
        ok1, e1, r1 = within(mean, x64 @ wm64, bm)
        ok2, e2, r2 = within(var, (x64 * x64) @ wv64, bv)
        s.line("a.engine", f"surrogate_fused moments {shape}",
               "float64 host moments (mean, var)",
               f"mean max|err| {e1:.3e} (/bound {r1:.3f}), "
               f"var max|err| {e2:.3e} (/bound {r2:.3f})",
               secs(t1, t2), ok1 and ok2)

        z = np.asarray(surrogate.crn_normal(key, (m, N), jnp.float32),
                       np.float64)
        y, _, t1, t2 = timed(lambda: engine.am_matmul(
            xj, wj, vids, backend="surrogate_fused", key=key))
        want = x64 @ wm64 + z * np.sqrt((x64 * x64) @ wv64)
        ok, err, ratio = within(y, want, bm + np.abs(z) * np.sqrt(bv))
        s.line("a.engine", f"surrogate_fused single map {shape}",
               "float64 host mean + z*sqrt(var), same z",
               f"max|err| {err:.3e}, max err/bound {ratio:.3f}",
               secs(t1, t2), ok)

        y, _, t1, t2 = timed(lambda: engine.am_matmul(
            xj, wj, vids_pop, backend="surrogate_fused", key=key))
        worst, oks = 0.0, True
        for p in range(vids_pop.shape[0]):
            pm, pv = folded(vids_pop[p])
            bmp, bvp = mxu_bound(x64, pm), mxu_bound(x64 * x64, pv)
            want = x64 @ pm + z * np.sqrt((x64 * x64) @ pv)
            ok, _, ratio = within(np.asarray(y)[p], want,
                                  bmp + np.abs(z) * np.sqrt(bvp))
            oks, worst = oks and ok, max(worst, ratio)
        s.line("a.engine", f"surrogate_fused population P=4 {shape}",
               "float64 host, per genome, same z",
               f"max err/bound {worst:.3f}", secs(t1, t2), oks)

        y, _, t1, t2 = timed(lambda: engine.am_matmul(
            xj, wj, vids, backend="bitexact_pallas"))
        # Sanity bound: the approximate compressors sit in the low 24 of
        # the 48 product columns, far below 2^-16 of a product.
        b = 2.0 ** -16 * (np.abs(x64) @ np.abs(w64))
        ok, err, ratio = within(y, x64 @ w64, b)
        if m == M_DECODE:
            y_dec = np.asarray(y)
        else:
            same, n_diff = bits_equal(np.asarray(y)[:M_DECODE], y_dec)
            ok = ok and same
            err_note = f"; rows 0..{M_DECODE - 1} equal the M={M_DECODE} run"\
                f" bit for bit: {same} ({n_diff} differ)"
        s.line("a.engine", f"bitexact_pallas {shape}",
               "float64 exact matmul (sanity bound 2^-16 sum|x||w|)",
               f"max|err| {err:.3e}, max err/bound {ratio:.3f}"
               + ("" if m == M_DECODE else err_note), secs(t1, t2), ok)

    # Pallas kernel vs oracle, bit for bit, where the oracle fits in HBM
    # (1920 B per multiply: M=8, K=N=128 is ~250 MB).
    m, k, n = 8, 128, 128
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    wq = jnp.asarray((rng.standard_normal((k, n)) / np.sqrt(k))
                     .astype(np.float32))
    vq = rng.integers(0, schemes.N_SEED_VARIANTS, (k, n)).astype(np.int32)
    got, _, t1, t2 = timed(lambda: engine.am_matmul(
        x, wq, vq, backend="bitexact_pallas"))
    want, _, r1, r2 = timed(lambda: engine.am_matmul(
        x, wq, vq, backend="bitexact_ref"))
    same, n_diff = bits_equal(got, want)
    s.line("a.engine", f"bitexact_pallas M={m} K={k} N={n}",
           "bitexact_ref on the chip, bit for bit",
           f"{n_diff} of {m * n} outputs differ",
           f"kernel {secs(t1, t2)}; oracle {secs(r1, r2)}", same)

    golden = np.load(ROOT / "artifacts" / "golden_bitexact.npz")
    kk, nn = golden["w_mm"].shape
    f = golden["w_cv"].shape[0]
    cases = [(v, np.full((kk, nn), i, np.int32), np.full((f, 3, 3), i, np.int32))
             for v, i in schemes.VARIANT_IDS.items()
             if v in schemes.SEED_VARIANTS]
    cases.append(("mixed", golden["mixed_mm_vids"], golden["mixed_cv_vids"]))
    for backend in ("bitexact_pallas", "bitexact_ref"):
        t0 = time.perf_counter()
        total = 0
        for name, vm, vc in cases:
            y = engine.am_matmul(jnp.asarray(golden["x_mm"]),
                                 jnp.asarray(golden["w_mm"]), vm,
                                 backend=backend)
            total += bits_equal(y, golden[f"{name}__matmul"])[1]
            y = engine.am_conv2d(jnp.asarray(golden["x_cv"]),
                                 jnp.asarray(golden["w_cv"]), vc,
                                 backend=backend)
            total += bits_equal(y, golden[f"{name}__conv2d"])[1]
        s.line("a.engine", f"{backend} on the committed goldens "
               f"({len(cases)} maps x matmul+conv2d)",
               "artifacts/golden_bitexact.npz, bit for bit",
               f"{total} values differ",
               f"all calls {time.perf_counter() - t0:.3f}s", total == 0)


# ---------------------------------------------------------------------------
# b. the paper CNN search
# ---------------------------------------------------------------------------


def phase_cnn(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine, schemes
    from repro.data import cifar_like
    from repro.experiments import paper_cnn
    from repro.models import cnn

    params = paper_cnn.load_params()
    rng = np.random.default_rng(SEED)
    genomes = rng.integers(0, schemes.N_SEED_VARIANTS,
                           (CNN_POP, cnn.N_SLOTS)).astype(np.int32)
    key = jax.random.PRNGKey(SEED)

    ev = paper_cnn.make_batched_evaluator(params, CNN_IMAGES)
    acc, _, t1, t2 = timed(lambda: ev(genomes, key))
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        params_cpu = jax.device_put(params, cpu)
        ev_cpu = paper_cnn.make_batched_evaluator(params_cpu, CNN_IMAGES)
        t0 = time.perf_counter()
        acc_cpu = ev_cpu(genomes, jax.random.PRNGKey(SEED))
        tc = time.perf_counter() - t0
    diff = float(np.max(np.abs(np.asarray(acc) - np.asarray(acc_cpu))))
    s.line("b.cnn", f"batched evaluator P={CNN_POP} images={CNN_IMAGES}",
           f"same evaluator on the host CPU backend (tol {CNN_ACC_TOL})",
           f"max|acc diff| {diff:.4f}, mean acc {float(np.mean(acc)):.4f}",
           secs(t1, t2) + f"; cpu {tc:.3f}s", diff <= CNN_ACC_TOL)

    t0 = time.perf_counter()
    res = paper_cnn.nsga_study(params, 4, n_images=CNN_IMAGES,
                               pop_size=CNN_POP, generations=3, seed=SEED,
                               log=None)
    front = res["front"]
    s.line("b.cnn", f"nsga_study k=4 pop={CNN_POP} generations=3",
           "a non-empty Pareto front",
           f"{len(front)} front members, {res['evals']} genomes scored",
           f"{time.perf_counter() - t0:.3f}s", len(front) > 0)

    g0 = np.asarray(front[0]["genome"], np.int32)
    maps = cnn.slot_maps_from_sequence(g0)
    x, _ = cifar_like.make_batch("test", 0, CNN_POP)
    x = jnp.asarray(x)
    w1, w2 = params["conv1_w"], params["conv2_w"]
    y1, _, t1, t2 = timed(lambda: engine.am_conv2d(
        x, w1, maps[0], backend="bitexact_pallas"))
    h = cnn._maxpool2(jax.nn.relu(y1 + params["conv1_b"]))
    y2, _, u1, u2 = timed(lambda: engine.am_conv2d(
        h, w2, maps[1], backend="bitexact_pallas"))
    for name, xin, w, mp, y, t in (
            ("conv1 3->10 on 32x32", x, w1, maps[0], y1, (t1, t2)),
            ("conv2 10->12 on 15x15", h, w2, maps[1], y2, (u1, u2))):
        ref = engine.am_conv2d(xin[:1], w, mp, backend="bitexact_ref")
        same, n_diff = bits_equal(np.asarray(y)[:1], ref)
        s.line("b.cnn", f"bitexact_pallas {name}, B={CNN_POP}",
               "bitexact_ref on image 0, bit for bit",
               f"{n_diff} of {np.asarray(ref).size} outputs differ",
               secs(*t), same and bool(np.all(np.isfinite(y))))


# ---------------------------------------------------------------------------
# c. foundry emulator
# ---------------------------------------------------------------------------


def phase_foundry(s: Smoke) -> None:
    import numpy as np

    from repro.core import errors, schemes
    from repro.foundry.characterize import DEFAULT_N, DEFAULT_SEED
    from repro.kernels import ops

    a, b = errors.random_fp32_operands(DEFAULT_N, seed=DEFAULT_SEED)
    maps = schemes.scheme_stack()
    got, _, t1, t2 = timed(lambda: ops.fp32_multiply_stacked(
        a, b, maps, impl="kernel"))
    want, _, r1, r2 = timed(lambda: ops.fp32_multiply_stacked(
        a, b, maps, impl="fused_xla"))
    same, n_diff = bits_equal(got, want)
    s.line("c.foundry", f"fp32_multiply_stacked Pallas kernel "
           f"{maps.shape[0]} maps x {a.size} operands",
           "fp32_mul XLA path, bit for bit",
           f"{n_diff} of {got.size} products differ",
           f"kernel {secs(t1, t2)}; xla {secs(r1, r2)}", same)


# ---------------------------------------------------------------------------
# d. serving at published width
# ---------------------------------------------------------------------------


def phase_serve(s: Smoke) -> None:
    import numpy as np

    from repro.launch import mesh as meshlib
    from repro.launch.serve import DEFAULT_TIER_POLICIES, Request, Server
    from repro.models import registry as R

    cfg = R.get("xlstm-125m").config
    arch = (f"{cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
            f"vocab={cfg.vocab}")
    mesh = meshlib.make_host_mesh()
    n_req, max_new, slots = 6, 8, 4
    tier_names = ("exact", "conservative", "aggressive")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
               for n in rng.integers(4, 17, n_req)]

    def submit_all(server, tiers, rid0=0):
        reqs = [Request(rid=rid0 + i, prompt=p.copy(), max_new=max_new,
                        tier=tiers[i % len(tiers)])
                for i, p in enumerate(prompts)]
        for r in reqs:
            server.submit(r)
        t0 = time.perf_counter()
        server.run()
        return reqs, time.perf_counter() - t0

    def serve(mode: str, **kw):
        """Build a server, run the requests (compiling), then run them again
        on the same server: the repeat is the run alone and must give the
        same tokens."""
        t0 = time.perf_counter()
        server = Server(cfg, mesh, slots=slots, ctx=64, seed=SEED, mode=mode,
                        **kw)
        tb = time.perf_counter() - t0
        tiers = tier_names if kw.get("tiers") else ("exact",)
        reqs, t1 = submit_all(server, tiers)
        stats = dict(server.stats)
        again, t2 = submit_all(server, tiers, rid0=len(prompts))
        repeat_same = [r.out for r in reqs] == [r.out for r in again]
        return reqs, stats, tb, t1, t2, repeat_same

    for label, kw in (
            ("tiers exact,conservative,aggressive",
             {"tiers": {t: DEFAULT_TIER_POLICIES[t] for t in tier_names}}),
            ("am_backend=surrogate_fused", {"am_backend": "surrogate_fused"})):
        out = {}
        for mode in ("batched", "per_slot"):
            reqs, stats, tb, t1, t2, repeat_same = serve(mode, **kw)
            done = all(r.status == "done" and len(r.out) == max_new
                       for r in reqs)
            out[mode] = [tuple(r.out) for r in reqs]
            gen = stats["generated"]
            s.line("d.serve", f"{arch} {label} mode={mode}: "
                   f"{n_req} requests on {slots} slots",
                   f"every request done with max_new={max_new} tokens; "
                   "a repeat on the same server gives the same tokens",
                   f"{sum(r.status == 'done' for r in reqs)} done, "
                   f"{sum(r.status == 'rejected' for r in reqs)} rejected, "
                   f"{gen} tokens, {stats['dispatches']} dispatches, "
                   f"repeat identical: {repeat_same}",
                   f"build {tb:.3f}s, first run {t1:.3f}s (compiles), "
                   f"repeat run {t2:.3f}s", done and repeat_same)
        n_same = sum(a == b for a, b in zip(out["batched"], out["per_slot"]))
        s.line("d.serve", f"{label} batched tokens",
               "per_slot tokens (slot-isolation contract)",
               f"{n_same} of {n_req} requests identical", "-",
               n_same == n_req)


# ---------------------------------------------------------------------------
# 4 chips: population-sharded CNN evaluator and search
# ---------------------------------------------------------------------------


def phase_sharded(s: Smoke, n_chips: int) -> None:
    import jax
    import numpy as np

    from repro.core import schemes
    from repro.experiments import paper_cnn
    from repro.models import cnn
    from repro.parallel import sharding as shd

    params = paper_cnn.load_params()
    mesh = shd.make_pop_mesh(n_chips)
    rng = np.random.default_rng(SEED)
    genomes = rng.integers(0, schemes.N_SEED_VARIANTS,
                           (4 * CNN_POP, cnn.N_SLOTS)).astype(np.int32)
    key = jax.random.PRNGKey(SEED)
    ev1 = paper_cnn.make_batched_evaluator(params, CNN_IMAGES)
    evn = paper_cnn.make_batched_evaluator(params, CNN_IMAGES, mesh=mesh)
    acc1, _, t1, t2 = timed(lambda: ev1(genomes, key))
    accn, _, u1, u2 = timed(lambda: evn(genomes, key))
    same, n_diff = bits_equal(acc1, accn)
    s.line("sharded", f"batched evaluator P={genomes.shape[0]} "
           f"images={CNN_IMAGES} on make_pop_mesh({n_chips})",
           "the same evaluator on 1 chip, bit for bit",
           f"{n_diff} of {acc1.size} accuracies differ",
           f"1 chip {secs(t1, t2)}; {n_chips} chips {secs(u1, u2)}", same)

    kw = dict(n_images=CNN_IMAGES, pop_size=CNN_POP, generations=3,
              seed=SEED, log=None)
    t0 = time.perf_counter()
    r1 = paper_cnn.nsga_study(params, 4, **kw)
    t1 = time.perf_counter()
    rn = paper_cnn.nsga_study(params, 4, mesh=mesh, **kw)
    t2 = time.perf_counter()
    same = r1["front"] == rn["front"] and len(r1["front"]) > 0
    s.line("sharded", f"nsga_study k=4 pop={CNN_POP} generations=3 on "
           f"make_pop_mesh({n_chips})", "nsga_study on 1 chip: same front",
           f"{len(rn['front'])} vs {len(r1['front'])} front members, "
           f"identical: {r1['front'] == rn['front']}",
           f"1 chip {t1 - t0:.3f}s; {n_chips} chips {t2 - t1:.3f}s", same)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the population-sharded CNN phase "
                         "on 4 chips, against 1 chip")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        _fail_early(f"the repository's src/repro is not next to {__file__}", 2)
    sys.path.insert(0, str(ROOT / "src"))

    # The phase-b reference runs on the host CPU backend of this process.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" in plats.split(",") and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail_early(f"no TPU: JAX found platform {dev.platform!r} "
                    f"({dev.device_kind})", 1)
    count = len(jax.devices())
    print(f"device {dev.platform} {dev.device_kind} x{count}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)
    if count < args.chips:
        _fail_early(f"asked for {args.chips} chips, JAX sees {count}", 1)

    s = Smoke()
    t0 = time.perf_counter()
    if args.chips == 1:
        s.run("a.engine", phase_engine)
        s.run("b.cnn", phase_cnn)
        s.run("c.foundry", phase_foundry)
        s.run("d.serve", phase_serve)
    else:
        s.run("sharded", lambda sm: phase_sharded(sm, args.chips))
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    if s.failures:
        print(f"chip_smoke: {len(s.failures)} check(s) failed: {s.failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
