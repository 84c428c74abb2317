"""Kernel micro-benchmarks: wall time of the AM numerics paths on this host.

Interpret-mode Pallas timings are NOT TPU projections (the kernel body runs
in Python); the jnp reference paths are jit-compiled and representative of
relative cost: exact vs surrogate (~2x matmul) vs bitexact (~10^2 int ops /
multiply). Prints name,us_per_call,derived CSV rows.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, surrogate


def _bench(fn, *args, iters: int = 5, warmup: int = 3) -> float:
    for _ in range(warmup):  # compile + thread-pool/allocator warm-up
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.time()
        jax.block_until_ready(fn(*args))
        times.append(time.time() - t0)
    # Median per-call time: robust to scheduler preemption on shared
    # runners, where a single descheduled call can double the mean.
    return float(np.median(times)) * 1e6


def engine_bench(m: int = 256, k: int = 256, n: int = 256, pop: int = 16,
                 iters: int = 5, seed: int = 0) -> dict:
    """AM engine throughput per backend (persisted to BENCH_engine.json).

    Matmul rows are jitted closures over the engine call — the serving /
    model configuration, where the engine traces inside the consumer's jit —
    so they measure device throughput. The population-conv row times the
    eager engine call (host-side per-genome moment folding included), the
    per-generation cost the NSGA-II evaluator pays. Bit-exact backends are
    timed on a reduced shape and reported with the extrapolation factor
    (they cost ~10^2 integer ops per multiply by design).
    """
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    vids = rng.integers(0, 9, (k, n)).astype(np.int32)
    key = jax.random.PRNGKey(0)

    out: dict = {"shape": [m, k, n], "iters": iters, "matmul_us": {}}
    t_exact = _bench(jax.jit(lambda xx: engine.am_matmul(xx, w)), x, iters=iters)
    out["matmul_us"]["exact"] = t_exact
    for backend in ("surrogate_xla", "surrogate_fused"):
        fn = jax.jit(lambda xx, b=backend: engine.am_matmul(
            xx, w, vids, backend=b, key=key))
        out["matmul_us"][backend] = _bench(fn, x, iters=iters)

    # Bit-exact on a reduced shape, extrapolated to (m, k, n).
    bm, bk, bn = 16, 32, 32
    xb, wb = x[:bm, :bk], w[:bk, :bn]
    vb = vids[:bk, :bn]
    t_bit = _bench(
        jax.jit(lambda xx: engine.am_matmul(xx, wb, vb, backend="bitexact_ref")),
        xb, iters=2)
    scale = (m * k * n) / (bm * bk * bn)
    out["matmul_us"]["bitexact_ref"] = t_bit
    out["bitexact_shape"] = [bm, bk, bn]
    out["bitexact_extrapolation"] = scale
    out["matmul_relative_cost"] = {
        b: t / t_exact for b, t in out["matmul_us"].items() if b != "bitexact_ref"
    }
    out["matmul_relative_cost"]["bitexact_ref_extrapolated"] = \
        t_bit * scale / t_exact

    # Population conv: the fused backend's vectorized path vs per-genome
    # surrogate_xla calls (the NSGA-II population-evaluation primitive).
    xc = jnp.asarray(rng.standard_normal((8, 32, 32, 3)).astype(np.float32))
    wc = jnp.asarray(rng.standard_normal((10, 3, 3, 3)).astype(np.float32))
    genomes = rng.integers(0, 9, (pop, 10, 3, 3)).astype(np.int32)
    t_fused = _bench(
        lambda: engine.am_conv2d(xc, wc, genomes, backend="surrogate_fused",
                                 key=key), iters=iters)
    t_per = _bench(
        lambda: [engine.am_conv2d(xc, wc, g, backend="surrogate_xla", key=key)
                 for g in genomes], iters=max(1, iters // 2))
    out["conv_population"] = {
        "pop": pop,
        "fused_us": t_fused,
        "per_genome_xla_us": t_per,
        "speedup": t_per / t_fused,
        "fused_genomes_per_sec": pop / (t_fused * 1e-6),
    }

    # Batched bit-exact emulator: V-variant stacked sweep (the foundry's
    # characterization primitive) vs V scalar fp32_multiply_batch sweeps.
    from repro.core import fp32_mul, schemes
    from repro.kernels import ops

    n_emu = 1 << 14
    a_e = rng.standard_normal(n_emu).astype(np.float32)
    b_e = rng.standard_normal(n_emu).astype(np.float32)
    maps = np.stack([schemes.scheme_map(v) for v in schemes.AM_SEED_VARIANTS])
    n_var = maps.shape[0]
    t_stack = _bench(lambda: ops.fp32_multiply_stacked(a_e, b_e, maps),
                     iters=max(1, iters // 2), warmup=1)
    t_scalar = _bench(
        lambda: [fp32_mul.fp32_multiply_batch(a_e, b_e, m_) for m_ in maps],
        iters=max(1, iters // 2), warmup=1)
    out["emulator"] = {
        "variants": n_var,
        "operands": n_emu,
        "stacked_us": t_stack,
        "scalar_us": t_scalar,
        "speedup": t_scalar / t_stack,
        "stacked_mpairs_per_sec": n_var * n_emu / t_stack,
    }

    print(f"engine_matmul_exact_{m}x{k}x{n},{t_exact:.1f},1.00x")
    for b in ("surrogate_xla", "surrogate_fused"):
        print(f"engine_matmul_{b}_{m}x{k}x{n},{out['matmul_us'][b]:.1f},"
              f"{out['matmul_us'][b]/t_exact:.2f}x")
    print(f"engine_matmul_bitexact_ref_{bm}x{bk}x{bn},{t_bit:.1f},"
          f"{t_bit*scale/t_exact:.0f}x_extrapolated")
    print(f"engine_conv_population_pop{pop},{t_fused:.1f},"
          f"{out['conv_population']['speedup']:.2f}x_vs_per_genome")
    print(f"engine_emulator_stacked_v{n_var}_n{n_emu},{t_stack:.1f},"
          f"{out['emulator']['speedup']:.2f}x_vs_scalar")
    return out


def search_throughput(
    pop: int = 64, n_images: int = 64, iters: int = 3, seed: int = 0
) -> dict:
    """NSGA-II evaluation throughput: batched vs per-individual objectives.

    Scores `iters` fresh random populations of `pop` genomes through (a) the
    blocked-GEMM population evaluator (one device call per population, the
    NSGA-II per-generation cost) and (b) the per-individual baseline — the
    seed's `make_fast_evaluator` inner loop, one device round trip plus one
    noise-key fold per genome, exactly what `nsga_study` paid per objective
    call before batching. Fresh genomes each iteration keep the memo cache
    out of the measurement. Returns machine-readable metrics.
    """
    from repro.experiments import paper_cnn
    from repro.models import cnn

    try:
        params = paper_cnn.load_params()
    except FileNotFoundError:  # throughput does not need trained weights
        params = cnn.init_params(jax.random.PRNGKey(0))

    rng = np.random.default_rng(seed)
    pops = [
        rng.integers(1, 9, (pop, cnn.N_SLOTS)).astype(np.int32)
        for _ in range(iters + 1)
    ]
    base = jax.random.PRNGKey(42)

    ev_b = paper_cnn.make_batched_evaluator(params, n_images)
    ev_b(pops[0], base)  # compile
    t0 = time.time()
    for p in pops[1:]:
        ev_b(p, base)
    t_batch = (time.time() - t0) / iters

    ev_i = paper_cnn.make_fast_evaluator(params, n_images)
    ev_i(pops[0][0], base)  # compile
    t0 = time.time()
    for it, p in enumerate(pops[1:]):
        for i, g in enumerate(p):
            ev_i(g, jax.random.fold_in(base, it * pop + i))
    t_indiv = (time.time() - t0) / iters

    return {
        "pop_size": pop,
        "n_images": n_images,
        "iters": iters,
        "batched_sec_per_generation": t_batch,
        "per_individual_sec_per_generation": t_indiv,
        "batched_genomes_per_sec": pop / t_batch,
        "per_individual_genomes_per_sec": pop / t_indiv,
        "speedup": t_indiv / t_batch,
    }


def nsga2_bench(pop: int = 64, n_images: int = 64) -> dict:
    """Full search-throughput report incl. an end-to-end mini NSGA-II study
    (memo-cache hit rate, wall-clock per generation). Prints CSV rows and
    returns the metrics dict (persisted by benchmarks/run.py)."""
    from repro.experiments import paper_cnn
    from repro.models import cnn

    m = search_throughput(pop=pop, n_images=n_images)
    print(f"nsga2_eval_batched_pop{pop},{m['batched_sec_per_generation']*1e6:.1f},"
          f"{m['batched_genomes_per_sec']:.1f}_genomes_per_sec")
    print(f"nsga2_eval_per_individual_pop{pop},"
          f"{m['per_individual_sec_per_generation']*1e6:.1f},"
          f"{m['per_individual_genomes_per_sec']:.1f}_genomes_per_sec")
    print(f"nsga2_eval_speedup,{m['speedup']:.2f}x,batched_vs_per_individual")

    try:
        params = paper_cnn.load_params()
    except FileNotFoundError:
        params = cnn.init_params(jax.random.PRNGKey(0))
    gens = 4
    res = paper_cnn.nsga_study(
        params, k=4, n_images=n_images, pop_size=pop, generations=gens,
        seed=0, log=None,
    )
    m["study"] = {
        "pop_size": pop,
        "generations": gens,
        # Pipeline metric: cache hits count, and `seconds` includes the
        # first-call jit compiles — end-to-end search throughput, not device
        # throughput (the compile-free device metric is `speedup` above).
        "genomes_per_sec": res["genomes_per_sec"],
        "scored_genomes_per_sec": res["scored_genomes_per_sec"],
        "sec_per_generation": res["seconds"] / (gens + 1),  # +1: init population
        "includes_compile": True,
        "cache_hit_rate": res["eval_stats"]["cache_hit_rate"],
        "batch_calls": res["eval_stats"]["batch_calls"],
        "genomes_scored": res["eval_stats"]["genomes_scored"],
    }
    s = m["study"]
    print(f"nsga2_study_pop{pop}_gen{gens},{s['sec_per_generation']*1e6:.1f},"
          f"{s['genomes_per_sec']:.1f}_genomes_per_sec,"
          f"cache_hit_rate={s['cache_hit_rate']:.3f},"
          f"batch_calls={s['batch_calls']}")
    return m


def nsga2_sharded_bench(
    pop: int = 128,
    n_images: int = 16,
    device_counts: tuple | None = None,
    iters: int = 12,
    warmup: int = 3,
) -> dict:
    """Population-sharded NSGA-II evaluation throughput per device count.

    One process sweeps the device counts over ``make_pop_mesh(n)`` subsets
    of ``jax.devices()`` — on a chip host one process holds every chip. The
    default sweep is every power of two up to the devices present; whoever
    launches the process chooses them (on a CPU host, forced host devices:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the run).
    A requested count above the devices present raises. Genome scores are
    bitwise identical across device counts (the engine's CRN invariant;
    asserted by tests/test_engine_sharded.py), so the sweep is a pure
    throughput comparison. Returns per-device-count genomes/sec columns plus
    the 2-device speedup (persisted to BENCH_nsga2.json).
    """
    from repro.experiments import paper_cnn
    from repro.models import cnn
    from repro.parallel import sharding as shd

    n_avail = len(jax.devices())
    if device_counts is None:
        device_counts = tuple(1 << i for i in range(n_avail.bit_length()))
    try:
        params = paper_cnn.load_params()
    except FileNotFoundError:
        params = cnn.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    g = rng.integers(1, 9, (pop, cnn.N_SLOTS)).astype(np.int32)
    key = jax.random.PRNGKey(42)
    out: dict = {
        "pop_size": pop,
        "n_images": n_images,
        "iters": iters,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "per_device_count": {},
    }
    for nd in device_counts:
        mesh = shd.make_pop_mesh(nd) if nd > 1 else None
        ev = paper_cnn.make_batched_evaluator(params, n_images, mesh=mesh)
        for _ in range(warmup):
            ev(g, key)
        t0 = time.time()
        for _ in range(iters):
            ev(g, key)
        sec = (time.time() - t0) / iters
        row = {"n_devices": nd, "sec_per_generation": sec,
               "genomes_per_sec": pop / sec}
        out["per_device_count"][str(nd)] = row
        print(f"nsga2_sharded_pop{pop}_dev{nd},{sec*1e6:.1f},"
              f"{row['genomes_per_sec']:.1f}_genomes_per_sec")
    base = out["per_device_count"].get("1")
    two = out["per_device_count"].get("2")
    if base and two:
        out["speedup_2dev_vs_1dev"] = (
            two["genomes_per_sec"] / base["genomes_per_sec"])
        print(f"nsga2_sharded_speedup_2dev,{out['speedup_2dev_vs_1dev']:.2f}x,"
              f"pop{pop}")
    return out


def foundry_bench(
    n_char: int = 1 << 13,
    n_variants: int = 4,
    pop: int = 16,
    n_images: int = 32,
    iters: int = 3,
) -> dict:
    """Variant-foundry throughput: spec synthesis, bit-level characterization,
    registration, and expanded-alphabet population evaluation.

    Measures the cost of growing the search alphabet (persisted to
    BENCH_foundry.json): map rendering is microseconds, characterization is
    the bit-level emulation sweep (pairs/sec, exact baselines shared across
    the family), and the expanded-alphabet evaluator row shows that scoring
    genomes over K >= 16 variants costs the same as K = 9 — the moment
    tables are gathered per call, so alphabet size never enters the GEMM.
    Runs inside foundry.temporary_variants(): the live registry is restored.
    """
    from repro import foundry
    from repro.core import schemes
    from repro.experiments import paper_cnn
    from repro.models import cnn

    specs = foundry.default_family()[:n_variants]
    out: dict = {"n_char": n_char, "n_variants": len(specs)}

    t0 = time.time()
    for s in specs:
        s.to_map()
    out["spec_to_map_us"] = (time.time() - t0) / len(specs) * 1e6

    with foundry.temporary_variants():
        t0 = time.time()
        regs = foundry.register_family(specs, n=n_char)
        reg_sec = time.time() - t0
        out["register_family_sec"] = reg_sec
        # 2 regimes x (1 exact baseline + n_variants approx sweeps).
        pairs = n_char * 2 * (1 + len(specs))
        out["characterize_pairs_per_sec"] = pairs / reg_sec
        out["k_alphabet"] = len(schemes.VARIANTS)

        try:
            params = paper_cnn.load_params()
        except FileNotFoundError:
            params = cnn.init_params(jax.random.PRNGKey(0))
        ev = paper_cnn.make_batched_evaluator(params, n_images)
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(42)
        rows = {}
        for label, hi in (("seed_k9", 9), (f"expanded_k{out['k_alphabet']}",
                                           out["k_alphabet"])):
            pops = [rng.integers(0, hi, (pop, cnn.N_SLOTS)).astype(np.int32)
                    for _ in range(iters + 1)]
            ev(pops[0], key)  # compile
            t0 = time.time()
            for p in pops[1:]:
                ev(p, key)
            sec = (time.time() - t0) / iters
            rows[label] = {"sec_per_generation": sec,
                           "genomes_per_sec": pop / sec}
        out["evaluator"] = rows

    print(f"foundry_spec_to_map,{out['spec_to_map_us']:.1f},us_per_spec")
    print(f"foundry_characterize_n{n_char}x{len(specs)},"
          f"{reg_sec * 1e6:.1f},{out['characterize_pairs_per_sec']:.0f}_pairs_per_sec")
    for label, r in rows.items():
        print(f"foundry_eval_{label}_pop{pop},{r['sec_per_generation']*1e6:.1f},"
              f"{r['genomes_per_sec']:.1f}_genomes_per_sec")
    return out


def codesign_bench(
    n_specs: int = 4,
    outer_pop: int = 4,
    outer_generations: int = 1,
    inner_pop: int = 8,
    inner_generations: int = 2,
    n_images: int = 32,
    char_n: int = 1 << 11,
) -> dict:
    """Two-level codesign search throughput (persisted to BENCH_codesign.json).

    Runs a reduced-budget repro.codesign search against the blocked-GEMM
    population evaluator and reports the three scale metrics of the
    subsystem: specs characterized per second (the stacked bit-level sweep,
    misses only), inner interleaving evaluations per second (end-to-end,
    includes the per-candidate registration + search machinery), and the
    memo hit rates at both levels (spec-hash characterization memo, outer
    spec-set fitness memo, alphabet-salted inner sequence memo). All
    registrations are transient (`temporary_variants` inside the search) —
    the live registry is untouched.
    """
    import jax

    from repro import codesign
    from repro.experiments import paper_cnn
    from repro.models import cnn

    try:
        params = paper_cnn.load_params()
    except FileNotFoundError:  # throughput does not need trained weights
        params = cnn.init_params(jax.random.PRNGKey(0))
    ev = paper_cnn.make_batched_evaluator(params, n_images)
    key = jax.random.PRNGKey(1000)
    cfg = codesign.CodesignConfig(
        n_specs=n_specs, outer_pop=outer_pop,
        outer_generations=outer_generations, inner_pop=inner_pop,
        inner_generations=inner_generations, char_n=char_n,
    )
    t0 = time.time()
    res = codesign.codesign_search(
        lambda g: ev(g, key), genome_len=cnn.N_SLOTS, cfg=cfg
    )
    sec = time.time() - t0
    sm = res["stats"]["spec_memo"]
    inner = res["stats"]["inner"]
    outer = res["stats"]["outer"]
    out = {
        "n_specs": n_specs,
        "outer_pop": outer_pop,
        "outer_generations": outer_generations,
        "inner_pop": inner_pop,
        "inner_generations": inner_generations,
        "n_images": n_images,
        "char_n": char_n,
        "seconds": sec,
        "specs_characterized": sm["misses"],
        "specs_characterized_per_sec": (
            sm["misses"] / sm["char_seconds"] if sm["char_seconds"] else 0.0
        ),
        "spec_memo_hit_rate": (
            sm["hits"] / (sm["hits"] + sm["misses"])
            if sm["hits"] + sm["misses"] else 0.0
        ),
        "inner_evals": inner["genomes_requested"],
        "inner_evals_per_sec": inner["genomes_requested"] / sec if sec else 0.0,
        "inner_cache_hit_rate": inner["cache_hit_rate"],
        "outer_candidates": outer["genomes_requested"],
        "outer_cache_hit_rate": outer["cache_hit_rate"],
        "archive_points": len(res["archive"]),
    }
    print(f"codesign_char_n{char_n},{sm['char_seconds']*1e6:.1f},"
          f"{out['specs_characterized_per_sec']:.2f}_specs_per_sec,"
          f"memo_hit_rate={out['spec_memo_hit_rate']:.3f}")
    print(f"codesign_inner_evals,{sec*1e6:.1f},"
          f"{out['inner_evals_per_sec']:.1f}_evals_per_sec,"
          f"cache_hit_rate={out['inner_cache_hit_rate']:.3f}")
    print(f"codesign_outer_pop{outer_pop}_gen{outer_generations},"
          f"{out['outer_candidates']},candidates,"
          f"cache_hit_rate={out['outer_cache_hit_rate']:.3f},"
          f"archive={out['archive_points']}")

    # Async island-model outer search: warm candidates/sec at 1/2/4 workers
    # vs the warm sequential path, plus a live 1w-vs-2w archive parity check
    # (the replay determinism the tests gate, measured here on real tasks).
    # The cold run above has absorbed jit compilation, so these rows time
    # steady-state throughput. On a 1-core box thread workers add overlap
    # only where JAX releases the GIL (XLA execution), so the committed
    # speedup is ~parity there; multi-core CI runners see the real gain.
    def run_async(workers: int) -> tuple[dict, dict]:
        acfg = codesign.CodesignConfig(
            n_specs=n_specs, outer_pop=outer_pop,
            outer_generations=outer_generations, inner_pop=inner_pop,
            inner_generations=inner_generations, char_n=char_n,
            workers=workers, n_islands=2, migration_interval=2,
            migration_k=1, async_window=2,
        )
        t0 = time.time()
        r = codesign.codesign_search(
            lambda g: ev(g, key), genome_len=cnn.N_SLOTS, cfg=acfg
        )
        dt = time.time() - t0
        a = r["async"]
        n_cand = r["stats"]["outer"]["genomes_requested"]
        return r, {
            "seconds": dt,
            "candidates": n_cand,
            "candidates_per_sec": n_cand / dt if dt else 0.0,
            "queue_wait_fraction": a["queue_wait_fraction"],
            "migration_wait_seconds": a["migration_wait_seconds"],
        }

    t0 = time.time()
    codesign.codesign_search(  # warm sequential reference
        lambda g: ev(g, key), genome_len=cnn.N_SLOTS, cfg=cfg
    )
    seq_sec = time.time() - t0
    seq_cps = outer["genomes_requested"] / seq_sec if seq_sec else 0.0

    run_async(2)  # the async trajectory's own warmup (characterization
    # baselines for its wave shapes; first-eval-from-worker-thread costs)
    runs = {w: run_async(w) for w in (1, 2, 4)}
    r1, m1 = runs[1]
    r2, m2 = runs[2]
    parity = json.dumps(r1["archive"].as_dict(), sort_keys=True) == \
        json.dumps(r2["archive"].as_dict(), sort_keys=True)
    replay_ok = json.dumps(
        codesign.replay_archive(r2["replay"]).as_dict(), sort_keys=True
    ) == json.dumps(r2["archive"].as_dict(), sort_keys=True)
    out["async"] = {
        "n_islands": 2,
        "sequential_seconds": seq_sec,
        "sequential_candidates_per_sec": seq_cps,
        **{f"workers_{w}": m for w, (_, m) in runs.items()},
        "candidates_per_sec_2w": m2["candidates_per_sec"],
        "speedup_2w_vs_1w": (
            m2["candidates_per_sec"] / m1["candidates_per_sec"]
            if m1["candidates_per_sec"] else 0.0
        ),
        "speedup_2w_vs_sequential": (
            m2["candidates_per_sec"] / seq_cps if seq_cps else 0.0
        ),
        "parity_archive_identical": bool(parity and replay_ok),
    }
    for w, (_, m) in runs.items():
        print(f"codesign_async_w{w},{m['seconds']*1e6:.1f},"
              f"{m['candidates_per_sec']:.2f}_candidates_per_sec,"
              f"queue_wait={m['queue_wait_fraction']:.3f},"
              f"migration_wait={m['migration_wait_seconds']*1e3:.1f}ms")
    print(f"codesign_async_summary,{seq_sec*1e6:.1f},"
          f"seq={seq_cps:.2f}_candidates_per_sec,"
          f"speedup_2w_vs_1w={out['async']['speedup_2w_vs_1w']:.2f},"
          f"parity={out['async']['parity_archive_identical']}")
    return out


def main() -> None:
    """Host micro-benchmarks, routed through the AM engine."""
    rng = np.random.default_rng(0)
    m = k = n = 256
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    vids = rng.integers(0, 9, (k, n)).astype(np.int32)
    key = jax.random.PRNGKey(0)

    t_exact = _bench(lambda: engine.am_matmul(x, w))
    print(f"matmul_exact_{m}x{k}x{n},{t_exact:.1f},1.00x")

    t_surr = _bench(lambda: engine.am_matmul(x, w, vids, backend="surrogate_xla",
                                             key=key))
    print(f"matmul_am_surrogate_{m}x{k}x{n},{t_surr:.1f},{t_surr/t_exact:.2f}x")

    xb, wb, vb = x[:16, :32], w[:32, :32], vids[:32, :32]
    t_bit = _bench(lambda: engine.am_matmul(xb, wb, vb, backend="bitexact_ref"),
                   iters=2)
    scale = (m * k * n) / (16 * 32 * 32)
    print(f"matmul_am_bitexact_16x32x32,{t_bit:.1f},"
          f"{t_bit*scale/t_exact:.0f}x_extrapolated")

    mult = surrogate.moment_tables()
    print(f"surrogate_calibration_variants,{len(mult[0])},cached")


if __name__ == "__main__":
    main()
