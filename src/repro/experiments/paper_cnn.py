"""Paper Sec. III experiments: uniform-AM CNN, NSGA-II interleaving, displacement.

Reproduces, on the procedural CIFAR-10 stand-in (data/cifar_like.py):

  * Fig. 2(a): each of the 8 FP32 AMs applied uniformly across both conv
    layers — inference accuracy + cumulative multiplier PDP;
  * Fig. 4 / Fig. 2(b): NSGA-II over 198-slot sequences for K = 2..8,
    objectives (area, PDP, accuracy-loss); knee-point selection;
  * Fig. 5: 10 random displacements of each selected sequence (positional
    robustness — the paper's double approximation);
  * bit-exact spot validation of the selected sequences (the surrogate is the
    inner-loop numerics; the bit-level emulator is the ground truth).

Results are persisted as JSON under artifacts/ so benchmarks can re-render
tables without re-running the (hour-scale) optimization.
"""
from __future__ import annotations

import contextlib
import functools
import json
import pathlib
import time

import jax
import numpy as np

from repro.core import engine, hwmodel, interleave, nsga2, schemes
from repro.data import cifar_like
from repro.models import cnn
from repro.obs import config as obs_config, trace as obs_trace, watchdog

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts"
PARAMS_FILE = ARTIFACTS / "paper_cnn_params.npz"

# The paper's hardware accounting: per-multiplier metrics scale by the slot
# count; conv slots here = 198 (22 filters x 9 coefficients).
N_SLOTS = cnn.N_SLOTS


def _obs_scope(obs: bool | None):
    """Study-level observability override: None inherits REPRO_OBS."""
    if obs is None:
        return contextlib.nullcontext()
    return obs_config.enabled_scope(obs)


def load_params():
    d = np.load(PARAMS_FILE)
    return {k: jax.numpy.asarray(v) for k, v in d.items()}


def train_params(steps: int = 3000, batch: int = 64, seed: int = 0, save: bool = True):
    params = cnn.init_params(jax.random.PRNGKey(seed))
    it = cifar_like.iterate("train", batch, steps)
    params = cnn.train(params, it, steps, log_every=max(1, steps // 10))
    if save:
        ARTIFACTS.mkdir(exist_ok=True)
        np.savez(PARAMS_FILE, **{k: np.asarray(v) for k, v in params.items()})
    return params


def _slot_maps(seq: np.ndarray):
    return cnn.slot_maps_from_sequence(np.asarray(seq, np.int32))


def eval_accuracy(
    params,
    seq: np.ndarray | None,
    n_images: int = 2000,
    *,
    numerics: str = "surrogate",
    key=None,
    noise_scale: float = 1.0,
):
    """CNN inference accuracy under a 198-slot sequence (None = exact).

    `numerics` is either a shorthand ("surrogate" -> surrogate_xla,
    "bitexact" -> bitexact_ref) or any engine backend name.
    """
    x, y = cifar_like.make_batch("test", 0, n_images)
    if seq is None:
        return cnn.accuracy(params, x, y, numerics="exact")
    backend = {"surrogate": "surrogate_xla", "bitexact": "bitexact_ref"}.get(
        numerics, numerics
    )
    cfg = cnn.AMConfig.from_sequence(seq, backend=backend, noise_scale=noise_scale)
    return cnn.accuracy(params, x, y, numerics=cfg, key=key)


def make_fast_evaluator(params, n_images: int, noise_scale: float = 1.0):
    """Jit-compiled surrogate CNN accuracy with *traced* slot maps.

    Compiles once; each genome evaluation is then a fast device call. This is
    the NSGA-II inner-loop evaluator (cnn.accuracy would recompile per genome
    because slot maps enter as constants). The surrogate moment tables enter
    as traced operands fetched per call, so the evaluator follows foundry
    registrations: a grown alphabet changes the tables' shape and forces a
    retrace instead of serving moments clamped to the trace-time registry.
    """
    import jax.numpy as jnp

    from repro.core import surrogate
    from repro.kernels import ref as kref

    x_np, y_np = cifar_like.make_batch("test", 0, n_images)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)

    @jax.jit
    def n_correct(map1, map2, mu_t, sg_t, key):
        k1, k2 = jax.random.split(key)
        h = kref.am_conv2d_surrogate_ref(
            x, params["conv1_w"], map1, k1, noise_scale,
            moment_tables=(mu_t, sg_t),
        ) + params["conv1_b"]
        h = cnn._maxpool2(jax.nn.relu(h))
        h = kref.am_conv2d_surrogate_ref(
            h, params["conv2_w"], map2, k2, noise_scale,
            moment_tables=(mu_t, sg_t),
        ) + params["conv2_b"]
        h = cnn._maxpool2(jax.nn.relu(h))
        logits = cnn._head(params, h)
        return jnp.sum(jnp.argmax(logits, -1) == y)

    def evaluate(seq: np.ndarray, key) -> float:
        m1, m2 = _slot_maps(seq)
        mu_t, sg_t = surrogate.moment_tables()
        return float(n_correct(
            jnp.asarray(m1), jnp.asarray(m2), jnp.asarray(mu_t),
            jnp.asarray(sg_t), key)) / n_images

    return evaluate


def make_batched_evaluator(
    params,
    n_images: int,
    noise_scale: float = 1.0,
    block: int = 2,
    image_chunk: int = 64,
    mesh=None,
    pop_axis_name: str = "pop",
):
    """Population-batched surrogate CNN accuracy: one device call per batch.

    Returns ``evaluate(genomes (P, 198) int32, key) -> (P,) accuracies``. This
    is the NSGA-II per-generation evaluator: the whole population is scored in
    a single jitted device call, so a generation costs one host->device round
    trip instead of P.

    A thin client of the AM engine's fused-surrogate machinery: slot-map
    canonicalization (engine.canonical_conv_map), host-side moment folding
    into per-genome GEMM weights (engine.fold_conv_gemm_weights), the im2col
    patch layout (engine.conv_patch_matrix) and the fixed-shape population
    padding policy (engine.pad_population) are all the engine's; this module
    only contributes the CNN-specific pipeline around them (pool, dense head,
    argmax) fused into ONE jit so a generation stays a single device call:

      * each conv is an im2col GEMM whose input patches are shared by every
        genome; the layer-1 patch matrix is precomputed once at build;
      * all GEMMs run channel-major ((F, K) @ (K, pixels)), the fast
        orientation for the CPU backend;
      * the population is processed in ``block``-genome slices inside one
        `lax.scan`, keeping per-block activations cache-resident instead of
        materializing population-width tensors (memory-bandwidth, not FLOPs,
        dominates batched evaluation);
      * the noise instance z is drawn once per (chunk, layer) from ``key`` and
        shared across the population — common random numbers, so genome
        comparisons are made under the same noise realization and a genome's
        score is independent of batch composition and evaluation order.

    Populations are padded to ``block`` x a power of two, so per-block GEMM
    shapes are fixed: a genome's score is bitwise identical whether it is
    evaluated alone or inside any batch (the batched-vs-per-individual parity
    the tests assert), and compilation cost is O(log P) distinct shapes.

    ``mesh`` (a 1-D device mesh whose axis is named ``pop_axis_name``, see
    parallel/sharding.py::make_pop_mesh) shards the genome-block axis over
    devices under shard_map: the population pads up to a block multiple of
    the mesh axis, each device scans its contiguous slice of blocks with the
    identical per-block math, and the CRN noise (keyed only by the global
    ``key`` and the chunk index, replicated across shards) makes accuracies
    bitwise identical to the single-device call at any shard count
    (tests/test_engine_sharded.py asserts this differentially).
    """
    import jax.numpy as jnp

    n_shards = 1 if mesh is None else int(dict(mesh.shape)[pop_axis_name])
    x_np, y_np = cifar_like.make_batch("test", 0, n_images)
    bc = max(
        d for d in range(1, min(image_chunk, n_images) + 1) if n_images % d == 0
    )
    nc = n_images // bc
    g_blk = block

    # Layer geometry (paper CNN: 32x32x3 -> conv3x3 -> pool -> conv3x3 -> pool).
    f1, f2 = cnn.LAYER_FILTERS  # 10, 12
    h1 = 30  # conv1 output spatial
    h2p = 15  # pooled
    h2 = 12  # conv2 output spatial actually consumed (13th row/col is
    # dropped by the VALID 2x2 pool, so it is never computed here)
    hf = 6  # final spatial

    # Precompute transposed im2col patches of the (fixed) evaluation images
    # (engine tap-major layout: rows (i, j, c)), chunked. ~97 kB per image.
    px = engine.conv_patch_matrix(x_np, 3, 3)  # (27, n, 900)
    px = px.reshape(27, nc, bc, h1 * h1).transpose(1, 0, 2, 3).reshape(nc, 27, -1)
    pxt = jnp.asarray(px, jnp.float32)
    pxxt = pxt * pxt
    yc = jnp.asarray(y_np.reshape(nc, bc))

    w1 = np.asarray(params["conv1_w"], np.float32)  # (f1, 3, 3, 3)
    w2 = np.asarray(params["conv2_w"], np.float32)  # (f2, 3, 3, f1)
    b1 = jnp.asarray(params["conv1_b"]).reshape(1, f1, 1, 1, 1)
    b2 = jnp.asarray(params["conv2_b"]).reshape(1, f2, 1)
    wd, bd = jnp.asarray(params["dense_w"]), jnp.asarray(params["dense_b"])

    @functools.lru_cache(maxsize=None)
    def _compiled(n_blocks: int):
        def n_correct(wm1, wv1, wm2, wv2, key):
            # Block count from the (possibly shard-local) operand, so the
            # same body serves the single-device and sharded paths.
            nb = wm1.shape[0]
            def chunk_step(total, inp):
                ci, pxc, pxxc, yb = inp
                k1, k2 = jax.random.split(jax.random.fold_in(key, ci))
                z1 = jax.random.normal(k1, (f1, bc, h1, h1))
                z2 = jax.random.normal(k2, (f2, bc * h2 * h2))

                def block_step(carry, ws):
                    bm1, bv1, bm2, bv2 = ws
                    mean = (bm1 @ pxc).reshape(g_blk, f1, bc, h1, h1)
                    var = (bv1 @ pxxc).reshape(g_blk, f1, bc, h1, h1)
                    y = mean + b1 + z1[None] * jnp.sqrt(var)
                    y = y.reshape(g_blk, f1, bc, h2p, 2, h2p, 2).max(6).max(4)
                    y = jax.nn.relu(y)  # relu/maxpool commute
                    cols = [
                        y[:, :, :, i : i + h2, j : j + h2]
                        for i in range(3)
                        for j in range(3)
                    ]
                    pat = jnp.stack(cols, axis=2).reshape(g_blk, f1 * 9, -1)
                    m2 = jnp.einsum("gfk,gkm->gfm", bm2, pat)
                    v2 = jnp.einsum("gfk,gkm->gfm", bv2, pat * pat)
                    y2 = m2 + b2 + z2[None] * jnp.sqrt(v2)
                    y2 = y2.reshape(g_blk, f2, bc, hf, 2, hf, 2).max(6).max(4)
                    y2 = jax.nn.relu(y2)
                    h = jnp.transpose(y2, (0, 2, 3, 4, 1)).reshape(g_blk, bc, -1)
                    pred = jnp.argmax(h @ wd + bd, -1)
                    return carry, jnp.sum(pred == yb[None], axis=1, dtype=jnp.int32)

                _, ncs = jax.lax.scan(block_step, 0, (wm1, wv1, wm2, wv2))
                return total + ncs.reshape(-1), None

            total, _ = jax.lax.scan(
                chunk_step,
                jnp.zeros((nb * g_blk,), jnp.int32),
                (jnp.arange(nc), pxt, pxxt, yc),
            )
            return total

        # One watchdog record per lru-cached block count; name lookups sum
        # them, so the budget is "distinct population shapes", not calls.
        if mesh is None:
            return watchdog.watch_jit(
                n_correct, name="paper_cnn.batched_evaluator")
        from jax.sharding import PartitionSpec as P

        sp = P(pop_axis_name)
        return watchdog.watch_jit(jax.shard_map(
            n_correct, mesh=mesh, in_specs=(sp, sp, sp, sp, P()),
            out_specs=sp, check_vma=False),
            name="paper_cnn.batched_evaluator")

    def evaluate(genomes: np.ndarray, key) -> np.ndarray:
        g = np.atleast_2d(np.asarray(genomes, np.int32))
        if g.shape[1] != N_SLOTS:
            raise ValueError(f"genome length {g.shape[1]} != {N_SLOTS} slots")
        p = g.shape[0]
        # Shard divisibility: round the power-of-two block count up to a
        # multiple of the mesh axis so every shard gets an equal slice of
        # blocks (a no-op for power-of-two meshes at or below the count).
        pb = engine.population_blocks(p, g_blk)
        n_blocks = -(-pb // n_shards) * n_shards
        g = engine.pad_population(g, g_blk)
        if g.shape[0] < n_blocks * g_blk:  # mesh wider than the padded pop
            g = np.concatenate(
                [g, np.repeat(g[:1], n_blocks * g_blk - g.shape[0], axis=0)])
        # Engine canonicalization + host-side moment folding into per-genome
        # GEMM weights (L1 tap-major to match the precomputed image patches,
        # L2 channel-major to match the pooled-activation stacking below).
        m1 = engine.canonical_conv_map(g[:, : f1 * 9], f1, 3, 3)
        m2 = engine.canonical_conv_map(g[:, f1 * 9 :], f2, 3, 3)
        wm1, wv1 = engine.fold_conv_gemm_weights(
            w1, m1, noise_scale=noise_scale, layout="tap_major")
        wm2, wv2 = engine.fold_conv_gemm_weights(
            w2, m2, noise_scale=noise_scale, layout="channel_major")
        counts = _compiled(n_blocks)(
            jnp.asarray(wm1.reshape(n_blocks, g_blk * f1, 27)),
            jnp.asarray(wv1.reshape(n_blocks, g_blk * f1, 27)),
            jnp.asarray(wm2.reshape(n_blocks, g_blk, f2, 9 * f1)),
            jnp.asarray(wv2.reshape(n_blocks, g_blk, f2, 9 * f1)),
            key,
        )
        return np.asarray(counts)[:p] / n_images

    return evaluate


def uniform_study(params, n_images: int = 2000, noise_scale: float = 1.0):
    """Fig. 2(a): accuracy + PDP of each AM deployed uniformly."""
    rows = {}
    acc_exact = eval_accuracy(params, None, n_images)
    rows["exact"] = {
        "accuracy": acc_exact,
        **hwmodel.sequence_cost(interleave.uniform_sequence("exact", N_SLOTS)),
    }
    # All eight uniform deployments scored in one batched device call, under
    # a common noise instance (accuracy differences isolate the AM designs).
    evaluate = make_batched_evaluator(params, n_images, noise_scale)
    seqs = np.stack([interleave.uniform_sequence(v, N_SLOTS) for v in schemes.AM_VARIANTS])
    accs = evaluate(seqs, jax.random.PRNGKey(0))
    for v, seq, acc in zip(schemes.AM_VARIANTS, seqs, accs):
        rows[v] = {"accuracy": float(acc), **hwmodel.sequence_cost(seq)}
    return rows


def accuracy_ranking(uniform_rows: dict) -> list[str]:
    """AM variants ranked by uniform-deployment accuracy (paper's ranking)."""
    ams = [(v, r["accuracy"]) for v, r in uniform_rows.items() if v != "exact"]
    return [v for v, _ in sorted(ams, key=lambda t: -t[1])]


def nsga_study(
    params,
    k: int,
    *,
    ranking: list[str] | None = None,
    alphabet: list[int] | None = None,
    n_images: int = 512,
    pop_size: int = 24,
    generations: int = 15,
    seed: int = 0,
    noise_scale: float = 1.0,
    batched: bool = True,
    position_agnostic: bool | None = None,
    mesh=None,
    initial_genomes=None,
    obs: bool | None = None,
    log=print,
):
    """NSGA-II over 198-slot sequences with a K-variant alphabet.

    Objectives (minimized, paper Sec. III-A): distinct-type area, total PDP,
    accuracy loss (1 - acc) on an inner-loop image subset.

    ``batched=True`` (default) scores each generation's offspring in a single
    blocked-GEMM device call; ``batched=False`` runs the same evaluator one
    genome at a time (one device round trip per genome) for comparison. The
    evaluator's fixed-block padding makes a genome's score independent of
    batch composition, so on a fixed seed both paths produce bit-identical
    Pareto fronts.

    ``position_agnostic`` controls the memo-cache key (see nsga2.optimize):
    the paper treats fitness as a function of the variant *multiset*, which
    holds at calibrated noise (positional accuracy spread is below the
    1/n_images resolution — Fig. 5). At amplified noise the surrogate
    accuracy is measurably positional, so the default (None) keys the cache
    on the multiset when ``noise_scale <= 1`` and on the exact sequence
    otherwise.

    ``mesh`` shards each generation's offspring evaluation over the mesh's
    population axis (see make_batched_evaluator); the memoizing front-end
    and the Pareto machinery are untouched, and the evaluator's bitwise
    shard invariance means the search trajectory — every front, every knee
    — is identical at any device count.

    ``alphabet`` overrides the ranked top-K selection with explicit variant
    ids — the foundry study's path to expanded (K >= 16) alphabets that
    include runtime-registered variants. ``initial_genomes`` warm-starts the
    population (see nsga2.optimize).
    """
    if alphabet is not None:
        alphabet = [int(v) for v in alphabet]
        if len(alphabet) != k:
            raise ValueError(f"alphabet length {len(alphabet)} != k={k}")
    elif ranking is None:
        alphabet = interleave.alphabet_for_k(k)
    else:
        alphabet = [schemes.VARIANT_IDS[v] for v in ranking[:k]]

    if position_agnostic is None:
        position_agnostic = noise_scale <= 1.0
    eval_key = jax.random.PRNGKey(seed + 1000)
    stats = nsga2.EvalStats()
    evaluate = make_batched_evaluator(params, n_images, noise_scale, mesh=mesh)

    if batched:

        def objectives_batch(genomes: np.ndarray) -> np.ndarray:
            accs = evaluate(genomes, eval_key)
            return np.column_stack([hwmodel.objectives_batch(genomes), 1.0 - accs])

        objective_kwargs = dict(objectives_batch=objectives_batch)
    else:

        def objectives(genome: np.ndarray) -> np.ndarray:
            cost = hwmodel.sequence_cost(genome)
            acc = float(evaluate(genome[None], eval_key)[0])
            return np.array([cost["area_um2"], cost["pdp_pj"], 1.0 - acc])

        objective_kwargs = dict(objective_fn=objectives)

    t0 = time.time()
    with _obs_scope(obs), obs_trace.span(
            "study.nsga", k=k, pop=pop_size, generations=generations):
        front = nsga2.optimize(
            genome_len=N_SLOTS,
            alphabet=alphabet,
            pop_size=pop_size,
            generations=generations,
            seed=seed,
            position_agnostic=position_agnostic,
            mesh=mesh,
            initial_genomes=initial_genomes,
            stats=stats,
            log=(lambda s: log(f"  [K={k}] {s}")) if log else None,
            **objective_kwargs,
        )
    seconds = time.time() - t0
    knee = nsga2.knee_point(front)
    return {
        "k": k,
        "alphabet": list(map(int, alphabet)),
        "front": [
            {"objectives": ind.objectives.tolist(), "genome": ind.genome.tolist()}
            for ind in front
        ],
        "knee_genome": knee.genome.tolist(),
        "knee_objectives": knee.objectives.tolist(),
        "evals": stats.genomes_scored,
        "eval_stats": stats.as_dict(),
        "batched": batched,
        # Pipeline throughput: cache hits count as delivered genomes.
        "genomes_per_sec": stats.genomes_requested / seconds if seconds > 0 else 0.0,
        # Evaluator throughput: only genomes actually sent to the device.
        "scored_genomes_per_sec": stats.genomes_scored / seconds if seconds > 0 else 0.0,
        "seconds": seconds,
    }


def displacement_study(
    params,
    seq: np.ndarray,
    *,
    n_perms: int = 10,
    n_images: int = 2000,
    seed: int = 0,
    noise_scale: float = 1.0,
):
    """Fig. 5: random slot permutations of an optimized sequence.

    All permutations are scored in one batched device call under a common
    noise instance (a fresh key, independent of the optimizer's), so the
    accuracy spread isolates the placement effect — exactly the positional
    sensitivity the paper's Fig. 5 probes.
    """
    rng = np.random.default_rng(seed)
    perms = np.stack([
        interleave.random_displacement(np.asarray(seq, np.int32), rng)
        for _ in range(n_perms)
    ])
    evaluate = make_batched_evaluator(params, n_images, noise_scale)
    accs = [float(a) for a in evaluate(perms, jax.random.PRNGKey(7000 + seed))]
    return {"accuracies": accs, "max": max(accs), "mean": float(np.mean(accs))}


def foundry_study(
    params=None,
    *,
    k_target: int = 16,
    family=None,
    n_images: int = 512,
    pop_size: int = 24,
    generations: int = 15,
    seed: int = 0,
    noise_scale: float = 1.0,
    char_n: int = 1 << 15,
    mesh=None,
    out_name: str | None = "foundry_study.json",
    obs: bool | None = None,
    log=print,
):
    """Expanded-alphabet interleaving search over foundry variants.

    1. Runs the baseline NSGA-II search over the full seed alphabet
       (K = 9: exact + the paper's eight AMs).
    2. Synthesizes, characterizes and registers enough foundry variants
       (foundry.default_family) to reach ``k_target`` total variants.
    3. Re-runs the search over the expanded alphabet, warm-started with the
       baseline Pareto front (every baseline genome is a valid expanded-
       alphabet genome, and the evaluator is deterministic per genome under
       common random numbers, so the expanded search can only improve).
    4. Reports two dominance results. ``weakly_dominates_baseline`` is the
       falsifiable claim: the expanded *search's* final front alone weakly
       dominates the K = 9 baseline front (elitism can in principle drop a
       warm-started point under crowding pressure, so this can fail). The
       reported ``front`` is the deduplicated non-dominated archive of the
       search front united with the baseline front — both are valid
       expanded-alphabet solutions, so the archive weakly dominates the
       baseline *by construction* and is reported as the deliverable, not
       as evidence.

    Registrations persist in-process and are made with ``overwrite=True``,
    so re-running the study in one interpreter (seed sweeps, notebooks)
    re-registers the family under stable ids instead of raising on the
    collision; wrap in foundry.temporary_variants() for isolation. Results
    land in ``artifacts/<out_name>``.
    """
    from repro import foundry

    if params is None:
        params = load_params()
    n_seed = len(schemes.SEED_VARIANTS)
    base_alphabet = list(range(n_seed))

    log(f"== baseline search (K={n_seed}, seed alphabet) ==")
    baseline = nsga_study(
        params, len(base_alphabet), alphabet=base_alphabet, n_images=n_images,
        pop_size=pop_size, generations=generations, seed=seed,
        noise_scale=noise_scale, mesh=mesh, obs=obs, log=log,
    )

    n_new = max(k_target - n_seed, 0)
    if family is not None:
        specs = list(family)
        if len(specs) < n_new:
            raise ValueError(f"family has {len(specs)} specs < {n_new} needed")
    else:
        specs = list(foundry.default_family(n_new))[:n_new]
    log(f"== registering {len(specs)} foundry variants (char n={char_n}) ==")
    with _obs_scope(obs), obs_trace.span(
            "study.foundry.register", n=len(specs), char_n=char_n):
        regs = foundry.register_family(specs, n=char_n, seed=seed,
                                       overwrite=True, log=log)

    expanded_alphabet = list(range(len(schemes.VARIANTS)))
    k_expanded = len(expanded_alphabet)
    warm = [np.asarray(ind["genome"], np.int32) for ind in baseline["front"]]
    log(f"== expanded search (K={k_expanded}, warm-started with "
        f"{len(warm)} baseline front genomes) ==")
    expanded = nsga_study(
        params, k_expanded, alphabet=expanded_alphabet, n_images=n_images,
        pop_size=pop_size, generations=generations, seed=seed,
        noise_scale=noise_scale, mesh=mesh, initial_genomes=warm, obs=obs,
        log=log,
    )

    base_objs = np.array([ind["objectives"] for ind in baseline["front"]])
    union, seen = [], set()
    for ind in expanded["front"] + baseline["front"]:
        key = (tuple(ind["objectives"]), tuple(ind["genome"]))
        if key not in seen:
            seen.add(key)
            union.append(ind)
    union_objs = np.array([ind["objectives"] for ind in union])
    keep = nsga2.pareto_filter(union_objs)
    front = [union[i] for i in keep]
    front_objs = union_objs[keep]
    # The falsifiable dominance claim: the search front ALONE. The archive
    # `front` above dominates by construction and is the deliverable only.
    search_dominates = nsga2.front_weakly_dominates(
        np.array([ind["objectives"] for ind in expanded["front"]]), base_objs
    )
    # Strict improvement: expanded-front points no baseline point matches.
    novel = int(np.sum(
        ~(base_objs[:, None, :] <= front_objs[None, :, :]).all(-1).any(0)
    ))

    results = {
        "k_baseline": len(base_alphabet),
        "k_expanded": k_expanded,
        "seed": seed,
        "n_images": n_images,
        "pop_size": pop_size,
        "generations": generations,
        "char_n": char_n,
        "variants": [r.as_dict() for r in regs],
        "baseline": baseline,
        "expanded": expanded,
        "front": front,
        "weakly_dominates_baseline": bool(search_dominates),
        "archive_front_dominates_by_construction": True,
        "novel_front_points": novel,
    }
    log(f"expanded archive front: {len(front)} points; search front weakly "
        f"dominates K=9 front: {search_dominates}; "
        f"{novel} points beyond the baseline front")
    if out_name:
        ARTIFACTS.mkdir(exist_ok=True)
        out = ARTIFACTS / out_name
        out.write_text(json.dumps(results, indent=1))
        log(f"wrote {out}")
    return results


def codesign_study(
    params=None,
    *,
    n_specs: int = 7,
    outer_pop: int = 8,
    outer_generations: int = 3,
    inner_pop: int = 16,
    inner_generations: int = 6,
    n_images: int = 512,
    seed: int = 0,
    noise_scale: float = 1.0,
    char_n: int = 1 << 15,
    char_seed: int = 0,
    mesh=None,
    workers: int = 0,
    n_islands: int = 1,
    migration_interval: int = 2,
    migration_k: int = 1,
    async_window: int = 2,
    baseline_name: str | None = "foundry_study.json",
    out_name: str | None = "codesign_study.json",
    obs: bool | None = None,
    log=print,
):
    """Two-level co-design: search the placement space AND the interleaving.

    Runs repro.codesign.codesign_search over ``n_specs``-placement outer
    genomes, scoring every candidate alphabet by an inner interleaving
    search through the blocked-GEMM population evaluator (optionally
    ``mesh``-sharded, so inner evaluations stay population-batched).

    ``workers >= 1`` switches the outer search to the asynchronous
    island-model work queue (codesign.CodesignConfig.workers): candidate
    evaluations run concurrently under thread-private registry scopes, and
    the archive is identical at any worker count (built by deterministic
    replay of the event log — returned as ``results["replay"]``, kept out
    of the JSON artifact for size). With ``n_islands > 1`` and a ``mesh``,
    each island runs its inner searches on its own round-robin mesh shard
    (parallel.sharding.island_meshes); the per-island evaluators are
    numerically identical per genome (the engine's sharded CRN parity), as
    the shared outer memo requires.

    The PR-4 foundry alphabet (`foundry.default_family()[:n_specs]`) is
    injected as one outer seed candidate (codesign.paper_family_params
    encodes the identical maps), and — when ``baseline_name`` exists and
    its alphabet matches — the committed foundry front warm-starts that
    candidate's inner search with its genomes remapped onto codesign's
    canonical id order. The defaults reproduce the committed foundry run's
    evaluator exactly (n_images=512, noise 1.0, eval key PRNGKey(seed+1000),
    char_n=2^15, char_seed=0), so those warm points re-score to the
    committed objective values and the elite archive covers the baseline
    front by construction; the *falsifiable* claim reported separately is
    ``search_front_weakly_dominates_baseline`` — dominance by the codesign
    search's own discoveries (source != "baseline" imports), which elitism
    or positional aliasing could in principle break.

    Results land in ``artifacts/<out_name>``: the dominance-pruned archive,
    the outer Pareto front over (-hypervolume, library area), per-candidate
    telemetry and the spec-memo / inner-search cache statistics.
    """
    from repro import codesign, foundry

    if params is None:
        params = load_params()
    evaluate = make_batched_evaluator(params, n_images, noise_scale, mesh=mesh)
    eval_key = jax.random.PRNGKey(seed + 1000)

    def accuracy_batch(genomes):
        return evaluate(genomes, eval_key)

    # The foundry seed candidate is a warm-start aid, only encodable for
    # spec counts the deterministic paper family covers; larger placement
    # spaces simply search cold.
    try:
        compat = codesign.encode(codesign.paper_family_params(n_specs))
    except ValueError:
        log(f"n_specs={n_specs} beyond the paper family; searching without "
            "a foundry seed candidate (no warm start, no baseline import)")
        compat = None
    baseline = None
    if compat is not None and baseline_name and (
            ARTIFACTS / baseline_name).exists():
        baseline = json.loads((ARTIFACTS / baseline_name).read_text())

    warm = None
    if baseline is not None:
        base_variant_names = [
            v["name"] for v in baseline.get("variants", [])
        ]
        default_names = [
            s.name for s in foundry.default_family()[:n_specs]
        ]
        if baseline.get("k_expanded") != len(schemes.SEED_VARIANTS) + n_specs:
            # Its genomes use an alphabet of a different size, so they can
            # neither warm-start nor be remapped; the points themselves are
            # still valid committed designs and are imported verbatim below.
            log(f"baseline k_expanded={baseline.get('k_expanded')} does not "
                f"match n_specs={n_specs}; skipping warm start (points "
                "still imported verbatim)")
        elif base_variant_names != default_names:
            # A custom-family baseline (foundry_study(family=...)) uses ids
            # 9.. for specs the compat genome does not encode — remapping
            # its genomes would silently mis-score them. Its points are
            # still valid committed designs, so they are imported verbatim
            # below; only the warm start is skipped.
            log("baseline variants are not default_family(); skipping warm "
                "start (points still imported verbatim)")
        else:
            # Foundry ids 9+i follow default_family order; codesign assigns
            # ids over the same maps in canonical (sorted-map) order — remap.
            canon = codesign.novel_specs(compat)
            canon_id = {
                sp.to_map().tobytes(): len(schemes.SEED_VARIANTS) + j
                for j, sp in enumerate(canon)
            }
            remap = np.arange(len(schemes.SEED_VARIANTS) + n_specs)
            for i, sp in enumerate(foundry.default_family()[:n_specs]):
                remap[len(schemes.SEED_VARIANTS) + i] = canon_id[
                    sp.to_map().tobytes()
                ]
            warm = [
                remap[np.asarray(ind["genome"], np.int32)].astype(np.int32)
                for ind in baseline["front"]
            ]

    cfg = codesign.CodesignConfig(
        n_specs=n_specs, outer_pop=outer_pop,
        outer_generations=outer_generations, inner_pop=inner_pop,
        inner_generations=inner_generations,
        # Multiset memo keys are only sound while positional accuracy
        # spread is below the evaluator's resolution (same guard as
        # nsga_study): amplified noise keys on the exact sequence.
        inner_position_agnostic=noise_scale <= 1.0,
        char_n=char_n, char_seed=char_seed, seed=seed,
        workers=workers, n_islands=n_islands,
        migration_interval=migration_interval, migration_k=migration_k,
        async_window=async_window,
    )
    island_kwargs = {}
    if workers >= 1 and n_islands > 1 and mesh is not None:
        from repro.parallel import sharding

        submeshes = sharding.island_meshes(mesh, n_islands)
        island_evals = [
            make_batched_evaluator(params, n_images, noise_scale, mesh=m)
            for m in submeshes
        ]
        island_kwargs = {
            "island_accuracy_batch": [
                (lambda g, ev=ev: ev(g, eval_key)) for ev in island_evals
            ],
            "island_meshes": submeshes,
        }
    log(f"== codesign search (outer {outer_pop}x{outer_generations}, inner "
        f"{inner_pop}x{inner_generations}, n_images={n_images}"
        + (f", async workers={workers} islands={n_islands}"
           if workers >= 1 else "") + ") ==")
    with _obs_scope(obs), obs_trace.span(
            "study.codesign", outer_pop=outer_pop,
            outer_generations=outer_generations, workers=workers):
        res = codesign.codesign_search(
            accuracy_batch, genome_len=N_SLOTS, cfg=cfg,
            seed_candidates=[(compat, warm)] if compat is not None else (),
            mesh=mesh, log=log, **island_kwargs,
        )
    archive = res["archive"]

    search_dominates = None
    dominates = None
    if baseline is not None:
        base_objs = np.array([ind["objectives"] for ind in baseline["front"]])
        # Falsifiable: the search's OWN discoveries alone — warm re-scores
        # (which under the default settings reproduce the committed values
        # exactly, making them dominant by construction) and imported
        # baseline points are both excluded.
        search_objs = np.array([
            list(p.objectives) for p in archive.points
            if p.source == "search"
        ])
        search_dominates = nsga2.front_weakly_dominates(
            search_objs, base_objs
        )
        # Deliverable: the archive united with the committed baseline points
        # (each is a valid K=16 co-design) — dominant by construction.
        for ind in baseline["front"]:
            archive.insert(codesign.ArchivePoint(
                objectives=tuple(map(float, ind["objectives"])),
                genome=tuple(map(int, ind["genome"])),
                alphabet_key="foundry_baseline",
                source="baseline",
            ))
        archive.add_alphabet("foundry_baseline", {
            "spec_names": base_variant_names,
            "source": baseline_name,
        })
        dominates = nsga2.front_weakly_dominates(
            archive.front_objectives(), base_objs
        )
        log(f"archive front: {len(archive)} points; weakly dominates "
            f"foundry K={baseline['k_expanded']} front: {dominates} "
            f"(search-only: {search_dominates})")

    # The archive's canonical (objective-sorted) point list is reported once
    # as "front"; "archive" keeps the alphabet side table + telemetry.
    arch_dict = archive.as_dict()
    front_points = arch_dict.pop("points")
    results = {
        "n_specs": n_specs,
        "n_images": n_images,
        "seed": seed,
        "noise_scale": noise_scale,
        "config": res["config"],
        "reference_point": res["reference_point"],
        "outer_front": res["outer_front"],
        "archive": arch_dict,
        "front": front_points,
        "stats": res["stats"],
        "baseline": baseline_name if baseline is not None else None,
        "weakly_dominates_foundry_front": dominates,
        "search_front_weakly_dominates_baseline": search_dominates,
    }
    if "async" in res:
        results["async"] = res["async"]  # per-island EvalStats telemetry
    if out_name:
        ARTIFACTS.mkdir(exist_ok=True)
        out = ARTIFACTS / out_name
        out.write_text(json.dumps(results, indent=1))
        log(f"wrote {out}")
    if "replay" in res:
        # Returned for parity checks; deliberately not serialized (the event
        # log carries every inner front and dwarfs the artifact).
        results["replay"] = res["replay"]
    return results


def run_all(
    *,
    ks=(2, 3, 4, 5, 8),
    n_images_rank: int = 2000,
    n_images_inner: int = 512,
    pop_size: int = 24,
    generations: int = 15,
    noise_scale: float = 1.0,
    out_name: str = "paper_cnn_results.json",
    obs: bool | None = None,
    log=print,
):
    """Full paper Sec. III pipeline; writes artifacts/<out_name>."""
    params = load_params()
    log("== uniform study (Fig 2a) ==")
    uni = uniform_study(params, n_images_rank, noise_scale=noise_scale)
    ranking = accuracy_ranking(uni)
    for v in ["exact"] + ranking:
        r = uni[v]
        log(f"  {v:8s} acc={r['accuracy']:.4f} pdp={r['pdp_pj']:.1f}pJ "
            f"benefit={r['pdp_benefit_pct']:.2f}%")

    results = {"uniform": uni, "ranking": ranking, "noise_scale": noise_scale,
               "nsga": {}, "displacement": {}}
    for k in ks:
        log(f"== NSGA-II K={k} ==")
        res = nsga_study(
            params, k, ranking=ranking, n_images=n_images_inner,
            pop_size=pop_size, generations=generations, noise_scale=noise_scale,
            obs=obs, log=log,
        )
        results["nsga"][str(k)] = res
        log(f"== displacement K={k} ==")
        disp = displacement_study(
            params, np.asarray(res["knee_genome"], np.int32),
            n_images=n_images_rank, noise_scale=noise_scale,
        )
        results["displacement"][str(k)] = disp
        log(f"  K={k} knee acc={1 - res['knee_objectives'][2]:.4f} "
            f"displaced max={disp['max']:.4f} mean={disp['mean']:.4f}")

    ARTIFACTS.mkdir(exist_ok=True)
    out = ARTIFACTS / out_name
    out.write_text(json.dumps(results, indent=1))
    log(f"wrote {out}")
    return results
