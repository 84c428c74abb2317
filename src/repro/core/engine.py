"""Unified AM numerics engine: one backend-dispatched matmul/conv2d API.

Every consumer of the paper's interleaved approximate-FP32 numerics — the
CNN model, the NSGA-II population evaluator, the LM-scale projections, the
serving loop and the benchmarks — routes through two primitives:

    am_matmul(x, w, slot_map, *, backend=..., key=...)
    am_conv2d(x, w, slot_map, *, backend=..., key=...)

`slot_map` is anything the canonicalizer understands (None, a policy string,
a flat variant sequence, a tile grid, a full per-slot map — each optionally
with a leading **population axis** (P, ...) of genomes), and `backend` picks
the fidelity/cost point:

  backend           fidelity                 intended use
  ----------------  -----------------------  --------------------------------
  exact             reference f32            baselines; slot_map ignored
  bitexact_ref      bit-level AM emulation   ground truth, final scoring
                    (pure jnp oracle)        (small shapes: ~10^2 ops/multiply)
  bitexact_pallas   bit-level AM emulation   on-device validation at CNN scale
                    (Pallas kernel)          (interpret-mode off TPU)
  surrogate_xla     calibrated moments,      general AM inference; moment maps
                    plain XLA matmul/conv    materialized per call
  surrogate_fused   calibrated moments,      NSGA-II search + LM-scale shapes;
                    fused one-pass kernel    population-vectorized, blocked
                                             channel-major GEMM on CPU, fused
                                             Pallas kernel on TPU

`backend=None` auto-selects: exact when there is no (non-trivial) slot map,
bit-exact for small shapes (final scoring), fused surrogate otherwise.

Population axis: a slot_map of shape (P, ...) scores P genomes in one call
(the NSGA-II generation batch, Pareto re-scoring, displacement studies);
outputs gain a leading P axis. Surrogate noise uses common random numbers —
one z per output position, shared across the population — so genome
comparisons are made under the same noise realization and a population call
matches the corresponding per-genome calls. `x` may also carry the
population axis (layer 2 of a population-evaluated CNN).

Population sharding: an AMEngine constructed with ``mesh=`` (a 1-D device
mesh whose axis is named ``pop_axis_name``, see
parallel/sharding.py::make_pop_mesh) splits the population axis of the
surrogate_xla / surrogate_fused backends across devices under shard_map.
The population is first padded to a multiple of the mesh axis
(pad_population), each shard evaluates its contiguous slice with exactly
the per-genome op sequence of the single-device path, and the CRN noise
invariant makes results independent of the shard count AND the shard
index: z is a function of the *global* call key and the single-genome
output shape only — never of the population index or the shard-local
index — so every shard reconstructs the identical noise realization from
the replicated key. Sharded outputs are bitwise identical to the
single-device population call (asserted in tests/test_engine_sharded.py).

The canonicalization (sequence -> per-slot variant ids -> moment/scheme
maps) is shared by all backends, lifted from core/interleave.py +
core/schemes.py; the VMEM-aware block-size chooser shared by the Pallas
backends lives in kernels/ops.py (`choose_block`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import interleave, schemes, surrogate
from repro.obs import metrics as obs_metrics
from repro.obs import numerics as obs_numerics
from repro.obs import trace as obs_trace
from repro.obs.config import enabled as _obs_enabled

BACKEND_NAMES = (
    "exact",
    "bitexact_ref",
    "bitexact_pallas",
    "surrogate_xla",
    "surrogate_fused",
)

# Auto-selector threshold: emulated multiplies per bit-exact pass we are
# willing to pay for ground-truth numerics (~10^2 integer ops per multiply).
BITEXACT_AUTO_MAX_MULS = 1 << 14

_REGISTERED_SEQUENCES: dict[str, np.ndarray] = {}


def register_sequence(name: str, variant_ids, *, overwrite: bool = False) -> None:
    """Register an optimized flat variant sequence under policy `seq:<name>`.

    Collisions raise unless ``overwrite=True`` (same contract as the variant
    registry in core/schemes.py) — a silent overwrite would reroute every
    consumer already holding the `seq:<name>` policy string.
    """
    if name in _REGISTERED_SEQUENCES and not overwrite:
        raise ValueError(
            f"sequence {name!r} already registered; pass overwrite=True to "
            "replace it"
        )
    _REGISTERED_SEQUENCES[name] = np.asarray(variant_ids, np.int32)


def list_sequences() -> tuple[str, ...]:
    """Names of registered `seq:<name>` policies, in registration order."""
    return tuple(_REGISTERED_SEQUENCES)


# ---------------------------------------------------------------------------
# Per-request tier routing (the serving path)
# ---------------------------------------------------------------------------
#
# A tier set is an ordered tuple of slot-map policies (None = exact); policy
# string `tiers:<name>` routes each batch ROW of a matmul through its own
# tier's moment map inside one dispatch — the serving tier's accuracy/energy
# SLO knob (exact for premium traffic, aggressive interleaves for bulk).
# The per-row tier indices and request-local positions are ambient state
# bound by `row_tier_context` around the consumer's decode call: they are
# traced (B,) vectors, so slot/tier assignment never retraces the step.

_TIER_SETS: dict[str, tuple[str | None, ...]] = {}


def register_tier_set(name: str, policies, *, overwrite: bool = False) -> None:
    """Register an ordered tier set under policy `tiers:<name>`.

    `policies` is a sequence of per-tier slot-map policy strings (or None
    for an exact tier: zero moments, zero variance — exact traffic rides
    the same batched dispatch). Re-registering identical content is a
    no-op; changing content requires overwrite=True (same contract as
    register_sequence: a silent reroute would change every consumer
    holding the `tiers:<name>` policy string).
    """
    policies = tuple(policies)
    for p in policies:
        if p is not None and not isinstance(p, str):
            raise ValueError(f"tier policy must be a policy string or None, got {p!r}")
        if isinstance(p, str) and p.startswith("tiers:"):
            raise ValueError("tier sets cannot nest other tier sets")
    if name in _TIER_SETS and _TIER_SETS[name] != policies and not overwrite:
        raise ValueError(
            f"tier set {name!r} already registered with different policies; "
            "pass overwrite=True to replace it")
    _TIER_SETS[name] = policies


def tier_set(name: str) -> tuple[str | None, ...]:
    try:
        return _TIER_SETS[name]
    except KeyError:
        raise ValueError(
            f"unknown tier set {name!r}; have {sorted(_TIER_SETS)}") from None


def list_tier_sets() -> tuple[str, ...]:
    return tuple(_TIER_SETS)


class _RowTierState(threading.local):
    def __init__(self):
        self.stack: list[tuple[Any, Any]] = []


_ROW_TIERS = _RowTierState()


@contextlib.contextmanager
def row_tier_context(tiers, pos):
    """Bind per-row tier indices + request-local positions for `tiers:<name>`
    policies. `tiers`/`pos`: (B,) int32, one entry per batch row; traced
    values are the normal case — the context is read at trace time inside
    the consumer's jitted step. Thread-local (the async co-design workers
    trace concurrently)."""
    _ROW_TIERS.stack.append((tiers, pos))
    try:
        yield
    finally:
        _ROW_TIERS.stack.pop()


def _current_row_tiers():
    if not _ROW_TIERS.stack:
        raise ValueError(
            "policy 'tiers:<name>' needs an active engine.row_tier_context "
            "binding per-row tier indices and request-local positions")
    return _ROW_TIERS.stack[-1]


# ---------------------------------------------------------------------------
# Slot-map canonicalization (shared by every backend)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=4096)
def _static_policy_sequence(policy: str, n: int) -> np.ndarray:
    if policy.startswith("uniform:"):
        return interleave.uniform_sequence(policy.split(":", 1)[1], n)
    if policy.startswith("rr:"):
        k = int(policy.split(":", 1)[1])
        alpha = np.asarray(interleave.alphabet_for_k(k), np.int32)
        return alpha[np.arange(n) % k]
    raise ValueError(f"unknown numerics policy {policy!r}")


def _policy_sequence(policy: str, n: int) -> np.ndarray:
    """Deterministic flat variant-id sequence of length n for a policy string.

    `seq:<name>` policies resolve against the runtime registry (uncached so
    re-registering a name takes effect); uniform/rr policies are cached.
    """
    if policy.startswith("seq:"):
        seq = _REGISTERED_SEQUENCES[policy.split(":", 1)[1]]
        if seq.size < n:  # tile the registered sequence to cover the grid
            seq = np.resize(seq, n)
        return seq[:n].copy()
    if _obs_enabled():
        before = _static_policy_sequence.cache_info().hits
        out = _static_policy_sequence(policy, n)
        hit = _static_policy_sequence.cache_info().hits > before
        obs_metrics.counter_inc("engine.policy_cache",
                                result="hit" if hit else "miss")
        return out
    return _static_policy_sequence(policy, n)


@dataclasses.dataclass(frozen=True)
class CanonicalMap:
    """Per-slot variant ids in the shape a backend consumes.

    vids: (K, N) for matmul / (F, kh, kw) for conv, with a leading P axis
    when `pop` is set. Always int32, always a concrete np.ndarray, so jitted
    consumers can fold maps into weights on the host.
    """

    vids: np.ndarray
    pop: bool

    @property
    def population(self) -> int:
        return self.vids.shape[0] if self.pop else 1

    def per_genome(self):
        """Iterate single-genome maps (pop=False each)."""
        if not self.pop:
            yield self
        else:
            for p in range(self.vids.shape[0]):
                yield CanonicalMap(self.vids[p], False)


def canonical_matmul_map(
    slot_map, k: int, n: int, *, tile_k: int = 128, tile_n: int = 128
) -> CanonicalMap:
    """Canonicalize any matmul slot-map spelling to per-(K, N) variant ids.

    Accepted: None (exact), a policy string, a full (K, N) map, a (gk, gn)
    tile grid, a flat gk*gn sequence — each with an optional leading
    population axis. A 2-D array matching (K, N) or (gk, gn) is read as a
    single map; use an explicit 3-D (P, gk, gn) for populations that would
    collide with those shapes.
    """
    gk, gn = -(-k // tile_k), -(-n // tile_n)
    if slot_map is None:
        return CanonicalMap(np.zeros((k, n), np.int32), False)
    if isinstance(slot_map, str):
        slot_map = _policy_sequence(slot_map, gk * gn)
    arr = np.asarray(slot_map, np.int32)

    def expand(a: np.ndarray) -> np.ndarray:
        if a.ndim == 1:
            if a.size != gk * gn:
                raise ValueError(
                    f"flat matmul sequence length {a.size} != tile grid {gk}x{gn}"
                )
            a = a.reshape(gk, gn)
        if a.shape == (k, n):
            return a
        if a.shape == (gk, gn):
            return np.repeat(np.repeat(a, tile_k, 0), tile_n, 1)[:k, :n]
        raise ValueError(
            f"matmul slot map shape {a.shape} matches neither full ({k}, {n}) "
            f"nor tile grid ({gk}, {gn})"
        )

    single = arr.ndim == 1 or (
        arr.ndim == 2 and (arr.shape == (k, n) or arr.shape == (gk, gn))
    )
    if single:
        return CanonicalMap(expand(arr), False)
    return CanonicalMap(np.stack([expand(a) for a in arr]), True)


def canonical_conv_map(slot_map, f: int, kh: int, kw: int) -> CanonicalMap:
    """Canonicalize any conv slot-map spelling to per-(F, kh, kw) variant ids.

    Accepted: None (exact), a policy string, a (F, kh, kw) map, a flat
    F*kh*kw sequence — each with an optional leading population axis.
    """
    n = f * kh * kw
    if slot_map is None:
        return CanonicalMap(np.zeros((f, kh, kw), np.int32), False)
    if isinstance(slot_map, str):
        slot_map = _policy_sequence(slot_map, n)
    arr = np.asarray(slot_map, np.int32)
    if arr.ndim == 1:
        if arr.size != n:
            raise ValueError(f"flat conv sequence length {arr.size} != {n} slots")
        return CanonicalMap(arr.reshape(f, kh, kw), False)
    if arr.shape == (f, kh, kw):
        return CanonicalMap(arr, False)
    if arr.ndim == 2 and arr.shape[1] == n:
        return CanonicalMap(arr.reshape(-1, f, kh, kw), True)
    if arr.ndim == 4 and arr.shape[1:] == (f, kh, kw):
        return CanonicalMap(arr, True)
    raise ValueError(
        f"conv slot map shape {arr.shape} does not fit (F,kh,kw)=({f},{kh},{kw})"
    )


def scheme_stack() -> np.ndarray:
    """(n_variants, 3, 48) compressor-code stack shared by bit-exact backends."""
    return schemes.scheme_stack()


def moment_maps(vids: np.ndarray, noise_scale: float = 1.0):
    """Gather per-slot (mu, sigma) moment maps for canonical variant ids."""
    mu_t, sg_t = surrogate.moment_tables()
    mu_t = (mu_t * noise_scale).astype(np.float32)
    sg_t = (sg_t * noise_scale).astype(np.float32)
    return mu_t[vids], sg_t[vids]


# --- conv GEMM weight folding (the search/population hot path) -------------
#
# The fused surrogate conv backend computes each conv as an im2col GEMM with
# the per-slot moments folded into per-genome weight matrices on the host —
# the channel-major (F, K) @ (K, pixels) orientation that is fastest on this
# 2-core box, and the formulation the population evaluator compiles once per
# shape. Two column layouts exist because image patches are cheapest to
# build tap-major while pooled-activation patches (layer 2 of the paper CNN)
# are cheapest channel-major.


def fold_conv_gemm_weights(
    w, maps: CanonicalMap, *, noise_scale: float = 1.0, layout: str = "tap_major"
):
    """Fold per-slot moments into (P?, F, kh*kw*Cin) mean/var GEMM weights.

    w: (F, kh, kw, Cin). Column order matches the corresponding patch
    layout: "tap_major" — (tap, channel) with channel fastest;
    "channel_major" — (channel, tap) with tap fastest.
    Returns (w_mean, w_var) float32 arrays, population axis iff maps.pop.
    Host (np) weights fold on the host — bitwise-stable, the population
    evaluator's contract; traced weights (w as a jit argument) fold in-graph.
    """
    traced = isinstance(w, jax.core.Tracer)
    t0 = time.perf_counter() if _obs_enabled() and not traced else None
    if traced:
        w = w.astype(jnp.float32)
    else:
        w = np.asarray(w, np.float32)
    f, kh, kw, cin = w.shape
    vids = maps.vids if maps.pop else maps.vids[None]
    taps = vids.reshape(vids.shape[0], f, kh * kw)
    mu, sg = moment_maps(taps, noise_scale)
    if layout == "tap_major":
        wf = w.reshape(f, kh * kw * cin)
        mu_c = np.repeat(mu, cin, axis=2)
        sg_c = np.repeat(sg, cin, axis=2)
    elif layout == "channel_major":
        wf = w.transpose(0, 3, 1, 2).reshape(f, cin * kh * kw)
        mu_c = np.tile(mu, (1, 1, cin))
        sg_c = np.tile(sg, (1, 1, cin))
    else:
        raise ValueError(f"unknown layout {layout!r}")
    wm = wf[None] * (1.0 + mu_c)
    wv = (wf * wf)[None] * (sg_c * sg_c)
    if not maps.pop:
        wm, wv = wm[0], wv[0]
    if t0 is not None:  # host folds only: in-graph folds time as compilation
        obs_metrics.observe("engine.fold_seconds", time.perf_counter() - t0,
                            op="conv")
    return wm.astype(np.float32), wv.astype(np.float32)


def fold_matmul_weights(w, maps: CanonicalMap, *, noise_scale: float = 1.0):
    """Fold per-slot moments into (P?, K, N) mean/var matmul weights.

    Exactly the weight transforms of surrogate_xla's `_moment_matmul` —
    ``w * (1 + mu)`` and ``(w * w) * (sg * sg)``, elementwise f32 — so the
    folded path is bitwise identical to the per-call transform (elementwise
    IEEE ops do not depend on host-vs-device spelling). Host (np) weights
    fold on the host — once per engine call, not per jit invocation; traced
    weights (w as a jit argument) fold in-graph.
    """
    traced = isinstance(w, jax.core.Tracer)
    t0 = time.perf_counter() if _obs_enabled() and not traced else None
    vids = maps.vids if maps.pop else maps.vids[None]
    mu, sg = moment_maps(vids, noise_scale)  # np f32 (P, K, N)
    if traced:
        wf = w.astype(jnp.float32)
        wm = wf[None] * (1.0 + jnp.asarray(mu))
        wv = (wf * wf)[None] * jnp.asarray(sg * sg)
    else:
        wf = np.asarray(w, np.float32)
        wm = (wf[None] * (1.0 + mu)).astype(np.float32)
        wv = ((wf * wf)[None] * (sg * sg)).astype(np.float32)
    if not maps.pop:
        wm, wv = wm[0], wv[0]
    if t0 is not None:  # host folds only: in-graph folds time as compilation
        obs_metrics.observe("engine.fold_seconds", time.perf_counter() - t0,
                            op="matmul")
    return wm, wv


def conv_patch_matrix(x: np.ndarray, kh: int, kw: int) -> np.ndarray:
    """Tap-major im2col of images: (B, H, W, C) -> (kh*kw*C, B, ho*wo).

    Row order matches fold_conv_gemm_weights(layout="tap_major"): taps scan
    (ky, kx) row-major with the channel fastest.
    """
    b, h, wd, c = x.shape
    ho, wo = h - kh + 1, wd - kw + 1
    taps = [
        x[:, i : i + ho, j : j + wo, :] for i in range(kh) for j in range(kw)
    ]  # kh*kw x (B, ho, wo, C)
    px = np.stack(taps, 0).transpose(0, 4, 1, 2, 3)  # (taps, C, B, ho, wo)
    return px.reshape(kh * kw * c, b, ho * wo)


def population_blocks(p: int, block: int) -> int:
    """Number of `block`-genome blocks for a population of p, padded to a
    power of two so per-block GEMM shapes are fixed: a genome's score is
    bitwise identical whether evaluated alone or inside any batch, and
    compilation cost is O(log P) distinct shapes."""
    return 1 << (max(1, -(-p // block)) - 1).bit_length()


def pad_population(arr: np.ndarray, block: int) -> np.ndarray:
    """Pad genomes (P, ...) to population_blocks(P) * block rows with copies
    of row 0 (padded scores are discarded by the caller)."""
    p = arr.shape[0]
    p_pad = population_blocks(p, block) * block
    if p_pad == p:
        return arr
    return np.concatenate([arr, np.repeat(arr[:1], p_pad - p, axis=0)])


def _pad_population_jax(x, p_pad: int):
    """jnp analogue of pad_population for device arrays (population-x)."""
    p = x.shape[0]
    if p_pad == p:
        return x
    return jnp.concatenate(
        [x, jnp.broadcast_to(x[:1], (p_pad - p,) + tuple(x.shape[1:]))]
    )


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    fidelity: str  # "exact" | "bit" | "moments"
    matmul: Callable
    conv2d: Callable


_BACKENDS: dict[str, BackendSpec] = {}


def register_backend(name: str, fidelity: str, *, matmul: Callable, conv2d: Callable):
    _BACKENDS[name] = BackendSpec(name, fidelity, matmul, conv2d)


def get_backend(name: str) -> BackendSpec:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown AM backend {name!r}; have {sorted(_BACKENDS)}")


def backends() -> tuple[str, ...]:
    return tuple(_BACKENDS)


def select_backend(kind: str, *, has_map: bool, work: int) -> str:
    """Automatic backend choice: bit-exact ground truth for small shapes
    (final scoring, validation); the fused surrogate for search- and
    LM-scale work. `work` is scalar multiplies for the whole call,
    including the population axis."""
    del kind
    if not has_map:
        return "exact"
    if work <= BITEXACT_AUTO_MAX_MULS:
        return "bitexact_ref"
    return "surrogate_fused"


# ---------------------------------------------------------------------------
# Shared evaluation plumbing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Ctx:
    """Per-call context handed to backend implementations."""

    engine: "AMEngine"
    block: Any
    return_moments: bool
    base_ndim: int  # rank of a single-genome x (2 matmul, 4 conv)
    pop_x: bool  # x carries a leading population axis

    @property
    def noise_scale(self) -> float:
        return self.engine.noise_scale


def _require_key(key, backend: str):
    if key is None:
        raise ValueError(f"backend {backend!r} draws noise and needs a PRNG key")


def _noise(key, mean, var):
    # crn_normal folds z to a trace-time constant when the key is concrete
    # (the serving / benchmark configuration, where the engine call is traced
    # inside a consumer's jit with a fixed key) — the draw itself costs more
    # than the GEMM pair at search shapes on the build box.
    z = surrogate.crn_normal(key, mean.shape, mean.dtype)
    return mean + z * jnp.sqrt(jnp.maximum(var, 0.0))


def _map_pop(ctx: _Ctx, cmap: CanonicalMap, fn, x):
    """Apply fn(x_slice, single_map) over the population axis, stacking.

    This per-genome path is the ground truth the vectorized fused backend
    is tested against; bit-exact and plain-XLA surrogate backends take it
    directly (population sizes there are small by construction).
    """
    if not cmap.pop:
        return fn(x, cmap)
    outs = [fn(x[p] if ctx.pop_x else x, m) for p, m in enumerate(cmap.per_genome())]
    if ctx.return_moments:
        means, vars_ = zip(*outs)
        return jnp.stack(means), jnp.stack(vars_)
    return jnp.stack(outs)


def _broadcast_pop(ctx: _Ctx, cmap: CanonicalMap, out):
    """Give map-ignoring backends (exact) the population axis the API promises."""
    if not cmap.pop or ctx.pop_x:
        return out
    if ctx.return_moments:
        mean, var = out
        shape = (cmap.population,)
        return (jnp.broadcast_to(mean[None], shape + mean.shape),
                jnp.broadcast_to(var[None], shape + var.shape))
    return jnp.broadcast_to(out[None], (cmap.population,) + out.shape)


def _moment_matmul(x, w, mu, sg):
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    mean = xf @ (wf * (1.0 + mu))
    var = (xf * xf) @ ((wf * wf) * (sg * sg))
    return mean, var


# ---------------------------------------------------------------------------
# Backend implementations
# ---------------------------------------------------------------------------


def _exact_matmul(ctx, x, w, cmap, key):
    del key
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)  # batches over pop-x
    if ctx.return_moments:
        y = (y, jnp.zeros_like(y))
    return _broadcast_pop(ctx, cmap, y)


def _exact_conv2d(ctx, x, w, cmap, key):
    from repro.kernels import ref

    del key
    if ctx.pop_x:
        p = x.shape[0]
        y = ref.conv2d_exact_ref(x.reshape((-1,) + x.shape[2:]), w)
        y = y.reshape((p, -1) + y.shape[1:])
    else:
        y = ref.conv2d_exact_ref(x, w)
    if ctx.return_moments:
        y = (y, jnp.zeros_like(y))
    return _broadcast_pop(ctx, cmap, y)


def _with_moments(ctx, y):
    """Deterministic backends have a point distribution: mean = y, var = 0,
    keeping the return_moments contract total across all backends."""
    return (y, jnp.zeros_like(y)) if ctx.return_moments else y


def _bitexact_matmul_ref(ctx, x, w, cmap, key):
    from repro.kernels import ref

    del key
    return _map_pop(
        ctx, cmap,
        lambda xs, m: _with_moments(ctx, ref.am_matmul_bitexact_ref(xs, w, m.vids)),
        x,
    )


def _bitexact_matmul_pallas(ctx, x, w, cmap, key):
    from repro.kernels import ops

    del key
    return _map_pop(
        ctx, cmap,
        lambda xs, m: _with_moments(
            ctx, ops.am_matmul_bitexact(xs, w, m.vids, block=ctx.block)),
        x,
    )


def _bitexact_conv2d_ref(ctx, x, w, cmap, key):
    from repro.kernels import ref

    del key
    return _map_pop(
        ctx, cmap,
        lambda xs, m: _with_moments(ctx, ref.am_conv2d_bitexact_ref(xs, w, m.vids)),
        x,
    )


def _bitexact_conv2d_pallas(ctx, x, w, cmap, key):
    from repro.kernels import ops

    del key
    return _map_pop(
        ctx, cmap,
        lambda xs, m: _with_moments(ctx, ops.am_conv2d_bitexact(xs, w, m.vids)),
        x,
    )


def _surrogate_matmul_xla(ctx, x, w, cmap, key):
    _require_key(key, "surrogate_xla")

    def one(xs, m):
        mu, sg = moment_maps(m.vids, ctx.noise_scale)
        mean, var = _moment_matmul(xs, w, jnp.asarray(mu), jnp.asarray(sg))
        if ctx.return_moments:
            return mean, var
        return _noise(key, mean, var)  # same key across genomes: CRN

    return _map_pop(ctx, cmap, one, x)


def _surrogate_matmul_fused(ctx, x, w, cmap, key):
    """Vectorized surrogate matmul: moments folded into (P?, K, N) weights
    once per call, both contractions + the CRN noise epilogue dispatched as
    one kernel op (kernels/ops.py::am_surrogate_matmul_epilogue — a single
    Pallas launch on TPU, the stacked batched GEMM spelling elsewhere).
    Bitwise identical to surrogate_xla's per-genome op sequence under CRN:
    the folded transforms, the per-output-element dot order, and the z
    realization (one z per output position, shared across the population)
    are all unchanged."""
    from repro.kernels import ops

    _require_key(key, "surrogate_fused")
    wm, wv = fold_matmul_weights(w, cmap, noise_scale=ctx.noise_scale)
    wm_j, wv_j = jnp.asarray(wm), jnp.asarray(wv)
    xf = x.astype(jnp.float32)
    if ctx.return_moments:
        if not cmap.pop:
            return ops.am_surrogate_moments_folded(
                xf, wm_j, wv_j, block=ctx.block)
        if ctx.pop_x:
            mean = jnp.einsum("pmk,pkn->pmn", xf, wm_j)
            var = jnp.einsum("pmk,pkn->pmn", xf * xf, wv_j)
        else:
            mean = jnp.einsum("mk,pkn->pmn", xf, wm_j)
            var = jnp.einsum("mk,pkn->pmn", xf * xf, wv_j)
        return mean, var
    # CRN: z is drawn for the single-genome (M, N) output and shared across
    # the population axis inside the epilogue op.
    z = surrogate.crn_normal(key, (xf.shape[-2], wm_j.shape[-1]), jnp.float32)
    return ops.am_surrogate_matmul_epilogue(xf, wm_j, wv_j, z, block=ctx.block)


def _surrogate_conv2d_xla(ctx, x, w, cmap, key):
    from repro.kernels import ref

    _require_key(key, "surrogate_xla")

    def one(xs, m):
        mu, sg = moment_maps(m.vids, ctx.noise_scale)  # (F, kh, kw)
        w_mu = w * (1.0 + jnp.asarray(mu)[..., None])
        w_sg2 = (w * w) * (jnp.asarray(sg) ** 2)[..., None]
        mean = ref.conv2d_exact_ref(xs, w_mu)
        var = ref.conv2d_exact_ref(xs * xs, w_sg2)
        if ctx.return_moments:
            return mean, var
        return _noise(key, mean, var)

    return _map_pop(ctx, cmap, one, x)


def _fused_conv_patches(xs, kh: int, kw: int):
    """Tap-major im2col on device: (B, H, W, C) -> ((K, B*ho*wo), dims).

    jnp twin of conv_patch_matrix, shared by the fused conv backend and the
    population-sharded conv path (identical op sequence keeps them bitwise
    interchangeable)."""
    b, h, wd, c = xs.shape
    ho, wo = h - kh + 1, wd - kw + 1
    cols = [
        xs[:, i : i + ho, j : j + wo, :] for i in range(kh) for j in range(kw)
    ]
    pat = jnp.transpose(jnp.stack(cols, 0), (0, 4, 1, 2, 3))
    return pat.reshape(kh * kw * c, -1), (b, ho, wo)


def _surrogate_conv2d_fused(ctx, x, w, cmap, key):
    """Population-vectorized surrogate conv: im2col GEMMs with moments folded
    into per-genome channel-major weights; one z per output position shared
    across the population (common random numbers)."""
    _require_key(key, "surrogate_fused")
    f, kh, kw, cin = np.shape(w)
    wm, wv = fold_conv_gemm_weights(w, cmap, noise_scale=ctx.noise_scale,
                                    layout="tap_major")
    wm_j, wv_j = jnp.asarray(wm), jnp.asarray(wv)  # (P?, F, K)

    def patches(xs):
        return _fused_conv_patches(xs, kh, kw)

    if not cmap.pop:
        pat, (b, ho, wo) = patches(x)
        mean, var = wm_j @ pat, wv_j @ (pat * pat)
    elif not ctx.pop_x:
        pat, (b, ho, wo) = patches(x)
        mean = jnp.einsum("pfk,km->pfm", wm_j, pat)
        var = jnp.einsum("pfk,km->pfm", wv_j, pat * pat)
    else:
        pats = jax.vmap(lambda xs: patches(xs)[0])(x)
        b, ho, wo = x.shape[1], x.shape[2] - kh + 1, x.shape[3] - kw + 1
        mean = jnp.einsum("pfk,pkm->pfm", wm_j, pats)
        var = jnp.einsum("pfk,pkm->pfm", wv_j, pats * pats)

    def unflatten(t):  # (..., F, B*ho*wo) -> (..., B, ho, wo, F)
        t = t.reshape(t.shape[:-1] + (b, ho, wo))
        return jnp.moveaxis(t, -4, -1)

    mean, var = unflatten(mean), unflatten(var)
    if ctx.return_moments:
        return mean, var
    # CRN: z is drawn WITHOUT the population axis and broadcast over it.
    z_shape = mean.shape[1:] if cmap.pop else mean.shape
    z = surrogate.crn_normal(key, z_shape, mean.dtype)
    return mean + z * jnp.sqrt(jnp.maximum(var, 0.0))


register_backend("exact", "exact", matmul=_exact_matmul, conv2d=_exact_conv2d)
register_backend("bitexact_ref", "bit", matmul=_bitexact_matmul_ref,
                 conv2d=_bitexact_conv2d_ref)
register_backend("bitexact_pallas", "bit", matmul=_bitexact_matmul_pallas,
                 conv2d=_bitexact_conv2d_pallas)
register_backend("surrogate_xla", "moments", matmul=_surrogate_matmul_xla,
                 conv2d=_surrogate_conv2d_xla)
register_backend("surrogate_fused", "moments", matmul=_surrogate_matmul_fused,
                 conv2d=_surrogate_conv2d_fused)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AMEngine:
    """Configured entry point to the backend registry.

    The module-level am_matmul/am_conv2d use DEFAULT_ENGINE; consumers with
    their own defaults (models, serving) hold an AMEngine instance.

    ``mesh`` (with ``pop_axis_name`` naming its single axis) switches
    population-axis surrogate calls onto the sharded path: genomes are
    padded to a multiple of the mesh axis, each device scores a contiguous
    population slice, and the CRN noise — keyed by the global call key and
    the single-genome output shape, never by shard or population index —
    makes the result bitwise identical to the single-device call.
    Non-population calls and the exact/bit-exact backends ignore the mesh.
    """

    backend: str | None = None  # None = auto-select per call
    tile_k: int = 128
    tile_n: int = 128
    noise_scale: float = 1.0
    mesh: Any = None  # 1-D device mesh for population sharding
    pop_axis_name: str = "pop"

    def _pop_shards(self, backend: str, cmap: CanonicalMap) -> int:
        """Mesh axis size when this call takes the sharded path, else 0."""
        if self.mesh is None or not cmap.pop:
            return 0
        if backend not in ("surrogate_xla", "surrogate_fused"):
            return 0
        return int(dict(self.mesh.shape)[self.pop_axis_name])

    def matmul(self, x, w, slot_map=None, *, backend=None, key=None,
               block=None, return_moments=False, x_population=None,
               site=None):
        """x (..., K) @ w (K, N) under AM numerics.

        Leading non-contracting dims of x are flattened into M for the
        backends and restored afterwards. With a population slot_map, a
        3-D x whose leading dim equals P is treated as per-genome input
        (override with x_population=True/False when ambiguous).

        A `tiers:<name>` slot_map takes the per-row tier-routed path
        instead (see register_tier_set / row_tier_context).

        ``site`` labels this call site in the numerics-audit accumulators
        (default "matmul"); it does not affect the computation.
        """
        if isinstance(slot_map, str) and slot_map.startswith("tiers:"):
            return self._row_tier_matmul(
                x, w, slot_map.split(":", 1)[1], key=key,
                return_moments=return_moments)
        k, n = w.shape
        cmap = canonical_matmul_map(
            slot_map, k, n, tile_k=self.tile_k, tile_n=self.tile_n
        )
        pop_x = self._resolve_pop_x(x, cmap, 2, x_population)
        lead = x.shape[(1 if pop_x else 0):-1]
        x2 = x.reshape((cmap.population, -1, k) if pop_x else (-1, k))
        m = int(np.prod(lead, dtype=np.int64)) if lead else 1
        name = backend or self.backend or select_backend(
            "matmul",
            has_map=slot_map is not None and bool(np.any(cmap.vids)),
            work=m * k * n * cmap.population,
        )
        obs_metrics.counter_inc("engine.dispatch", op="matmul", backend=name)
        ctx = _Ctx(self, block, return_moments, base_ndim=2, pop_x=pop_x)
        if self._pop_shards(name, cmap):
            out = self._sharded_matmul(name, ctx, x2, w, cmap, key)
        else:
            out = get_backend(name).matmul(ctx, x2, w, cmap, key)
        if self._audit_wanted(name, cmap, key, out, return_moments,
                              site or "matmul"):
            self._audit_matmul(site or "matmul", name, slot_map, x2, w,
                               cmap, key, out)

        def fix(t):
            if cmap.pop:
                return t.reshape((t.shape[0],) + tuple(lead) + (n,))
            return t.reshape(tuple(lead) + (n,))

        if return_moments:
            return fix(out[0]), fix(out[1])
        return fix(out)

    def _row_tier_matmul(self, x, w, set_name: str, *, key,
                         return_moments: bool = False):
        """Per-row tier-routed surrogate matmul (the serving path).

        Row r computes the surrogate moments under its own tier's folded
        weights: mean_r = x_r @ (w (1 + mu_t)), var_r = x_r^2 @ (w^2 sg_t^2)
        with t = tiers[r] from the ambient row_tier_context — one gather +
        two batched contractions for the whole mixed-tier batch, no per-tier
        dispatch. A None-policy tier has all-zero moments: its rows come out
        exact-mean, zero-variance, so premium traffic shares the dispatch.

        Noise is drawn PER ROW from fold_in(key, pos[r]) — a function of the
        call key and the request-local position only, never the row/slot
        index or the global schedule. That extends the CRN isolation
        contract to continuous batching: a request's noise realization is
        identical in any slot, under any neighbors, at any admission time.
        """
        policies = tier_set(set_name)
        tiers, pos = _current_row_tiers()
        k, n = w.shape
        lead = x.shape[:-1]
        x2 = x.reshape(-1, k)
        rows = int(tiers.shape[0])
        if x2.shape[0] != rows:
            raise ValueError(
                f"tiers:{set_name}: x has {x2.shape[0]} rows (lead dims "
                f"{lead}) but the row_tier_context binds {rows}; per-row "
                "tier routing needs exactly one matmul row per served slot")
        vids = np.stack([
            canonical_matmul_map(p, k, n, tile_k=self.tile_k,
                                 tile_n=self.tile_n).vids
            for p in policies])  # (T, K, N) concrete
        wm, wv = fold_matmul_weights(
            w, CanonicalMap(vids, True), noise_scale=self.noise_scale)
        wm_r = jnp.asarray(wm)[tiers]  # (B, K, N): each row's folded weights
        wv_r = jnp.asarray(wv)[tiers]
        xf = x2.astype(jnp.float32)
        mean = jnp.einsum("bk,bkn->bn", xf, wm_r)
        var = jnp.einsum("bk,bkn->bn", xf * xf, wv_r)
        if return_moments:
            return mean.reshape(lead + (n,)), var.reshape(lead + (n,))
        _require_key(key, f"tiers:{set_name}")
        zkeys = jax.vmap(lambda p_: jax.random.fold_in(key, p_))(pos)
        z = jax.vmap(lambda kk: surrogate.crn_normal(kk, (n,), jnp.float32))(
            zkeys)
        out = mean + z * jnp.sqrt(jnp.maximum(var, 0.0))
        return out.reshape(lead + (n,))

    def conv2d(self, x, w, slot_map=None, *, backend=None, key=None,
               return_moments=False, x_population=None, site=None):
        """NHWC VALID stride-1 conv2d under AM numerics.

        x: (B, H, W, Cin) — or (P, B, H, W, Cin) with a population slot_map;
        w: (F, kh, kw, Cin); slot_map canonicalizes to (P?, F, kh, kw).
        ``site`` labels this call in the numerics-audit accumulators.
        """
        if isinstance(slot_map, str) and slot_map.startswith("tiers:"):
            raise NotImplementedError(
                "per-row tier policies are a serving (matmul) feature; conv "
                "has no per-request batch rows to route")
        f, kh, kw, cin = w.shape
        cmap = canonical_conv_map(slot_map, f, kh, kw)
        pop_x = self._resolve_pop_x(x, cmap, 4, x_population)
        ho = x.shape[-3] - kh + 1
        wo = x.shape[-2] - kw + 1
        name = backend or self.backend or select_backend(
            "conv2d",
            has_map=slot_map is not None and bool(np.any(cmap.vids)),
            work=int(x.shape[-4]) * ho * wo * f * kh * kw * cin * cmap.population,
        )
        obs_metrics.counter_inc("engine.dispatch", op="conv2d", backend=name)
        ctx = _Ctx(self, None, return_moments, base_ndim=4, pop_x=pop_x)
        if self._pop_shards(name, cmap):
            return self._sharded_conv2d(name, ctx, x, w, cmap, key)
        out = get_backend(name).conv2d(ctx, x, w, cmap, key)
        if self._audit_wanted(name, cmap, key, out, return_moments,
                              site or "conv2d"):
            self._audit_conv2d(site or "conv2d", name, slot_map, x, w,
                               cmap, key, out)
        return out

    # --- population sharding (surrogate backends only) ---------------------
    #
    # Each shard receives a contiguous slice of the padded population and
    # applies EXACTLY the per-genome op sequence of the single-device path
    # (lax.map of the same dot/conv, or the same slice-invariant einsum), so
    # the gathered result is bitwise identical to the unsharded call.
    # CRN invariant: z = normal(global_key, single_genome_output_shape) —
    # a function of the replicated key only, never of the shard-local or
    # global population index — so every shard draws the same realization.

    def _shard_pop_call(self, fn, pop_args, rep_args, *, n_outs: int):
        """Run fn(*pop_args, *rep_args) under shard_map, population-sharded
        leading axes for pop_args, replicated rep_args and outputs sharded."""
        from jax.sharding import PartitionSpec as PS

        sp = PS(self.pop_axis_name)
        in_specs = (sp,) * len(pop_args) + (PS(),) * len(rep_args)
        out_specs = (sp,) * n_outs if n_outs > 1 else sp
        f = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        return f(*pop_args, *rep_args)

    def _sharded_matmul(self, name, ctx: _Ctx, x2, w, cmap: CanonicalMap, key):
        _require_key(key, name)
        nshard = self._pop_shards(name, cmap)
        p = cmap.population
        vids = pad_population(cmap.vids, nshard)
        pop_x, return_moments = ctx.pop_x, ctx.return_moments
        if pop_x:
            x2 = _pad_population_jax(jnp.asarray(x2), vids.shape[0])

        # CRN: one z for the single-genome (M, N) output, computed OUTSIDE
        # shard_map from the global key (constant-folded when the key is
        # concrete) and replicated — bitwise the same realization every
        # shard previously drew from the replicated key.
        if return_moments:
            rep_args = ()
        else:
            z = surrogate.crn_normal(
                key, (np.shape(x2)[-2], np.shape(w)[1]), jnp.float32)
            rep_args = (z,)

        if name == "surrogate_fused":
            # The slice-invariant einsum formulation of the single-device
            # fused backend: per-shard batched dots over host-folded weights.
            wm, wv = fold_matmul_weights(
                w, CanonicalMap(vids, True), noise_scale=self.noise_scale)

            def per_shard(*args):
                if pop_x:
                    wm_s, wv_s, x_s = args[:3]
                    xf = x_s.astype(jnp.float32)
                    mean = jnp.einsum("pmk,pkn->pmn", xf, wm_s)
                    var = jnp.einsum("pmk,pkn->pmn", xf * xf, wv_s)
                else:
                    wm_s, wv_s = args[:2]
                    xf = jnp.asarray(x2).astype(jnp.float32)
                    mean = jnp.einsum("mk,pkn->pmn", xf, wm_s)
                    var = jnp.einsum("mk,pkn->pmn", xf * xf, wv_s)
                if return_moments:
                    return mean, var
                z_s = args[-1]
                return mean + z_s[None] * jnp.sqrt(jnp.maximum(var, 0.0))

            pop_args = [jnp.asarray(wm), jnp.asarray(wv)]
        else:  # surrogate_xla: lax.map of the per-genome op sequence
            mu, sg = moment_maps(vids, self.noise_scale)  # (Pp, K, N) np

            def per_shard(*args):
                if pop_x:
                    mu_s, sg_s, x_s = args[:3]
                    mapped = (mu_s, sg_s, x_s)
                else:
                    mu_s, sg_s = args[:2]
                    mapped = (mu_s, sg_s)

                def one(a):
                    xi = a[2] if pop_x else jnp.asarray(x2)
                    return _moment_matmul(xi, w, a[0], a[1])

                mean, var = jax.lax.map(one, mapped)
                if return_moments:
                    return mean, var
                z_s = args[-1]
                return mean + z_s[None] * jnp.sqrt(jnp.maximum(var, 0.0))

            pop_args = [jnp.asarray(mu), jnp.asarray(sg)]

        if pop_x:
            pop_args.append(x2)
        out = self._shard_pop_call(
            per_shard, tuple(pop_args), rep_args,
            n_outs=2 if return_moments else 1)
        if return_moments:
            return out[0][:p], out[1][:p]
        return out[:p]

    def _sharded_conv2d(self, name, ctx: _Ctx, x, w, cmap: CanonicalMap, key):
        _require_key(key, name)
        nshard = self._pop_shards(name, cmap)
        p = cmap.population
        vids = pad_population(cmap.vids, nshard)
        f, kh, kw, cin = np.shape(w)
        pop_x, return_moments = ctx.pop_x, ctx.return_moments
        xj = jnp.asarray(x)
        if pop_x:
            xj = _pad_population_jax(xj, vids.shape[0])

        # CRN: z for the single-genome (B, Ho, Wo, F) output, drawn OUTSIDE
        # shard_map from the global key (constant-folded when the key is
        # concrete) and replicated — bitwise the realization every shard
        # previously drew in-graph from the replicated key.
        if return_moments:
            rep_args = ()
        else:
            b = xj.shape[-4]
            ho, wo = xj.shape[-3] - kh + 1, xj.shape[-2] - kw + 1
            z_dtype = jnp.result_type(xj.dtype, jnp.float32)
            z = surrogate.crn_normal(key, (b, ho, wo, f), z_dtype)
            rep_args = (z,)

        if name == "surrogate_xla":
            from repro.kernels import ref

            mu, sg = moment_maps(vids, self.noise_scale)  # (Pp, F, kh, kw)
            # Same folding arithmetic as the per-genome backend, batched.
            w_mu = jnp.asarray(w) * (1.0 + jnp.asarray(mu)[..., None])
            w_sg2 = (jnp.asarray(w) * jnp.asarray(w)) * (
                jnp.asarray(sg) ** 2)[..., None]

            def per_shard(*args):
                if pop_x:
                    wmu_s, wsg_s, x_s = args[:3]
                    mapped = (wmu_s, wsg_s, x_s)
                else:
                    wmu_s, wsg_s = args[:2]
                    mapped = (wmu_s, wsg_s)

                def one(a):
                    xi = a[2] if pop_x else xj
                    mean = ref.conv2d_exact_ref(xi, a[0])
                    var = ref.conv2d_exact_ref(xi * xi, a[1])
                    return mean, var

                mean, var = jax.lax.map(one, mapped)
                if return_moments:
                    return mean, var
                z_s = args[-1]
                return mean + z_s[None] * jnp.sqrt(jnp.maximum(var, 0.0))

            pop_args = [w_mu, w_sg2] + ([xj] if pop_x else [])
        else:  # surrogate_fused: the slice-invariant einsum formulation
            wm, wv = fold_conv_gemm_weights(
                w, CanonicalMap(vids, True), noise_scale=self.noise_scale,
                layout="tap_major")

            def per_shard(*args):
                if pop_x:
                    wm_s, wv_s, x_s = args[:3]
                    pats = jax.vmap(
                        lambda xs: _fused_conv_patches(xs, kh, kw)[0])(x_s)
                    b, ho, wo = (x_s.shape[1], x_s.shape[2] - kh + 1,
                                 x_s.shape[3] - kw + 1)
                    mean = jnp.einsum("pfk,pkm->pfm", wm_s, pats)
                    var = jnp.einsum("pfk,pkm->pfm", wv_s, pats * pats)
                else:
                    wm_s, wv_s = args[:2]
                    pat, (b, ho, wo) = _fused_conv_patches(xj, kh, kw)
                    mean = jnp.einsum("pfk,km->pfm", wm_s, pat)
                    var = jnp.einsum("pfk,km->pfm", wv_s, pat * pat)

                def unflatten(t):
                    t = t.reshape(t.shape[:-1] + (b, ho, wo))
                    return jnp.moveaxis(t, -4, -1)

                mean, var = unflatten(mean), unflatten(var)
                if return_moments:
                    return mean, var
                z_s = args[-1]
                return mean + z_s[None] * jnp.sqrt(jnp.maximum(var, 0.0))

            pop_args = [jnp.asarray(wm), jnp.asarray(wv)] + ([xj] if pop_x else [])

        out = self._shard_pop_call(
            per_shard, tuple(pop_args), rep_args,
            n_outs=2 if return_moments else 1)
        if return_moments:
            return out[0][:p], out[1][:p]
        return out[:p]

    # --- online numerics auditing (obs/numerics.py) ------------------------
    #
    # A deterministically sampled subset of eager approximate calls is
    # re-run on the exact backend (a capped tile for large shapes) and the
    # realized signed relative error streamed into obs_numerics.AUDIT,
    # together with a calibration z-score of the realized errors against
    # the surrogate-predicted (mu, sigma). The sampling decision is a pure
    # hash of the call's global CRN key + site — the same invariant that
    # makes CRN noise schedule/shard-invariant makes the audited-call set
    # reproducible. The audited output is NEVER modified: audit-on runs are
    # bitwise identical to audit-off runs.
    #
    # Traced calls (any tracer among out/key) are skipped — re-running
    # inside a jit would bloat every compiled graph; eager call sites
    # (foundry sweeps, benchmarks, tests, model evaluation outside jit)
    # carry the signal. Population maps are skipped too (the per-genome
    # search path has its own bit-exactness gates); serving tiers get the
    # shadow-exact request audits in launch/serve.py instead.

    def _audit_wanted(self, name, cmap: CanonicalMap, key, out,
                      return_moments: bool, site: str) -> bool:
        if not obs_numerics.audit_active():  # one branch when audits are off
            return False
        if (return_moments or cmap.pop or key is None
                or not bool(np.any(cmap.vids))
                or get_backend(name).fidelity == "exact"
                or isinstance(out, jax.core.Tracer)
                or isinstance(key, jax.core.Tracer)):
            return False
        return obs_numerics.sample_decision(key, site)

    def _variant_label(self, slot_map) -> str:
        return slot_map if isinstance(slot_map, str) else "custom"

    def _record_audit(self, site, name, slot_map, y, y_ref, mean_pred,
                      var_pred, t0) -> None:
        rel = obs_numerics.relative_error(y, y_ref)
        mask = var_pred > 0
        z = None
        if mask.any():
            # Residuals standardized by the surrogate-predicted moments are
            # ~iid N(0,1) when the error model is calibrated (exactly the
            # CRN field for moments-fidelity backends, CLT for bit-exact
            # ones), so sqrt(n) * mean(resid) ~ N(0,1) either way.
            r = (y - mean_pred)[mask] / np.sqrt(var_pred[mask])
            z = float(r.mean() * np.sqrt(r.size))
        obs_numerics.record(site, name, self._variant_label(slot_map), rel, z)
        obs_metrics.observe("numerics.audit.seconds",
                            time.perf_counter() - t0, op=site)

    def _audit_matmul(self, site, name, slot_map, x2, w, cmap, key, out):
        with obs_trace.span("engine.audit", op=site, backend=name):
            t0 = time.perf_counter()
            rows = obs_numerics.audit_max_rows()
            xs = np.asarray(x2, np.float64)[:rows]
            y = np.asarray(out, np.float64)[:rows]
            ectx = _Ctx(self, None, False, base_ndim=2, pop_x=False)
            y_ref = np.asarray(
                _exact_matmul(ectx, jnp.asarray(xs, jnp.float32), w, cmap,
                              None),
                np.float64)
            wf = np.asarray(w, np.float64)
            mu, sg = moment_maps(cmap.vids, self.noise_scale)  # (K, N) f32
            mean_pred = xs @ (wf * (1.0 + mu.astype(np.float64)))
            var_pred = (xs * xs) @ ((wf * wf) * np.square(sg, dtype=np.float64))
            self._record_audit(site, name, slot_map, y, y_ref, mean_pred,
                               var_pred, t0)

    def _audit_conv2d(self, site, name, slot_map, x, w, cmap, key, out):
        with obs_trace.span("engine.audit", op=site, backend=name):
            t0 = time.perf_counter()
            nb = obs_numerics.audit_max_images()
            xs = np.asarray(x, np.float64)[:nb]
            y = np.asarray(out, np.float64)[:nb]
            f, kh, kw, cin = np.shape(w)
            ectx = _Ctx(self, None, False, base_ndim=4, pop_x=False)
            y_ref = np.asarray(
                _exact_conv2d(ectx, jnp.asarray(xs, jnp.float32), w, cmap,
                              None),
                np.float64)
            # Predicted moments via the same host fold as the fused backend,
            # promoted to f64: mean = (w(1+mu)) @ patches, var = (w² σ²) @ p².
            wm, wv = fold_conv_gemm_weights(
                w, cmap, noise_scale=self.noise_scale, layout="tap_major")
            pat = conv_patch_matrix(xs, kh, kw)  # (kh*kw*C, nb, ho*wo) f64
            pk = pat.reshape(pat.shape[0], -1)

            def unflatten(t):  # (F, nb*ho*wo) -> (nb, ho, wo, F)
                t = t.reshape(f, nb, y.shape[-3], y.shape[-2])
                return np.moveaxis(t, 0, -1)

            mean_pred = unflatten(wm.astype(np.float64) @ pk)
            var_pred = unflatten(wv.astype(np.float64) @ (pk * pk))
            self._record_audit(site, name, slot_map, y, y_ref, mean_pred,
                               var_pred, t0)

    @staticmethod
    def _resolve_pop_x(x, cmap: CanonicalMap, base_ndim: int, x_population):
        if x_population is None:
            pop_x = cmap.pop and np.ndim(x) == base_ndim + 1
        else:
            pop_x = bool(x_population)
        if pop_x:
            if not cmap.pop:
                raise ValueError("x has a population axis but slot_map does not")
            if x.shape[0] != cmap.population:
                raise ValueError(
                    f"x population axis {x.shape[0]} != slot-map population "
                    f"{cmap.population}"
                )
        return pop_x


DEFAULT_ENGINE = AMEngine()


def am_matmul(x, w, slot_map=None, *, backend=None, key=None, engine=None,
              block=None, return_moments=False, x_population=None,
              tile_k=None, tile_n=None, noise_scale=None, mesh=None,
              pop_axis_name=None, site=None):
    """Backend-dispatched AM matmul (module-level convenience)."""
    eng = _configured(engine, tile_k=tile_k, tile_n=tile_n,
                      noise_scale=noise_scale, mesh=mesh,
                      pop_axis_name=pop_axis_name)
    return eng.matmul(x, w, slot_map, backend=backend, key=key, block=block,
                      return_moments=return_moments, x_population=x_population,
                      site=site)


def am_conv2d(x, w, slot_map=None, *, backend=None, key=None, engine=None,
              return_moments=False, x_population=None, noise_scale=None,
              mesh=None, pop_axis_name=None, site=None):
    """Backend-dispatched AM conv2d (module-level convenience)."""
    eng = _configured(engine, noise_scale=noise_scale, mesh=mesh,
                      pop_axis_name=pop_axis_name)
    return eng.conv2d(x, w, slot_map, backend=backend, key=key,
                      return_moments=return_moments, x_population=x_population,
                      site=site)


def _configured(engine, **overrides) -> AMEngine:
    eng = engine or DEFAULT_ENGINE
    kw = {k: v for k, v in overrides.items() if v is not None}
    return dataclasses.replace(eng, **kw) if kw else eng
