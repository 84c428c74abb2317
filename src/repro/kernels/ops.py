"""Public jit'd wrappers over the Pallas kernels: padding, impl dispatch, and
the roofline-autotuned block chooser shared by every Pallas entry point.

These are the kernel-level primitives the AM engine (core/engine.py)
dispatches to; call them directly only when you need explicit control over
blocks. `impl="kernel"` runs the Pallas kernel (compiled on a TPU, in
interpret mode elsewhere — kernels/platform.py decides per call);
`impl="ref"` runs the pure-jnp oracle; the surrogate path adds
`impl="fused_xla"` — the same fused one-pass contraction expressed as a
single XLA computation, the fast spelling on a CPU host — and
`impl="auto"` (kernel on TPU, fused_xla otherwise).
Shapes are padded to block multiples and cropped back.

Block selection (`choose_block`) is an autotuner: candidate block shapes that
fit the VMEM budget are scored against the kernel roofline model
(roofline/analysis.py::surrogate_block_time / bitexact_block_time) for the
current target — the TPU's entry in the peak table keyed by device_kind
(an unlisted TPU raises), the 2-core ~1.2 GB/s build box off TPU — and the
winner is memoized in a per-shape tuning cache persisted to
artifacts/tuning_cache.json (override with $REPRO_TUNING_CACHE). Given a
cache entry the chooser is a pure lookup, so block choices are deterministic
across runs and across model revisions until the cache is regenerated.
"""
from __future__ import annotations

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fp32_mul
from repro.kernels import am_surrogate_matmul as _sgk
from repro.kernels import approx_conv as _convk
from repro.kernels import approx_matmul as _mmk
from repro.kernels import bitexact_emulator as _emuk
from repro.kernels import platform
from repro.kernels import ref as _ref

# Per-core VMEM envelopes the chooser sizes against (TPU v5e has ~16 MiB per
# core; the bit-exact kernels leave headroom for the compiler's own buffers).
VMEM_BYTES = 16 * 2**20
BITEXACT_VMEM_BUDGET = 8 * 2**20

# The bit-exact matmul kernel double-buffers its x (bm,bk), w (bk,bn) and
# 24-word mask (24,bk,bn) blocks, keeps 4 lane accumulators and the output
# (bm,bn) tile, and the packed emulation of one k row holds about
# _EMU_TILE_WORDS live (bm,bn) int32 tiles. The fused surrogate kernel's
# live set is x (bm,bk) + folded wm/wv (bk,bn)*2 + z/out/var (bm,bn)*3 f32
# (the unfolded moments kernel's w/mu/sg + two accumulators is the same size).
_EMU_TILE_WORDS = 64


def _bitexact_live_bytes(bm: int, bk: int, bn: int) -> int:
    buffered = bm * bk + (1 + fp32_mul.N_MASK_WORDS) * bk * bn
    return 4 * (2 * buffered + (4 + 2 + _EMU_TILE_WORDS) * bm * bn)


def _surrogate_live_bytes(bm: int, bk: int, bn: int) -> int:
    return (bm * bk + 3 * bk * bn + 3 * bm * bn) * 4


# ---------------------------------------------------------------------------
# Block autotuner: candidates -> roofline score -> persisted tuning cache
# ---------------------------------------------------------------------------

TUNING_CACHE_ENV = "REPRO_TUNING_CACHE"
# Bump when candidate enumeration or the scoring model changes shape: stale
# cache entries for old versions are ignored rather than misapplied.
_TUNE_VERSION = 2

_tuning_cache: dict[str, list] = {}
_disk_cache_loaded = False


def tuning_cache_path() -> pathlib.Path:
    """$REPRO_TUNING_CACHE, else artifacts/tuning_cache.json at the repo root
    (located by walking up from this file; falls back to the CWD for
    installed-package layouts without a repo checkout)."""
    env = os.environ.get(TUNING_CACHE_ENV)
    if env:
        return pathlib.Path(env)
    for parent in pathlib.Path(__file__).resolve().parents:
        if (parent / "artifacts").is_dir():
            return parent / "artifacts" / "tuning_cache.json"
    return pathlib.Path("artifacts") / "tuning_cache.json"


def _load_disk_cache() -> None:
    global _disk_cache_loaded
    if _disk_cache_loaded:
        return
    _disk_cache_loaded = True
    path = tuning_cache_path()
    try:
        disk = json.loads(path.read_text())
    except (OSError, ValueError):
        return
    for key, block in disk.items():
        _tuning_cache.setdefault(key, block)


def save_tuning_cache(path: pathlib.Path | None = None) -> pathlib.Path:
    """Persist the in-memory tuning cache (sorted keys, stable diffs)."""
    path = path or tuning_cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(
        json.dumps({k: _tuning_cache[k] for k in sorted(_tuning_cache)},
                   indent=1) + "\n")
    return path


def clear_tuning_cache() -> None:
    """Drop in-memory entries and re-arm the disk load (tests)."""
    global _disk_cache_loaded
    _tuning_cache.clear()
    _disk_cache_loaded = False


def _kernel_target():
    from repro.roofline import analysis

    if not platform.on_tpu():
        return analysis.BUILD_BOX_KERNEL
    return analysis.kernel_target(platform.device_kind())


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _pow2_range(lo: int, hi: int) -> list[int]:
    out, p = [], lo
    while p <= hi:
        out.append(p)
        p *= 2
    return out or [lo]


def candidate_blocks(kind: str, m: int, k: int, n: int,
                     *, vmem_bytes: int | None = None) -> list[tuple]:
    """Tile-legal (bm, bk, bn) candidates that fit the VMEM budget.

    Legal for Mosaic: bm is a multiple of 8, and bk and bn are multiples of
    128 or the whole padded dim (the wrappers pad each dim to a multiple of
    its block). Power-of-two dims are capped at the pow2 ceiling of the
    problem and at the kernel's practical maxima; every returned candidate
    satisfies the kind's live-set budget, so any of them is safe to launch —
    the scorer only decides which is fastest.
    """
    if kind == "bitexact_matmul":
        budget = vmem_bytes or BITEXACT_VMEM_BUDGET
        fits = _bitexact_live_bytes
        bms = _pow2_range(8, min(max(_pow2_ceil(m), 8), 32))
        bns = _pow2_range(128, min(max(_pow2_ceil(n), 128), 256))
        # K up to 128 is one whole block (padded to the 8-row w tile).
        bks = ([-(-k // 8) * 8] if k <= 128
               else _pow2_range(128, min(_pow2_ceil(k), 512)))
    elif kind == "surrogate_matmul":
        budget = vmem_bytes or VMEM_BYTES
        fits = _surrogate_live_bytes
        caps = tuple(min(max(_pow2_ceil(d), 8), 512) for d in (m, k, n))
        bms = _pow2_range(8, caps[0])
        bks = [b for b in _pow2_range(8, caps[1])
               if b % 128 == 0 or b == caps[1]]
        bns = [b for b in _pow2_range(8, caps[2])
               if b % 128 == 0 or b == caps[2]]
    else:
        raise ValueError(f"no block candidates for kind {kind!r}")
    cands = [(bm, bk, bn) for bm in bms for bk in bks for bn in bns
             if fits(bm, bk, bn) <= budget]
    if not cands:  # degenerate budget: smallest legal block
        cands = [(bms[0], bks[0], bns[0])]
    return cands


def score_block(kind: str, block, m: int, k: int, n: int) -> float:
    """Modeled seconds for one candidate on the current kernel target."""
    from repro.roofline import analysis

    target = _kernel_target()
    if kind == "bitexact_matmul":
        return analysis.bitexact_block_time(
            m, k, n, block, target, mask_words=fp32_mul.N_MASK_WORDS)
    if kind == "surrogate_matmul":
        return analysis.surrogate_block_time(m, k, n, block, target)
    raise ValueError(f"no block model for kind {kind!r}")


def autotune_block(kind: str, m: int, k: int, n: int,
                   *, vmem_bytes: int | None = None) -> tuple:
    """Pure argmin over candidate_blocks under score_block (no cache I/O).

    Ties break toward the larger block, then the larger bn/bk — a total,
    deterministic order, so equal scores cannot flap between runs.
    """
    cands = candidate_blocks(kind, m, k, n, vmem_bytes=vmem_bytes)
    return min(
        cands,
        key=lambda b: (score_block(kind, b, m, k, n),
                       -b[0] * b[1] * b[2], -b[2], -b[1]),
    )


def choose_block(kind: str, m: int, k: int, n: int, *, vmem_bytes: int | None = None):
    """One block chooser for all Pallas entry points (autotuned + cached).

    kind="bitexact_matmul": (bm, bk, bn) whose kernel live set
      (_bitexact_live_bytes) fits the bit-exact VMEM budget (default 8 MiB).
    kind="surrogate_matmul": (bm, bk, bn) whose fused-kernel live set
      (bm*bk + 3*bk*bn + 3*bm*bn) * 4 B fits the v5e VMEM envelope.

    Both kinds consult the tuning cache first (in-memory, seeded from
    artifacts/tuning_cache.json / $REPRO_TUNING_CACHE); on a miss the
    roofline autotuner runs and the result is recorded and best-effort
    persisted, so later runs — and CI, which checks the cache in — are pure
    lookups.
    """
    if kind not in ("bitexact_matmul", "surrogate_matmul"):
        raise ValueError(f"unknown block kind {kind!r}")
    key = (f"v{_TUNE_VERSION}:{_kernel_target().name}:{kind}:"
           f"{m}x{k}x{n}:{vmem_bytes or 0}")
    _load_disk_cache()
    hit = _tuning_cache.get(key)
    if hit is not None:
        return tuple(int(b) for b in hit)
    block = autotune_block(kind, m, k, n, vmem_bytes=vmem_bytes)
    _tuning_cache[key] = [int(b) for b in block]
    try:
        save_tuning_cache()
    except OSError:  # read-only checkout: stay in-memory
        pass
    return block


def _pad_to(x, mults, axes):
    pads = [(0, 0)] * x.ndim
    for ax, mlt in zip(axes, mults):
        rem = (-x.shape[ax]) % mlt
        pads[ax] = (0, rem)
    if any(p != (0, 0) for p in pads):
        x = jnp.pad(x, pads)
    return x


# ---------------------------------------------------------------------------
# Surrogate matmul: moments, folded moments, fused noise epilogue
# ---------------------------------------------------------------------------


def am_surrogate_moments(x, w, mu, sg, *, block=None, impl="auto"):
    """Fused statistical AM matmul moments: (mean, var), both (M, N) f32.

    impl: "kernel" (Pallas) | "fused_xla" (one jitted XLA
    computation, bit-identical to the oracle) | "ref" | "auto".
    """
    m, k = x.shape
    n = w.shape[1]
    if impl == "auto":
        impl = "kernel" if platform.on_tpu() else "fused_xla"
    if impl == "ref" or impl == "fused_xla":
        return _fused_xla_moments(x, w, mu, sg)
    block = block or choose_block("surrogate_matmul", m, k, n)
    bm, bk, bn = block
    xp = _pad_to(x, (bm, bk), (0, 1))
    wp = _pad_to(w, (bk, bn), (0, 1))
    mup = _pad_to(mu, (bk, bn), (0, 1))
    sgp = _pad_to(sg, (bk, bn), (0, 1))
    mean, var = _sgk.am_surrogate_matmul_kernel(
        xp, wp, mup, sgp, block=(bm, bk, bn))
    return mean[:m, :n], var[:m, :n]


@jax.jit
def _fused_xla_moments(x, w, mu, sg):
    return _ref.am_surrogate_matmul_ref(x, w, mu, sg)


def _stacked_moments(x, w_mean, w_var):
    """Both contractions of the surrogate (mean, var) pair. The two plain
    dots are bitwise identical to the stacked batched-einsum spelling (the
    dot order per output element is unchanged either way) and measure
    slightly faster on the build box — the batched GEMM walks the pair in
    one backend call but pays an extra (2, M, K) stack materialization."""
    xf = x.astype(jnp.float32)
    mean = jnp.dot(xf, w_mean, preferred_element_type=jnp.float32)
    var = jnp.dot(xf * xf, w_var, preferred_element_type=jnp.float32)
    return mean, var


def am_surrogate_moments_folded(x, w_mean, w_var, *, block=None, impl="auto"):
    """(mean, var) from pre-folded weights w_mean = w(1+mu), w_var = w^2 sg^2.

    The engine's surrogate_fused backend folds the per-slot moment maps into
    the weights once (host-side for concrete weights) and calls this — or
    the epilogue below — per step. Returns (mean (M, N), var (M, N)) f32.
    """
    m, k = x.shape
    n = w_mean.shape[-1]
    if impl == "auto":
        impl = "kernel" if platform.on_tpu() else "fused_xla"
    if impl in ("ref", "fused_xla"):
        return _stacked_moments(x, w_mean, w_var)
    block = block or choose_block("surrogate_matmul", m, k, n)
    bm, bk, bn = block
    xp = _pad_to(x, (bm, bk), (0, 1))
    wmp = _pad_to(w_mean, (bk, bn), (0, 1))
    wvp = _pad_to(w_var, (bk, bn), (0, 1))
    mean, var = _sgk.am_surrogate_matmul_folded_kernel(
        xp, wmp, wvp, block=(bm, bk, bn))
    return mean[:m, :n], var[:m, :n]


def am_surrogate_matmul_epilogue(x, w_mean, w_var, z, *, block=None,
                                 impl="auto"):
    """Noise-complete surrogate matmul with the CRN draw fused as a GEMM
    epilogue: out = x @ w_mean + z * sqrt(max((x*x) @ w_var, 0)).

    z is the caller's CRN noise tile — (M, N), already drawn from the global
    call key and the single-genome output shape (core/engine.py invariant) —
    so this function stays deterministic and oracle-comparable.

    Shapes: x (M, K) or (P, M, K); w_mean/w_var (K, N) or (P, K, N); z (M, N)
    shared across P. Output gains the population axis iff the weights carry
    one. impl="fused_xla" is bitwise identical to the surrogate_xla op
    sequence (separate dots + elementwise epilogue); impl="kernel" fuses the
    epilogue into the last k-step of the Pallas grid (blocked-k accumulation
    order, allclose to the oracle).
    """
    pop = w_mean.ndim == 3
    pop_x = x.ndim == 3
    if impl == "auto":
        impl = "kernel" if platform.on_tpu() else "fused_xla"
    if impl in ("ref", "fused_xla"):
        xf = x.astype(jnp.float32)
        if not pop:
            mean, var = _stacked_moments(xf, w_mean, w_var)
        elif pop_x:
            mean = jnp.einsum("pmk,pkn->pmn", xf, w_mean)
            var = jnp.einsum("pmk,pkn->pmn", xf * xf, w_var)
        else:
            mean = jnp.einsum("mk,pkn->pmn", xf, w_mean)
            var = jnp.einsum("mk,pkn->pmn", xf * xf, w_var)
        zb = z if not pop else z[None]
        return mean + zb * jnp.sqrt(jnp.maximum(var, 0.0))

    m, k = x.shape[-2:]
    n = w_mean.shape[-1]
    block = block or choose_block("surrogate_matmul", m, k, n)
    bm, bk, bn = block
    xp = _pad_to(x, (bm, bk), (x.ndim - 2, x.ndim - 1))
    wmp = _pad_to(w_mean, (bk, bn), (w_mean.ndim - 2, w_mean.ndim - 1))
    wvp = _pad_to(w_var, (bk, bn), (w_var.ndim - 2, w_var.ndim - 1))
    zp = _pad_to(z, (bm, bn), (0, 1))
    out = _sgk.am_surrogate_matmul_epilogue_kernel(
        xp, wmp, wvp, zp, block=(bm, bk, bn))
    return out[..., :m, :n]


def am_surrogate_matmul(x, w, mu, sg, key, *, block=None, impl="kernel"):
    """Noise-complete statistical AM matmul: mean + z*sqrt(var)."""
    if impl == "ref":
        mean, var = _ref.am_surrogate_matmul_ref(x, w, mu, sg)
    else:
        mean, var = am_surrogate_moments(x, w, mu, sg, block=block, impl=impl)
    z = jax.random.normal(key, mean.shape, mean.dtype)
    return mean + z * jnp.sqrt(jnp.maximum(var, 0.0))


# ---------------------------------------------------------------------------
# Bit-exact entry points
# ---------------------------------------------------------------------------


def am_matmul_bitexact(x, w, variant_ids, *, block=None, impl="kernel"):
    """Bit-exact interleaved AM matmul.

    The per-slot variant ids become per-slot column-mask words (one XLA
    gather from the live registry's mask table) before the kernel runs.
    Dims pad to block multiples with zeros (exact slots), which leaves the
    defined accumulation order's sums unchanged, and crop back.
    """
    if impl == "ref":
        return _ref.am_matmul_bitexact_ref(x, w, variant_ids)
    m, k = x.shape
    n = w.shape[1]
    bm, bk, bn = block or choose_block("bitexact_matmul", m, k, n)
    vids = jnp.asarray(variant_ids, jnp.int32)
    masks = jnp.moveaxis(jnp.asarray(fp32_mul.mask_stack())[vids], -1, 0)
    xp = _pad_to(x.astype(jnp.float32), (bm, bk), (0, 1))
    wp = _pad_to(w.astype(jnp.float32), (bk, bn), (0, 1))
    mp = _pad_to(masks, (bk, bn), (1, 2))
    out = _mmk.am_matmul_bitexact_kernel(xp, wp, mp, block=(bm, bk, bn))
    return out[:m, :n]


def am_conv2d_bitexact(x, w, slot_map, *, impl="kernel"):
    """Bit-exact interleaved conv2d (NHWC, VALID, stride 1)."""
    if impl == "ref":
        return _ref.am_conv2d_bitexact_ref(x, w, slot_map)
    return _convk.am_conv2d_bitexact_kernel(x, w, slot_map)


def fp32_multiply_stacked(a, b, scheme_maps, *, chunk: int | None = None,
                          impl="auto"):
    """Emulate (V, n) products of one operand stream under V scheme maps.

    The batched bit-exact emulator: the Booth partial-product generation
    (the expensive, variant-independent half of the emulation) is computed
    once per operand chunk and broadcast against the V compressor-code maps,
    so characterizing V variants costs far less than V scalar sweeps
    (foundry.characterize_batch's amortization, packaged as a kernel op).

    a, b: float32 (n,) host or device arrays; scheme_maps: (V, 3, 48) int32.
    impl: "fused_xla" (one jitted broadcast emulation per chunk — the build
    box spelling, bit-identical to per-variant fp32_multiply_batch) |
    "kernel" (Pallas grid over (variant block, operand chunk)) | "auto"
    (kernel on a TPU). Returns np.float32 (V, n).
    """
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    maps = np.asarray(scheme_maps, np.int32)
    if maps.ndim != 3 or maps.shape[1:] != (3, 48):
        raise ValueError(f"scheme_maps must be (V, 3, 48), got {maps.shape}")
    if impl == "auto":
        impl = "kernel" if platform.on_tpu() else "fused_xla"
    if chunk is None:
        chunk = max(1 << 10, (1 << 15) // max(maps.shape[0], 1))
    if impl == "kernel":
        return _emuk.fp32_multiply_stacked_kernel(a, b, maps, chunk=chunk)
    if impl != "fused_xla":
        raise ValueError(f"unknown impl {impl!r}")
    codes = jnp.asarray(maps)[:, None]  # (V, 1, 3, 48)
    outs = []
    for i in range(0, a.size, chunk):
        outs.append(np.asarray(fp32_mul._fp32_multiply_jit(
            a[i : i + chunk][None], b[i : i + chunk][None], codes
        )))
    return np.concatenate(outs, axis=1) if outs else np.zeros(
        (maps.shape[0], 0), np.float32)
