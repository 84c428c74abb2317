"""Roofline terms from a compiled dry-run artifact (no hardware needed).

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_traffic_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / link_bw

The post-SPMD module is the per-device program, so all three terms are
per-device seconds (= step time if that term were the only bottleneck).

Costs come from roofline/hlo_costs.py, which re-walks the compiled HLO with
while-loop trip counts — XLA:CPU's built-in cost_analysis() counts each scan
body once and under-reports scanned stacks by orders of magnitude (verified;
its raw numbers are recorded alongside for transparency).

MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens (serve) is the
useful-work yardstick; useful_flops_frac = MODEL_FLOPS / (HLO_FLOPs · chips)
exposes remat recompute and attention/dispatch overheads.

Hardware constants: the per-chip peak table DEVICE_PEAKS, keyed by JAX's
``device_kind``, with its source.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.roofline import hlo_costs


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Published peaks of one chip."""

    name: str
    bf16_flops: float  # FLOP/s
    int8_ops: float  # OP/s
    hbm_bytes: float  # B
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per link


# Keyed by jax.devices()[i].device_kind. Source: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of interconnect per chip (4 links of 50 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeak("tpu_v5e", 197e12, 393e12, 16e9, 819e9, 50e9),
}


def device_peak(device_kind: str) -> DevicePeak:
    """The peak table's entry for ``device_kind``; an unlisted kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "roofline/analysis.py DEVICE_PEAKS with its source") from None


_V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E.bf16_flops  # the dry-run analyses' target chip
HBM_BW = _V5E.hbm_bw
ICI_BW = _V5E.ici_bw


# ---------------------------------------------------------------------------
# Kernel block-time model (the autotuner's scoring function)
# ---------------------------------------------------------------------------
#
# kernels/ops.py::choose_block ranks candidate Pallas block shapes with the
# same three-term roofline used for whole programs, specialized to one grid
# program: compute = block FLOPs / (peak x matrix-unit utilization), memory =
# per-program tile traffic / sustained bandwidth, plus a fixed per-program
# dispatch overhead that penalizes over-fine grids. The model only has to
# RANK blocks consistently — absolute seconds are not calibrated — so the
# constants below are order-of-magnitude targets, and the chosen block is
# persisted in a tuning cache keyed by (kind, shape, target).


@dataclasses.dataclass(frozen=True)
class KernelTarget:
    """Scoring target for the block autotuner.

    peak_flops/mem_bw set the roofline; align is the matrix-unit tile edge
    (blocks smaller than it underutilize the unit); launch_overhead is the
    per-grid-program dispatch cost that penalizes tiny blocks.
    """

    name: str
    peak_flops: float  # f32 FLOP/s
    mem_bw: float  # B/s, sustained
    align: int
    launch_overhead: float  # seconds per grid program


def kernel_target(device_kind: str) -> KernelTarget:
    """Scoring target for a TPU chip from the peak table (MXU is 128x128)."""
    peak = device_peak(device_kind)
    return KernelTarget(peak.name, peak.bf16_flops, peak.hbm_bw, 128, 1e-6)


# The 2-core ~1.2 GB/s build box: 2 cores x ~3 GHz x 8-lane FMA, with
# cache-resident blocking the goal (hence the small align and the large
# relative dispatch overhead of interpret-mode/XLA loop bodies).
BUILD_BOX_KERNEL = KernelTarget("build_box_2core", 4.8e10, 1.2e9, 8, 2e-6)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _mxu_utilization(bm: int, bn: int, bk: int, align: int) -> float:
    """Fraction of the matrix unit a (bm, bk)x(bk, bn) tile keeps busy."""
    eff = 1.0
    for b in (bm, bn, bk):
        eff *= min(b, align) / align
    return max(eff, 1e-6)


def surrogate_block_time(m: int, k: int, n: int, block, target: KernelTarget,
                         *, pop: int = 1) -> float:
    """Modeled seconds for the fused surrogate (mean/var + epilogue) kernel.

    Per grid program the kernel reads an x tile (bm, bk), two folded weight
    tiles (bk, bn), and on the last k step a z tile plus the output write —
    the channel-major blocking where outputs stay resident across the k loop.
    """
    bm, bk, bn = block
    gm, gk, gn = _ceil_div(m, bm), _ceil_div(k, bk), _ceil_div(n, bn)
    programs = pop * gm * gn * gk
    flops = 4.0 * pop * (gm * bm) * (gk * bk) * (gn * bn)  # two MACs/element
    x_bytes = 4.0 * programs * bm * bk
    w_bytes = 4.0 * programs * 2 * bk * bn
    out_bytes = 4.0 * pop * gm * gn * 3 * bm * bn  # z read + out/var write
    t_compute = flops / (target.peak_flops
                         * _mxu_utilization(bm, bn, bk, target.align))
    t_memory = (x_bytes + w_bytes + out_bytes) / target.mem_bw
    # Additive, not max(): a pure roofline max() hides the utilization
    # penalty of degenerate tiles whenever one term dominates, which would
    # rank (bm, 1, bn) blocks above well-shaped ones. The sum still ranks
    # bandwidth- and compute-bound candidates consistently.
    return t_compute + t_memory + programs * target.launch_overhead


def bitexact_block_time(m: int, k: int, n: int, block, target: KernelTarget,
                        *, mask_words: int = 24) -> float:
    """Modeled seconds for the bit-exact emulation kernel.

    Compute: ~600 int32 ops per emulated multiply on (bm, bn) tiles. Memory:
    per program an x tile (bm, bk), a w tile and ``mask_words`` column-mask
    words per (k, n) slot; the same per-program term as the surrogate model.
    """
    bm, bk, bn = block
    gm, gk, gn = _ceil_div(m, bm), _ceil_div(k, bk), _ceil_div(n, bn)
    programs = gm * gn * gk
    muls = float(programs) * bm * bk * bn
    x_bytes = 4.0 * programs * bm * bk
    w_bytes = 4.0 * programs * (1 + mask_words) * bk * bn
    t_memory = (x_bytes + w_bytes) / target.mem_bw
    t_compute = 600.0 * muls / (target.peak_flops
                                * _mxu_utilization(bm, bn, bk, target.align))
    return t_compute + t_memory + programs * target.launch_overhead


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float  # per device
    hlo_bytes: float  # per device (raw traffic approximation)
    hlo_bytes_fused: float  # per device, minus pure data-movement chains
    coll_bytes: float  # per device
    coll_breakdown: dict
    model_flops: float  # global
    bytes_per_device: float  # residency (memory_analysis), not traffic

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory_raw(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_memory(self) -> float:
        """TPU-projected: excludes convert/copy chains XLA:CPU materializes
        but a bf16-native TPU backend fuses (see hlo_costs.HloCosts)."""
        return self.hlo_bytes_fused / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_frac(self) -> float:
        denom = self.hlo_flops * self.chips
        return self.model_flops / denom if denom else 0.0

    @property
    def roofline_frac(self) -> float:
        """compute term / max term: 1.0 = perfectly compute-bound."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        return self.t_compute / t if t > 0 else 0.0

    @property
    def mfu_upper_bound(self) -> float:
        """MODEL_FLOPS / (chips · peak · max-term): the MFU this compiled
        graph could reach if perfectly overlapped — the hillclimb target."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS * t)

    def row(self) -> str:
        return (
            f"{self.arch:26s} {self.shape:12s} {self.mesh:9s} "
            f"comp={self.t_compute*1e3:10.3f}ms mem={self.t_memory*1e3:10.3f}ms "
            f"coll={self.t_collective*1e3:10.3f}ms -> {self.bottleneck:10s} "
            f"useful={self.useful_flops_frac*100:6.1f}% "
            f"MFU*={self.mfu_upper_bound*100:5.1f}%"
        )

    def to_json(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "hlo_bytes_fused": self.hlo_bytes_fused,
            "t_memory_raw": self.t_memory_raw,
            "coll_bytes": self.coll_bytes, "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "bytes_per_device": self.bytes_per_device,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_frac": self.roofline_frac,
            "mfu_upper_bound": self.mfu_upper_bound,
        }


def model_flops(cfg, shape_kind: str, batch: int, seq: int) -> float:
    """6·N_active·tokens for train, 2·N_active·tokens for serve."""
    n = active_param_count(cfg)
    if shape_kind == "train":
        return 6.0 * n * batch * seq
    if shape_kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch  # decode: one token per request


def active_param_count(cfg) -> int:
    """Params touched per token (MoE: top_k of n_experts; embeddings excl. head gather)."""
    total = _total_params(cfg)
    if cfg.n_experts and cfg.top_k:
        expert = _expert_params(cfg)
        total = total - expert + expert * cfg.top_k // cfg.n_experts
    return total


def _total_params(cfg) -> int:
    import jax

    from repro.models import registry as R

    aparams = R.abstract_params(cfg)
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(aparams))


def _expert_params(cfg) -> int:
    n_moe_layers = sum(1 for (_, f) in cfg.pattern if f == "moe")
    n_moe = cfg.n_rep * n_moe_layers + sum(
        1 for j in range(cfg.n_tail) if cfg.pattern[j][1] == "moe")
    return n_moe * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff


def from_compiled(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
                  cfg, shape_kind: str, batch: int, seq: int):
    """Roofline record + raw artifacts from one compiled cell."""
    hlo = compiled.as_text()
    costs = hlo_costs.analyze(hlo)
    raw = compiled.cost_analysis()
    if isinstance(raw, list):
        raw = raw[0]
    try:
        mem = compiled.memory_analysis()
        bpd = float(getattr(mem, "temp_size_in_bytes", 0)
                    + getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "output_size_in_bytes", 0)
                    - getattr(mem, "alias_size_in_bytes", 0))
    except Exception:
        bpd = 0.0
    roof = Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=costs.flops, hlo_bytes=costs.traffic_bytes,
        hlo_bytes_fused=costs.traffic_bytes_fused,
        coll_bytes=costs.coll_bytes, coll_breakdown=costs.coll_breakdown,
        model_flops=model_flops(cfg, shape_kind, batch, seq),
        bytes_per_device=bpd,
    )
    extras = {
        "xla_cost_analysis_flops": float(raw.get("flops", 0.0)),
        "xla_cost_analysis_bytes": float(raw.get("bytes accessed", 0.0)),
        "coll_count": costs.coll_count,
        "while_trips": costs.while_trips,
    }
    return roof, extras
