"""Roofline terms from a compiled dry-run artifact (DESIGN.md §7).

Per (arch × shape × mesh):
    compute_term    = device_FLOPs / peak_FLOPs_per_chip
    memory_term     = device_bytes / HBM_bw_per_chip
    collective_term = device_collective_bytes / ICI_bw_per_chip

``cost_analysis()`` on the SPMD-partitioned module reports *per-device*
FLOPs/bytes, so the terms above are per-chip already (equivalent to the
global-HLO/(chips×peak) formulation).  Collective bytes are parsed from the
optimized per-device HLO: sum of operand bytes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops (all-reduce counted 2×
for the ring's reduce+broadcast phases).
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass, field



@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s, chip-to-chip interconnect


#: Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bw=819e9, ici_bw=1600e9 / 8),
}

#: ``device_kind`` of a TPU v5e chip (the dry-run's target)
V5E = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; a kind missing from :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to repro.analysis.roofline.PEAKS with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in the (per-device) HLO.

    ``-done`` ops are skipped (their ``-start`` counterpart is counted).
    all-reduce is weighted 2x (ring reduce-scatter + all-gather phases).
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        if "-done(" in line:
            continue
        type_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(type_str)
        if kind == "all-reduce":
            b *= 2
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    device_flops: float
    device_bytes: float
    collective_bytes: float
    model_flops_global: float      # 6·N·D (train) or 2·N_active·tokens (decode)
    n_devices: int
    device_kind: str               # key of PEAKS
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    useful_flops_frac: float = 0.0
    step_time_s: float = 0.0
    roofline_frac: float = 0.0
    collectives: dict = field(default_factory=dict)
    memory_per_device_gb: float = 0.0
    notes: str = ""

    def finalize(self) -> "Roofline":
        peaks = chip_peaks(self.device_kind)
        self.compute_s = self.device_flops / peaks.bf16_flops
        self.memory_s = self.device_bytes / peaks.hbm_bw
        self.collective_s = self.collective_bytes / peaks.ici_bw
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        self.dominant = max(terms, key=terms.get)
        total_hlo_flops = self.device_flops * self.n_devices
        self.useful_flops_frac = (
            self.model_flops_global / total_hlo_flops if total_hlo_flops else 0.0
        )
        # bound on step time: max of the three terms (perfect overlap);
        # roofline fraction = useful-compute time / bound.
        self.step_time_s = max(terms.values())
        useful_compute_s = self.model_flops_global / (
            peaks.bf16_flops * self.n_devices)
        self.roofline_frac = (
            useful_compute_s / self.step_time_s if self.step_time_s else 0.0
        )
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, shape, n_active_params: int) -> float:
    """MODEL_FLOPS: 6·N·D for train; 2·N·new_tokens for decode; 2·N·D prefill."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active_params * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active_params * tokens
    return 2.0 * n_active_params * shape.global_batch  # decode: 1 token/seq
