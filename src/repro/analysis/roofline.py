"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds:

    compute    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = collective_bytes_per_chip / link_bw

``compiled.cost_analysis()`` is per-partition (one chip) under SPMD, so
no extra division by chip count is applied.  Collective bytes are not in
cost_analysis; :func:`collective_bytes` parses the post-SPMD HLO and
models per-device bytes-on-wire per op:

    all-gather        out_bytes * (n-1)/n
    reduce-scatter    out_bytes * (n-1)
    all-reduce        2 * out_bytes * (n-1)/n      (ring RS+AG)
    all-to-all        out_bytes * (n-1)/n
    collective-permute out_bytes

with n = replica-group size parsed per op.  Peak rates come from
:data:`PEAKS`, keyed by the chip's ``device_kind``.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peak rates, per second."""
    bf16_flops: float
    hbm_bw: float                     # bytes
    ici_link_bw: float                # bytes, one link


#: Keyed by ``jax.Device.device_kind``.  TPU v5e ("TPU v5 lite"): Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s ICI per chip over 4 links.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             ici_link_bw=1600e9 / 8 / 4),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip; a kind missing from :data:`PEAKS` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{(.*?)\}")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string, incl. tuple types."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
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
    op_bytes: Dict[str, int] = field(default_factory=dict)
    op_count: Dict[str, int] = field(default_factory=dict)
    total_wire_bytes: float = 0.0

    def add(self, kind: str, wire_bytes: float) -> None:
        self.op_bytes[kind] = self.op_bytes.get(kind, 0) + int(wire_bytes)
        self.op_count[kind] = self.op_count.get(kind, 0) + 1
        self.total_wire_bytes += wire_bytes


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Parse post-SPMD HLO; model per-device bytes-on-wire per op."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\d]+)\s+"
                     r"([\w\-]+)", s)
        if not m:
            continue
        opname = m.group(2)
        kind = next((c for c in _COLLECTIVES
                     if opname == c or opname.startswith(c + "-start")
                     or opname == c + "-done"), None)
        if kind is None:
            continue
        if opname.endswith("-done"):
            continue                      # counted at -start
        out_bytes = _shape_bytes(m.group(1))
        # group size
        n = 1
        g = _GROUPS_RE.search(s)
        if g:
            n = len(g.group(1).split(","))
        else:
            g2 = _GROUPS_V2_RE.search(s)
            if g2:
                n = int(g2.group(2))
        if kind == "all-gather":
            wire = out_bytes * (n - 1) / max(n, 1)
        elif kind == "reduce-scatter":
            wire = out_bytes * (n - 1)
        elif kind == "all-reduce":
            wire = 2 * out_bytes * (n - 1) / max(n, 1)
        elif kind == "all-to-all":
            wire = out_bytes * (n - 1) / max(n, 1)
        else:                              # collective-permute
            wire = out_bytes
        stats.add(kind, wire)
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    wire_bytes_per_chip: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float                 # 6·N·D (or 6·N_active·D)
    useful_ratio: float                # MODEL_FLOPS / (HLO_FLOPs·chips)
    peak_fraction: float               # t_compute / max(all terms)
    collectives: Dict[str, int] = field(default_factory=dict)
    memory_per_chip_gb: float = 0.0
    note: str = ""

    def to_json(self) -> Dict:
        return asdict(self)


def build_roofline(arch: str, shape: str, mesh_name: str, chips: int,
                   device_kind: str,
                   flops_per_chip: float, bytes_per_chip: float,
                   wire_bytes_per_chip: float, model_flops: float,
                   collectives: Optional[Dict[str, float]] = None,
                   memory_per_chip: float = 0.0, note: str = ""
                   ) -> Roofline:
    pk = peaks(device_kind)
    t_c = flops_per_chip / pk.bf16_flops
    t_m = bytes_per_chip / pk.hbm_bw
    t_x = wire_bytes_per_chip / pk.ici_link_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    dom = max(t_c, t_m, t_x)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_chip=flops_per_chip, bytes_per_chip=bytes_per_chip,
        wire_bytes_per_chip=wire_bytes_per_chip,
        t_compute=t_c, t_memory=t_m, t_collective=t_x,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=(model_flops / (flops_per_chip * chips)
                      if flops_per_chip > 0 else 0.0),
        peak_fraction=(t_c / dom if dom > 0 else 0.0),
        collectives={k: int(v) for k, v in (collectives or {}).items()},
        memory_per_chip_gb=memory_per_chip / 1e9,
        note=note,
    )


def model_flops_for(arch_cfg, shape_spec) -> float:
    """6·N·D training FLOPs (dense) / 6·N_active·D (MoE); forward-only
    (2·N·D) for prefill; per-token (2·N_active) for decode."""
    n = active_params(arch_cfg)
    if shape_spec.kind == "train":
        return 6.0 * n * shape_spec.global_batch * shape_spec.seq_len
    if shape_spec.kind == "prefill":
        return 2.0 * n * shape_spec.global_batch * shape_spec.seq_len
    return 2.0 * n * shape_spec.global_batch        # one token per stream


def active_params(cfg) -> float:
    """Parameters touched per token (MoE: shared + top_k experts)."""
    total = cfg.n_params()
    if not cfg.n_experts:
        return float(total)
    fe = cfg.moe_d_ff or cfg.d_ff
    mult = 3 if cfg.gated_mlp else 2
    n_moe_layers = cfg.n_layers - cfg.moe_layer_start
    all_experts = cfg.n_experts * mult * cfg.d_model * fe * n_moe_layers
    active_experts = cfg.top_k * mult * cfg.d_model * fe * n_moe_layers
    return float(total - all_experts + active_experts)


def format_table(rows: List[Roofline]) -> str:
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':9s} "
           f"{'t_comp(ms)':>10s} {'t_mem(ms)':>10s} {'t_coll(ms)':>10s} "
           f"{'bound':>10s} {'useful':>7s} {'roofl%':>7s} {'HBM(GB)':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:22s} {r.shape:12s} {r.mesh:9s} "
            f"{r.t_compute*1e3:10.3f} {r.t_memory*1e3:10.3f} "
            f"{r.t_collective*1e3:10.3f} {r.bottleneck:>10s} "
            f"{r.useful_ratio:7.3f} {r.peak_fraction*100:6.1f}% "
            f"{r.memory_per_chip_gb:8.2f}")
    return "\n".join(lines)
