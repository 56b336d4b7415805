"""Three-term roofline from a compiled dry-run artifact (brief §ROOFLINE).

    compute term    = HLO_FLOPs            / (chips × peak_FLOP/s)
    memory term     = HLO_bytes            / (chips × HBM_bw)
    collective term = collective_bytes     / (chips × link_bw)

``cost_analysis()`` of an SPMD executable reports the *per-device* module,
so per-device quantities divided by per-chip rates give exactly the same
seconds as the global formulation above; both views are recorded.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (brief-supplied).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from .hlo import CollectiveStats, parse_collectives, profile_module


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12        # bf16 per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    link_bw: float = 50e9             # bytes/s per ICI link
    hbm_bytes: float = 16e9           # v5e HBM capacity


V5E = HW()


def model_flops(cfg, shape) -> int:
    """Useful (model) FLOPs per step: 6·N·D train, 2·N·D forward-only,
    with N = active params (MoE: experts scaled by top_k/E)."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2 * n * tokens
    # decode: one token per sequence
    return 2 * n * shape.global_batch


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    # per-device quantities from the compiled artifact (scan-aware profile)
    flops_per_device: float
    bytes_per_device: float
    coll_operand_bytes: int
    coll_wire_bytes: int
    # memory_analysis
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    # model-level
    model_flops_total: int
    by_kind: dict
    # raw XLA cost_analysis numbers (cross-check; while bodies counted ×1)
    flops_xla_raw: float = 0.0
    bytes_xla_raw: float = 0.0
    mxu_flops_per_device: float = 0.0
    # CPU-backend bf16->f32 upcast artifacts (absent on the TPU target);
    # memory/traffic are reported TPU-adjusted, raw kept for audit
    cpu_upcast_bytes: float = 0.0
    cpu_upcast_traffic: float = 0.0
    alias_bytes: int = 0           # donated-buffer aliasing (in==out)
    hw: HW = V5E

    # -- derived terms (seconds) ---------------------------------------------
    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.hw.peak_flops

    @property
    def memory_s(self) -> float:
        adj = max(self.bytes_per_device - self.cpu_upcast_traffic, 0.0)
        return adj / self.hw.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_operand_bytes / self.hw.link_bw

    @property
    def collective_wire_s(self) -> float:
        return self.coll_wire_bytes / self.hw.link_bw

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step-time model: max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def hlo_flops_total(self) -> float:
        return self.flops_per_device * self.n_devices

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return (self.model_flops_total / self.hlo_flops_total
                if self.hlo_flops_total else 0.0)

    @property
    def mfu(self) -> float:
        """Model FLOPs over the roofline step time × fleet peak — the
        roofline fraction the brief scores (perfect overlap assumed)."""
        denom = self.step_s * self.n_devices * self.hw.peak_flops
        return self.model_flops_total / denom if denom else 0.0

    @property
    def device_bytes(self) -> int:
        """TPU-adjusted per-device bytes: XLA-CPU's fp32 upcasts of bf16
        params/caches don't exist on the MXU target, and donated buffers
        alias their outputs."""
        raw = (self.argument_bytes + self.output_bytes + self.temp_bytes
               - self.alias_bytes)
        return int(max(raw - self.cpu_upcast_bytes, self.argument_bytes))

    @property
    def fits(self) -> bool:
        return self.device_bytes <= self.hw.hbm_bytes

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k in (
            "arch", "shape", "mesh", "n_devices", "flops_per_device",
            "bytes_per_device", "coll_operand_bytes", "coll_wire_bytes",
            "flops_xla_raw", "bytes_xla_raw", "mxu_flops_per_device",
            "cpu_upcast_bytes", "cpu_upcast_traffic", "alias_bytes",
            "argument_bytes", "output_bytes", "temp_bytes",
            "model_flops_total")}
        d["by_kind"] = {k: list(v) for k, v in self.by_kind.items()}
        for k in ("compute_s", "memory_s", "collective_s",
                  "collective_wire_s", "bound", "step_s", "useful_ratio",
                  "mfu", "device_bytes", "fits"):
            d[k] = getattr(self, k)
        return d

    def row(self) -> str:
        return (f"{self.arch:<22} {self.shape:<12} {self.mesh:<6} "
                f"c={self.compute_s:9.4f}s m={self.memory_s:9.4f}s "
                f"x={self.collective_s:9.4f}s -> {self.bound:<10} "
                f"useful={self.useful_ratio:6.3f} mfu={self.mfu:6.3%} "
                f"mem={self.device_bytes / 1e9:6.2f}GB"
                f"{'' if self.fits else ' OVER'}")


def roofline_from_compiled(compiled, *, arch: str, shape, mesh_name: str,
                           n_devices: int, cfg, hw: HW = V5E,
                           hlo_text: Optional[str] = None) -> RooflineReport:
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    prof = profile_module(txt, n_devices)
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=prof.flops,
        bytes_per_device=prof.traffic_bytes,
        coll_operand_bytes=int(prof.operand_bytes),
        coll_wire_bytes=int(prof.wire_bytes),
        flops_xla_raw=float(ca.get("flops", 0.0)),
        bytes_xla_raw=float(ca.get("bytes accessed", 0.0)),
        mxu_flops_per_device=prof.mxu_flops,
        cpu_upcast_bytes=prof.cpu_upcast_bytes,
        cpu_upcast_traffic=prof.cpu_upcast_traffic,
        argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        alias_bytes=int(getattr(ma, "alias_size_in_bytes", 0)),
        model_flops_total=model_flops(cfg, shape),
        by_kind=prof.by_kind, hw=hw)


# alias used by drivers that already hold the pieces
def roofline_report(**kw) -> RooflineReport:
    return RooflineReport(**kw)


def load_reports(path: str) -> list:
    """Read the dry-run JSONL back into dict rows."""
    rows = []
    with open(path) as f:
        for line in f:
            if line.strip():
                rows.append(json.loads(line))
    return rows
