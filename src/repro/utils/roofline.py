"""Roofline-term computation from compiled dry-run artifacts.

Hardware model: the per-chip peaks of :data:`PEAKS`, looked up by the
``device_kind`` JAX reports (a kind missing from the table is an error,
never a default).

  compute_term   = HLO_FLOPs       / (chips × flops)
  memory_term    = HLO_bytes       / (chips × hbm_bw)
  collective_term= collective_bytes/ link_bw

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), D = tokens processed in
the step; the MODEL/HLO ratio flags remat- or dispatch-inflated compute.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class Peaks:
    flops: float     # bf16 FLOP/s per chip
    hbm_bw: float    # HBM B/s per chip
    link_bw: float   # B/s per inter-chip link


#: Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links × 50 GB/s).
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_gflops: float          # total across chips
    hlo_gbytes: float
    collective_gbytes: float   # per-chip wire bytes × chips
    compute_term_s: float
    memory_term_s: float
    collective_term_s: float
    dominant: str
    model_gflops: float
    useful_ratio: float        # MODEL_FLOPS / HLO_FLOPs
    bytes_per_chip_gb: float   # peak live memory from memory_analysis
    step_time_bound_s: float   # max of the three terms
    mfu_bound: float           # model_flops / (chips·peak·step_time_bound)

    def row(self) -> str:
        return (
            f"| {self.arch} | {self.shape} | {self.mesh} | "
            f"{self.compute_term_s:.2e} | {self.memory_term_s:.2e} | "
            f"{self.collective_term_s:.2e} | {self.dominant} | "
            f"{self.useful_ratio:.2f} | {self.mfu_bound*100:.1f}% | "
            f"{self.bytes_per_chip_gb:.2f} |"
        )


def build_report(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    flops: float,
    hbm_bytes: float,
    collective_per_chip_bytes: float,
    model_flops: float,
    bytes_per_chip: float,
    device_kind: str,
) -> RooflineReport:
    pk = peaks(device_kind)
    compute_term = flops / (chips * pk.flops)
    memory_term = hbm_bytes / (chips * pk.hbm_bw)
    collective_term = collective_per_chip_bytes / pk.link_bw
    terms = {
        "compute": compute_term,
        "memory": memory_term,
        "collective": collective_term,
    }
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    mfu = (model_flops / (chips * pk.flops * bound)) if bound > 0 else 0.0
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_gflops=flops / 1e9, hlo_gbytes=hbm_bytes / 1e9,
        collective_gbytes=collective_per_chip_bytes * chips / 1e9,
        compute_term_s=compute_term, memory_term_s=memory_term,
        collective_term_s=collective_term, dominant=dominant,
        model_gflops=model_flops / 1e9,
        useful_ratio=(model_flops / flops) if flops else 0.0,
        bytes_per_chip_gb=bytes_per_chip / 1e9,
        step_time_bound_s=bound, mfu_bound=mfu,
    )


def model_flops_for(cfg, shape, n_active: Optional[int] = None) -> float:
    """6·N_active·D with D = tokens processed by the lowered step."""
    n = n_active if n_active is not None else cfg.active_param_count()
    if shape.kind == "decode":
        d = shape.global_batch * 1
        return 2.0 * n * d  # inference fwd only
    d = shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * d
    return 6.0 * n * d  # train: fwd + bwd
