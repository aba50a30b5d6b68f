"""The chip's peaks, and the work the algorithm needs, for the rooflines.

A roofline share is the least time the chip could take for the work,
``max(flops / peak_flops, bytes / peak_bytes_per_s)``, over the time the
kernel took. The work is the algorithm's over valid rows, never the padded
buffer's or the kernel's arguments, so a change that compacts a buffer or
replaces a kernel reads against the same work.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # FLOP/s, bf16 on the MXU
    hbm_bw: float   # bytes/s


#: Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
#: at 819 GB/s.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def min_seconds(self, pk: Peaks) -> float:
        return max(self.flops / pk.flops, self.bytes / pk.hbm_bw)

    def bound(self, pk: Peaks) -> str:
        return ("compute" if self.flops / pk.flops >= self.bytes / pk.hbm_bw
                else "memory")


def knn_work(level_valid: Sequence[int], d: int, k: int) -> Work:
    """The kNN graphs of a fit: at each level, every valid point against
    every valid point, 2·v²·d FLOP; the v·d f32 points read once and the
    v·k neighbour ids and distances written once."""
    flops = sum(2.0 * v * v * d for v in level_valid)
    nbytes = sum(4.0 * v * d + 8.0 * v * k for v in level_valid)
    return Work(flops, nbytes)


def lloyd_work(p: int, k: int, d: int, iters: int) -> Work:
    """The nearest-centre passes of a k-means fit after its seeding: one in
    each of its ``iters`` Lloyd iterations and one for the final labels,
    each every valid prototype against every centre, 2·p·k·d FLOP; the
    p·d f32 prototypes and k·d centres read once and the p labels written
    once a pass."""
    passes = iters + 1
    return Work(passes * 2.0 * p * k * d, passes * 4.0 * (p * d + k * d + p))


def roofline_percent(work: Work, kernel_seconds: float, pk: Peaks):
    """Share of the roofline in percent; None where the kernel never ran
    or there are no peaks to hold it against."""
    if pk is None or kernel_seconds <= 0 or work.flops <= 0:
        return None
    return 100.0 * work.min_seconds(pk) / kernel_seconds
