"""Roofline terms of a step — hardware model is a TPU v5e-like chip:

    compute    = per-device FLOPs / 197 TFLOP/s (bf16)
    memory     = per-device bytes accessed / 819 GB/s HBM
    collective = per-device link egress bytes / 50 GB/s ICI
"""

from __future__ import annotations

import dataclasses

__all__ = ["HW", "roofline_terms"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12  # bf16 FLOP/s per chip
    hbm_bw: float = 819e9  # bytes/s per chip
    link_bw: float = 50e9  # bytes/s per ICI link


def roofline_terms(
    *,
    flops_per_device: float,
    bytes_per_device: float,
    collective_egress: float,
    hw: HW = HW(),
) -> dict:
    compute_s = flops_per_device / hw.peak_flops
    memory_s = bytes_per_device / hw.hbm_bw
    collective_s = collective_egress / hw.link_bw
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    total = sum(terms.values())
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "step_time_lower_bound_s": bound,
        "roofline_fraction": (bound / total) if total > 0 else 0.0,
    }
