"""Target hardware model: TPU v5e pod (the simulation/roofline substrate).

All DistSim analytical event times and every roofline term derive from
these constants. They are published peaks of the target chip, not
measurements (Google Cloud documentation, "TPU v5e"); times measured on
the chip come from ``MeasuredProvider`` and ``chip_smoke.py``:

    197 TFLOP/s bf16 per chip; 819 GB/s HBM; ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12          # FLOP/s per chip
    hbm_bw: float = 819e9                    # bytes/s
    hbm_bytes: float = 16e9                  # HBM capacity per chip
    vmem_bytes: float = 128 * 2 ** 20        # ~128 MiB VMEM
    ici_link_bw: float = 50e9                # bytes/s per ICI link (one dir)
    ici_links_per_axis: int = 2              # bidirectional ring → 2 links
    dcn_bw: float = 25e9                     # bytes/s per host inter-pod (DCN)
    mxu_dim: int = 128                       # systolic array side
    # launch/fusion fixed overhead per HLO op (s). Calibratable.
    op_overhead: float = 2e-6
    # collective latency term per hop (s)
    ici_hop_latency: float = 1e-6
    dcn_latency: float = 25e-6


V5E = ChipSpec()


def mxu_efficiency(m: int, n: int, k: int, spec: ChipSpec = V5E) -> float:
    """Fraction of peak a GEMM of logical dims (m,n,k) achieves.

    TPU systolic arrays lose throughput when dims are not multiples of the
    MXU tile and when the surface-to-volume ratio is bad (small dims).
    This simple two-factor model is the analytical provider's efficiency
    curve; MeasuredProvider replaces it with real timings.
    """
    d = spec.mxu_dim

    def align(x: int) -> float:
        if x >= d:
            full = (x // d) * d
            return max(full / x, 0.75)        # ragged tail wastes a tile
        return max(x / d, 0.05)               # under-filled systolic array

    a = align(m) * align(n) * align(k)
    # small-matrix pipeline fill/drain penalty
    depth = min(m, n, k)
    fill = depth / (depth + d)
    return max(0.04, min(0.95, a * (0.5 + 0.5 * fill) * 0.85))
