"""Hardware constants.

Accelerator peaks live in one table keyed by the ``device_kind`` JAX
reports; a device that is not in the table is an error, never a default.

TPU v5e (the deployment target), from Google Cloud's "TPU v5e"
documentation: 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (four links).

Intel Knights Landing / Xeon Phi 7210 (the paper's evaluation platform,
used by the faithful-reproduction benchmarks):
  64 cores, 6 TFLOP/s fp32 aggregate, MCDRAM up to 400 GB/s, 16 GB capacity.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float       # FLOP/s
    int8_ops: float         # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_link_bytes_per_s: float
    source: str


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9, ici_link_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e' (system architecture)"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip JAX names ``device_kind``; raises for a kind the
    table does not hold."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}") from None


# --- TPU v5e: the deployment target the analytic cost models price ---
_V5E = CHIP_PEAKS["TPU v5 lite"]
TPU_PEAK_FLOPS = _V5E.bf16_flops          # bf16 per chip
TPU_HBM_BW = _V5E.hbm_bytes_per_s         # bytes/s per chip
TPU_ICI_BW = _V5E.ici_link_bytes_per_s    # bytes/s per link (roofline)
TPU_HBM_GB = _V5E.hbm_bytes / 1e9

# --- Paper's KNL (Xeon Phi 7210) ---
KNL_CORES = 64
KNL_PEAK_FLOPS = 6e12          # fp32 aggregate
KNL_FLOPS_PER_CORE = KNL_PEAK_FLOPS / KNL_CORES
KNL_MEM_BW = 400e9             # MCDRAM bytes/s
KNL_MEM_GB = 16.0
KNL_LLC_BYTES = 32e6           # aggregate L2
