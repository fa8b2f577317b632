"""Published peaks by exact ``device_kind``, and the operations and bytes
a histogram build needs. Copied from ``lightgbm_tpu/telemetry/costmodel.py``
(``TPU_PEAKS``, ``analytical_hist_counts``) so that a later change to the
program cannot move the yardstick."""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple


class ChipPeaks(NamedTuple):
    kind: str
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2e at 819 GB/s. jax 0.9.0
# with libtpu 0.0.34 reports such a chip as device_kind "TPU v5 lite".
PEAKS: Dict[str, ChipPeaks] = {p.kind: p for p in (
    ChipPeaks("TPU v5 lite", 197e12, 393e12, 819e9, 16e9),
)}

HIST_CH = 3      # gradient, hessian, count


def peaks_for(kind: str) -> ChipPeaks:
    """A device that is not in the table is an error, not a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise LookupError(f"no published peaks for device_kind {kind!r} "
                          f"(known: {sorted(PEAKS)})") from None


def hist_counts(rows: int, cols: int, bins: int, leaves: int
                ) -> Tuple[float, float]:
    """(operations, bytes) one histogram build needs, by the program's
    own formulation: the one-hot matmul on the MXU, 2·R·(F·B)·(L·3), and
    the streams that cannot be avoided (uint8 bins and float32
    gradient/hessian/count in, float32 histogram out). ``leaves`` is the
    number of leaves the call builds for, not the lanes the kernel pads
    to, so padding never counts as work done."""
    ops = 2.0 * rows * (cols * bins) * (leaves * HIST_CH)
    byts = rows * cols + rows * HIST_CH * 4 + cols * bins * leaves * HIST_CH * 4
    return ops, float(byts)


def roofline_seconds(ops: float, byts: float, peaks: ChipPeaks,
                     int8: bool = False) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / (peaks.int8_ops if int8 else peaks.bf16_flops)
    t_bytes = byts / peaks.hbm_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
