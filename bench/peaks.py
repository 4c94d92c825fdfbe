"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

The benchmark keeps its own table so that the yardstick stays where it
is when the program's cost model moves.  A device missing here is an
error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_per_s: float        # dense bf16 matrix operations
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_s(flops: float, nbytes: float, p: Peaks):
    """The least time the chip could take for ``flops`` and ``nbytes``,
    and which of the two bounds it (``"memory"`` or ``"compute"``)."""
    t_mem = nbytes / p.hbm_bytes_per_s
    t_cmp = flops / p.flops_per_s
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
