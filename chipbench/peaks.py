"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` that JAX reports.

A device that is not in the table is an error, never a default: a share of
a peak is only meaningful against the chip the run actually held.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s of dense bf16 matrix multiplication
    int8_ops: float  # OP/s of dense int8 matrix multiplication
    hbm_bytes: float  # bytes of device memory
    hbm_bw: float  # bytes/s of device memory bandwidth
    ici_bw: float  # bytes/s of chip-to-chip interconnect, per chip
    source: str


_V5E = Peaks(
    bf16_flops=197e12,
    int8_ops=393e12,
    hbm_bytes=16e9,
    hbm_bw=819e9,
    ici_bw=1600e9 / 8,  # 1,600 Gbit/s
    source="Google Cloud documentation, 'TPU v5e' (system architecture table)",
)

TABLE: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(TABLE)}"
        ) from None
