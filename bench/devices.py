"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float        # bytes


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


class UnknownDevice(ValueError):
    pass


def peaks_for(platform: str, kind: str) -> Peaks:
    """The peaks of a device, or ``UnknownDevice`` for any device that is
    not a TPU listed in ``PEAKS`` (a CPU among them)."""
    if platform != "tpu":
        raise UnknownDevice(f"platform {platform!r} ({kind!r}) is not a TPU")
    if kind not in PEAKS:
        raise UnknownDevice(f"no published peaks for device kind {kind!r}; "
                            f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
