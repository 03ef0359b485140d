"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" ("TPU v5 lite" is its
device kind): 197 TFLOP/s in bfloat16, HBM at 819 GB/s.  A kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float   # FLOP/s
    hbm_bytes_per_s: float


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
