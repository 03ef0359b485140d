"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax import (repro/launch/dryrun.py) and only then
builds the mesh.

Topology: TPU v5e pod slices.  Single pod: 16×16 = 256 chips as
(data=16, model=16).  Multi-pod: 2 pods × 256 = 512 chips as
(pod=2, data=16, model=16) — gradient/GLA reductions cross pods over DCI on
the `pod` axis; model parallelism never leaves a pod.
"""
from __future__ import annotations

from typing import NamedTuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(devices: int = 8, axes=("data",)):
    """Small mesh over however many (possibly fake) devices exist."""
    n = len(jax.devices())
    use = min(devices, n)
    shape = (use,) if len(axes) == 1 else (use // 2, 2)
    return jax.make_mesh(shape, axes)


class ChipPeaks(NamedTuple):
    """Published peaks of one chip (roofline denominators)."""

    flops_bf16: float   # FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # chip-to-chip interconnect bytes/s, all links


# Keyed by ``jax.Device.device_kind``.  "TPU v5 lite" is TPU v5e; source:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw=1600e9 / 8),
}
V5E = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one ``device_kind`` chip.  A kind with no published row is
    an error — never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
