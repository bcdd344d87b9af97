"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (devices are only enumerated when the mesh is built).

Production target: TPU v5e pods, 256 chips/pod.
  single-pod : (data=16, model=16)                 = 256 chips
  multi-pod  : (pod=2, data=16, model=16)          = 512 chips
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices tests forced."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:n]).reshape(shape), axes)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # bytes/s
    hbm_bytes: float
    ici_link_bw: float          # bytes/s per chip-to-chip link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s
# bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                             ici_link_bw=1600e9 / 8 / 4),
}

# the chip the production meshes above are built from
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; a kind with no published entry
    is an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None
