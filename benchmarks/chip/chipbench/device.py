"""The device: what JAX reports, the chip's published peaks, compiles.

``PEAKS`` is the benchmark's own table, keyed by ``jax.Device.device_kind``,
so the yardstick does not move with the program. A kind that is not in it is
an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # bytes/s
    hbm_bytes: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


class NoChip(SystemExit):
    """Raised before any result when JAX finds no TPU, or too few."""


def require_chip(devices, count: int) -> None:
    if not devices or devices[0].platform != "tpu":
        found = sorted({d.platform for d in devices})
        raise NoChip(f"chipbench: no TPU; JAX found {found}")
    if len(devices) < count:
        raise NoChip(f"chipbench: the cell needs {count} TPU chips, JAX "
                     f"found {len(devices)}")


def record(devices, used) -> dict:
    """The result's ``device``: as JAX reports it, and the peak bytes in
    use on the fullest chip of those the cell used."""
    d = devices[0]
    peak = 0
    for dev in used:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileLog:
    """Programs made ready to run (compiled, or loaded from the persistent
    cache), their seconds, and how many were loaded, from JAX's own
    monitoring events. Inside the window there should be none of either."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.n, self.seconds, self.hits = 0, 0.0, 0

        def on_duration(name, secs, **_):
            if name == self.EVENT:
                self.n += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == self.HIT:
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.n, self.seconds, self.hits
