"""The chip a run measures: its published peaks, the device check, and the
device record of the result line.

Peaks are keyed by ``jax.devices()[0].device_kind``.  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s.  A kind missing from the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(SystemExit):
    """Raised when the run has no accelerator it can measure."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise NoChip(f"no published peaks for device kind {device_kind!r} "
                     f"(known: {sorted(PEAKS)})") from None


def require_chip(chips: int) -> dict:
    """The device record for the result line; raises ``NoChip`` unless JAX
    sees at least ``chips`` TPU chips of a kind in ``PEAKS``."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found "
                     f"{len(devices)}")
    peaks(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    peak = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def bytes_in_use() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0))
