"""The chips a run uses: the table of published peaks, and the device record."""

from __future__ import annotations

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; have {sorted(PEAKS)}")
    return PEAKS[kind]


def chips(jax, count: int):
    """The first ``count`` TPU devices; raises NoChip rather than use the CPU."""
    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise NoChip(str(e)) from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < count:
        raise NoChip(f"the cell needs {count} chips; JAX found {len(devices)}")
    return devices[:count]


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` so far in this process."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def describe(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
            "memory_peak_bytes": peak_bytes(devices)}
