"""The chip: refusing anything but a TPU, naming the device, its peaks and
its memory high-water mark."""
from __future__ import annotations

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (system architecture): 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def require_tpu(chips: int):
    """The TPU devices of this process, or NoAccelerator."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise NoAccelerator(f"JAX backend is {backend!r}, not 'tpu'")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    """The result line's ``device`` object, with the peak memory of the
    fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
