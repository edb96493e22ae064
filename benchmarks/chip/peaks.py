"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind: str, what: str) -> float:
    """One peak of one kind of device; KeyError names what is known."""
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no {what!r} peak for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None
