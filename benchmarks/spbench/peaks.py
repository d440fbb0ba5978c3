"""Published peaks of the chips this benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (one chip): 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s. A kind that is not in
the table is refused; there is no default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,          # bf16 MXU peak; no f32 figure is published
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(PEAKS)}"
                            ) from None
