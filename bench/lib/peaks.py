"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A device that is not here is an error, never a default: a share of a peak
that was taken from another chip's table means nothing.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
    # int8, 16 GiB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
