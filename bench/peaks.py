"""Published peak rates of the chips the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A kind that is not here is an error:
a roofline or utilization against a guessed peak would mean nothing.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
