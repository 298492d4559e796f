"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default. (Copied from ``nnstreamer_tpu/utils/hw.py``'s v5e row so that a
later PR cannot move the yardstick; the original stays for the program's
own use.)"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} - add a row with its source") from None
