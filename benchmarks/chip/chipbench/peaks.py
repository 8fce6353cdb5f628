"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports. A device that is not in the table is an
error, not a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_s": 197e12,
        "int8_op_s": 393e12,
        "hbm_byte_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
