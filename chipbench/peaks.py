"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM2 at 819 GB/s per chip, 197 TFLOP/s bf16, 393 TOP/s int8.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str, table: dict = PEAKS) -> dict:
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(table)}") from None
