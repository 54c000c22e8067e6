"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s
chip-to-chip interconnect. Copied from ``scripts/model_benches.py:PEAK_BF16``
with bytes/s and memory added.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes
    ici_bytes_s: float     # bytes/s, chip to chip


_V5E = Peak(197e12, 819e9, 16e9, 1600e9 / 8)

PEAKS = {
    "TPU v5 lite": _V5E,   # what jax 0.9.0 / libtpu 0.0.34 reports for a v5e
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"benchmark/harness/peaks.py with its source") from None
