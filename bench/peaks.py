"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a
utilization against a guessed peak is no measurement.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s ICI
per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,       # FLOP/s
        "hbm_bw": 819e9,            # bytes/s
        "hbm_bytes": 16e9,
        "ici_bw": 1600e9 / 8,       # bytes/s per chip
        "source": "Google Cloud, TPU v5e system architecture",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
