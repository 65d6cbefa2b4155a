"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"device kind {device_kind!r} has no entry in the "
                         f"peaks table (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
