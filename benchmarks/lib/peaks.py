"""Published peaks of the chips the benchmark has run on, by the
`device_kind` JAX reports.  A device that is not here is an error, never
a default: a share of the wrong peak is worse than no share."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for `device_kind`; raises for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmarks/lib/peaks.py (known: {sorted(PEAKS)}); add a row "
            "with its source, do not default") from None
