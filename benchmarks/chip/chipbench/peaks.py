"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device missing from the table is an error:
a share of a peak is never computed against a guessed one."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them, "
            f"with their source, to benchmarks/chip/chipbench/peaks.py"
        ) from None
