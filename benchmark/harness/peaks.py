"""Published peaks of the chips the benchmark may run on, by the
`device_kind` JAX reports. A device that is not here is an error, never a
default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py with its source"
        )
    return PEAKS[device_kind]
