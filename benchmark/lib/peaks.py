"""Published peaks of the chips this benchmark may run on, keyed by
`jax.Device.device_kind`. A device that is not here is an error, never a
default: a utilization against a guessed peak is not a measurement.

Copied in spirit from bench.py's table (sound there; that file is C4's to
remove), with the source of each row.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
    # 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}
# the same chip under the name some runtimes report
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            f"with its source to benchmark/lib/peaks.py") from None
