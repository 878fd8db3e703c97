"""Published peaks of the chips the benchmark may run on, keyed by the
runtime's ``device_kind``.  A kind that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s inter-chip interconnect.
The runtime reports such a chip as ``device_kind`` "TPU v5 lite".
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to benchmark/lib/peaks.py") from None
