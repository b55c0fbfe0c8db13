"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

A device that is not here is an error, never a default.
"""
from __future__ import annotations

from .loader import BenchError

# Google Cloud documentation, "TPU v5e" system architecture page: 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s interconnect a chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind, what):
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise BenchError("no published %s for device kind %r in peaks.py"
                         % (what, device_kind)) from None
