"""Published peaks of the chips this benchmark may run on, by `device_kind`.

One table, with its source. A device that is not in it is an error, never a
default: a utilization against a guessed peak is not a measurement.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture page: 197 TFLOP/s
# bf16, 16 GB HBM2e at 819 GB/s per chip. jax reports it as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_s": 197e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in PEAKS."""


def lookup(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind.strip()]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in perf/lib/peaks.py "
            f"(have {sorted(PEAKS)}); add its published peaks with their "
            "source before measuring on it"
        ) from None
