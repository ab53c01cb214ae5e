"""Device time of one prefill program run in the traced slice, median."""

from perf.lib import readers

UNIT = "ms"
LAYER = "model step"
SOURCE = "device_trace"
MOVES = "ttft_p95_ms"


def read(obs: dict):
    return readers.prefill_dev_ms_p50(obs)
