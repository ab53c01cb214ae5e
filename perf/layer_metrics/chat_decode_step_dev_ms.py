"""Device time of the decode-burst program in the traced slice, per decode
step.
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "model step"
SOURCE = "device_trace"
MOVES = "ttft_p95_ms"


def read(obs: dict):
    return readers.decode_step_dev_ms(obs)
