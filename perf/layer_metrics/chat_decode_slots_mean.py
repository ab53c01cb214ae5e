"""Slots decoding per scheduler tick that ran a burst, mean over the window.
"""

from perf.lib import readers

UNIT = "count"
LAYER = "serve host loop"
SOURCE = "program_counter"
MOVES = "ttft_p95_ms"


def read(obs: dict):
    return readers.decode_slots_mean(obs)
