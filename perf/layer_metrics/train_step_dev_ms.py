"""Device time of one run of the train-step program in the traced slice (median
over its runs).
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "jitted step"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.step_dev_ms(obs)
