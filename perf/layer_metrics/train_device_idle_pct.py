"""1 - union of device op intervals / traced slice, averaged over the chips
used.
"""

from perf.lib import readers

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.device_idle_pct(obs)
