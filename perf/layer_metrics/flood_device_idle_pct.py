"""1 - union of device op intervals / traced slice, averaged over the chips
used.
"""

from perf.lib import readers

UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return readers.device_idle_pct(obs)
