"""Share of chip 0's busy time in the traced slice under the backward pass: every
op whose scope path holds `transpose(`, a rematerialised forward inside it
too. Read off each device op's `op_name` path (`perf/lib/scopes.py`). A share
is read, not steered: `better` only says which way the existing `*_dev_pct`
shares point.
"""

from perf.lib import scopes

UNIT = "%"
LAYER = "jitted step"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return scopes.direction_pct(obs, "bwd")
