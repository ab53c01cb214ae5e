"""Share of chip 0's busy time in the traced slice under NO scope of the
vocabulary: ops without a path, with a path that names no module (the resident
batch gather), and what a parent op runs between its children. The
measurement's own blind spot. Read off each device op's `op_name` path
(`perf/lib/scopes.py`). A share is read, not steered: `better` only says which
way the existing `*_dev_pct` shares point.
"""

from perf.lib import scopes

UNIT = "%"
LAYER = "jitted step"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return scopes.unscoped_pct(obs)
