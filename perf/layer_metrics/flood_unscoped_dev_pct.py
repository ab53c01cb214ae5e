"""Share of chip 0's busy time in the traced slice under NO scope of the
vocabulary: ops without a path (a copy of an undonated pool), the engine's own
bookkeeping (a prompt's pages placed in the pool), and what a parent op runs
between its children. The measurement's own blind spot. Read off each device
op's `op_name` path (`perf/lib/scopes.py`). A share is read, not steered:
`better` only says which way the existing `*_dev_pct` shares point.
"""

from perf.lib import scopes

UNIT = "%"
LAYER = "model step"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return scopes.unscoped_pct(obs)
