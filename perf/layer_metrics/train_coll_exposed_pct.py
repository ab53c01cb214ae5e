"""Share of the traced slice in which a collective runs on a chip and nothing
else runs there.
"""

from perf.lib import readers

UNIT = "%"
LAYER = "mesh, collectives"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.coll_exposed_pct(obs)
