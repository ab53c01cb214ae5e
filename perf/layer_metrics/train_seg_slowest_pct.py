"""How much longer the window's slowest segment took than its median segment: a
stall a user would pay for.
"""

UNIT = "%"
LAYER = "train driver"
SOURCE = "host_clock"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return obs["rates"]["slowest_pct"]
