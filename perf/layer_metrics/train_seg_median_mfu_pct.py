"""MFU at the MEDIAN segment time: what the steady phases of the window run
at. The cell's end-to-end `train_mfu_pct` is all the work over all the
window's time; where a stall was paid, that reads below this.
"""

UNIT = "%"
LAYER = "train driver"
SOURCE = "host_clock"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return obs["median_mfu_pct"]
