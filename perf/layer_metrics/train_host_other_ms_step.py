"""Milliseconds of host time a step spends inside `train_epoch` and outside
its `data`, `dispatch` and `block` spans: `epoch_open`, `after_group` less
its `block`, and whatever has no name.
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "train driver"
SOURCE = "program_span"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return spans.host_other_ms_per_step(obs)
