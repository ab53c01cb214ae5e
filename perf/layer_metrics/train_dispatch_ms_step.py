"""Milliseconds a step spends in the Trainer's `dispatch` span (the jitted
step's call returning), over the window.
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "train driver"
SOURCE = "program_span"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.span_ms_per_step(obs, "dispatch")
