"""Milliseconds a step spends in the Trainer's own `data` span (placing the
step's rows), over the window.
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "train driver"
SOURCE = "program_span"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.span_ms_per_step(obs, "data")
