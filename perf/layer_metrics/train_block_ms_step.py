"""Milliseconds a step spends in the Trainer's `block` spans (the host waits
for the device: the log readback and the fence that closes an epoch), over
the window.
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "train driver"
SOURCE = "program_span"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.span_ms_per_step(obs, "block")
