"""Executables JAX built (compiled, or loaded from the persistent cache) inside
the measured window; 0 is expected.
"""

from perf.lib import readers

UNIT = "count"
LAYER = "entry"
SOURCE = "program_counter"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return readers.compiles_in_window(obs)
