"""Device time of one prefill program run in the traced slice, median (the
programs named `jit__prefill_admit` / `jit__prefix_prefill`: the name is a
contract of `serve/engine.py`).
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "model step"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return readers.prefill_dev_ms_p50(obs)
