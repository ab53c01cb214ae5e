"""Mean milliseconds a tick spends in `burst_readback`, the host waiting for
the device to hand the burst's tokens back: the device-bound share of the
tick.
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.ms_per_tick(obs, "burst_readback")
