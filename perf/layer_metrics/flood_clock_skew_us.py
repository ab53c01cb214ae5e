"""Worst distance, in microseconds, between a mirrored `serve:*` annotation
on the profiler's clock and the program span it mirrors moved there by the
run's one clock offset: how far the idle gaps' attribution can be trusted.
"""

from perf.lib import spans

UNIT = "us"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.clock_skew_us(obs, "serve")
