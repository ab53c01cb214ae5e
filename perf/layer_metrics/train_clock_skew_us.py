"""Worst distance, in microseconds, between a mirrored `train:*` annotation on
the profiler's clock and the Trainer span it mirrors moved there by the
run's one clock offset.
"""

from perf.lib import spans

UNIT = "us"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return spans.clock_skew_us(obs, "train")
