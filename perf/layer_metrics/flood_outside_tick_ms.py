"""Mean milliseconds between two consecutive `tick` spans: the caller's loop
around `Scheduler.step()`, here the benchmark's own (intake, bookkeeping).
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.outside_tick_ms(obs)
