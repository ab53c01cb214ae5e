"""Mean milliseconds a tick spends in `deliver`: requeueing what was
preempted, the row loop that hands tokens to requests, finishing, chunk
emission, the metrics hook.
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.ms_per_tick(obs, "deliver")
