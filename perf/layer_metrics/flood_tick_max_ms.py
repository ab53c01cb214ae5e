"""The longest `tick` span before the traced slice closed, in milliseconds: a
stalled `Scheduler.step()` shows here.
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.tick_max_ms(obs)
