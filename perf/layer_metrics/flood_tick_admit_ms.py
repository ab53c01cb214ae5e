"""Mean milliseconds a `Scheduler.step()` (one `tick` span) spends in its
`admit` span: the gate, the allocator, the radix tree and every prefill of
the tick.
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.ms_per_tick(obs, "admit")
