"""Mean milliseconds a tick spends launching its decode dispatch: the
engine's `burst_plan` (page tables grown, copy-on-write, drafts) plus
`burst_dispatch` (the jitted call returning).
"""

from perf.lib import spans

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "serve_tok_s"


def read(obs: dict):
    return spans.ms_per_tick(obs, "burst_plan", "burst_dispatch")
