"""Share of the traced slice's slot-seconds held by requests still taking in
their prompt: over the slice's `serve:tick` events, the tick's `prefilling`
(running slots mid-prefill as the tick ends) x its duration, over its `slots` x
its duration (`perf/lib/annots.py`). Only a cell whose traffic file sets
`prefill_chunk` has such slots. A share is read, not steered.
"""

from perf.lib import annots

UNIT = "%"
LAYER = "serve host loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(obs: dict):
    return annots.slot_seconds_pct(obs, "prefilling")
