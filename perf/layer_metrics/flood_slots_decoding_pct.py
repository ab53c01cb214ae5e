"""Share of the traced slice's slot-seconds spent decoding: over the slice's
`serve:tick` events, the tick's `decoding` (slots in its burst, 0 where none
ran) x its duration, over its `slots` (the engine's) x its duration. Weighted by
time, where `flood_decode_slots_mean` weighs every tick alike. The attributes
ride the span's profiler annotation (`perf/lib/annots.py`); None for a program
that hands none over.
"""

from perf.lib import annots

UNIT = "%"
LAYER = "serve host loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(obs: dict):
    return annots.slot_seconds_pct(obs, "decoding")
