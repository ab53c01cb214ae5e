"""Prompt tokens the radix cache already held at admission, over the prompt
tokens admitted inside the traced slice: `prefix_hit` over `prompt_len` of the
slice's `serve:prefill` events and `serve:chunk_admit` instants, one event an
admission (a chunked prompt's `prefill_chunk` spans repeat the hit and are not
counted): `perf/lib/annots.py prefix_hit_pct`.
"""

from perf.lib import annots

UNIT = "%"
LAYER = "serve host loop"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(obs: dict):
    return annots.prefix_hit_pct(obs)
