"""Positions the traced slice's admission calls ran that hold no token, over
the positions they ran: sum of `bucket` less the real positions, over sum of
`bucket`, of the `serve:prefill` and `serve:prefill_chunk` events that began in
the slice. Real is a chunk's `take`, and a prefill's `prompt_len - prefix_hit`
(the prefix cache's blocks are not run again): `perf/lib/annots.py
prefill_calls`.
"""

from perf.lib import annots

UNIT = "%"
LAYER = "model step"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(obs: dict):
    return annots.prefill_pad_pct(obs)
