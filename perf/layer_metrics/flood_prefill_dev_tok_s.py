"""Real prompt positions a device second of prefill takes in: the real positions
(`flood_prefill_pad_pct`'s) of the admission calls whose `serve:prefill` /
`serve:prefill_chunk` event begins inside the traced slice, over the device
seconds of the programs `jit__prefill_admit` / `jit__prefix_prefill` inside it
("XLA Modules" line, chip 0). Events and programs are in one xplane, on one
clock (`perf/lib/annots.py`).
"""

from perf.lib import annots

UNIT = "tokens/s"
LAYER = "model step"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return annots.prefill_dev_tok_s(obs)
