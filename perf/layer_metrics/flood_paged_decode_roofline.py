"""Least time to move the live tokens' K and V (plus q and out) over the paged
decode kernel's time: memory-bound, bytes / 819 GB/s.
"""

from perf.lib import readers

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return readers.paged_decode_roofline_pct(obs)
