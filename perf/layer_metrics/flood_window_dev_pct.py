"""Share of chip 0's busy time in the traced slice in the window attention's
own kernels: `window_walk` (a decode step's walk from the window's first page,
the window layers') and `window_prefill` (a prompt chunk's paged flash
attention, every attention layer's, global ones too: one kernel under a
run-time first key). The projections, the rotary and the cache writes are
`flood_attn_dev_pct`'s, which holds this share too. A share is read, not
steered.
"""

from perf.lib import readers, sparse, xtrace

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    secs = sparse.named_seconds(obs, "window_")
    if not secs:
        return None
    sl = readers._slice(obs)
    return 100.0 * secs / xtrace.busy(sl[0], sl[1], sl[2])["per_chip_s"][0]
