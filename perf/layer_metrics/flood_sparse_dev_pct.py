"""Share of chip 0's busy time in the traced slice in the block-sparse
attention's own ops: the kernels `sparse_walk` and `sparse_prefill` by name
and the selection's XLA ops by their scope `sparse_select` (the projections,
the cache writes and the output gate are `flood_attn_dev_pct`'s, which holds
this share too). A share is read, not steered.
"""

from perf.lib import readers, sparse, xtrace

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    secs = sparse.named_seconds(obs, "sparse_")
    if not secs:
        return None
    sl = readers._slice(obs)
    return 100.0 * secs / xtrace.busy(sl[0], sl[1], sl[2])["per_chip_s"][0]
