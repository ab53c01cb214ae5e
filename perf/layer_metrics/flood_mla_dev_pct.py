"""Share of the chip's busy time in the traced slice spent in the absorbed
latent-attention decode kernel (the op named `paged_decode_mla`; prefill,
suffix and chunk calls expand K and V in XLA fusions and are not in it).
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "paged_decode_mla")
