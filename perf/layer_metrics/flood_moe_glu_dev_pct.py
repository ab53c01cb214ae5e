"""Share of the chip's busy time in the traced slice spent in the gated
expert kernel (the op named `moe_gmm_glu`, in decode and in prefill alike).
The router, the combine and the shared expert are XLA fusions and are not in
it.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "moe_gmm_glu")
