"""Share of the chip's busy time in the traced slice spent in the expert
layers' ops that have a name of their own (leaf ops named `moe_*`: the
`moe_gmm` kernel, in decode and in prefill alike). The router and the combine
are XLA fusions under named scopes and are not in it.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "moe_")
