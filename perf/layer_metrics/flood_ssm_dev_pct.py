"""Share of the chip's busy time in the traced slice spent in the recurrent
layers' ops that have a name of their own (leaf ops named `ssm_*`: the
`ssm_step` kernel of decode; a prompt's chunked scan is XLA fusions under the
scope `ssm_scan` and is not in it).
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "ssm_")
