"""Share of the chip's busy time in the traced slice spent in the Mamba-1
layers' two kernels (leaf ops named `sel_*`: `sel_step`, the one-token
recurrence of every decode step, and `sel_scan`, a prompt's scan); their
projections, conv and norms are XLA fusions and are not in it.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "sel_")
