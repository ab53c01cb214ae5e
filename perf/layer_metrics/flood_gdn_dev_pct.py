"""Share of the chip's busy time in the traced slice spent in the Gated
DeltaNet layers' two kernels (leaf ops named `gdn_*`: `gdn_step`, the
one-token recurrence of every decode step, and `gdn_scan`, a prompt's carry
through its chunks); their projections, conv, norms and the chunk terms of
the scan (the triangular inverse) are XLA fusions and are not in it.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "gdn_")
