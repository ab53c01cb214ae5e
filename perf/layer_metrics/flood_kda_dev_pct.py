"""Share of the chip's busy time in the traced slice spent in the Kimi Delta
Attention layers' three kernels (leaf ops named `kda_*`: `kda_step`, the
one-token recurrence of every decode step; `kda_terms` and `kda_scan`, a
prompt chunk's terms and its carry through them); their projections, conv,
gates and norms are XLA fusions and are not in it. A program without such
ops: no reading.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return hybrid.kernel_dev_pct(obs, "kda_")
