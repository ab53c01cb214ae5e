"""Share of chip 0's busy time in the traced slice under the normalisation
layers, forward and backward (`ln1`, `ln2`, `ln_f`, `norm*`), as far as XLA
left them ops of their own: statistics fused into a matmul's epilogue read
that matmul's scope. Read off each device op's `op_name` path
(`perf/lib/scopes.py`). A share is read, not steered: `better` only says which
way the existing `*_dev_pct` shares point.
"""

from perf.lib import scopes

UNIT = "%"
LAYER = "jitted step"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return scopes.class_pct(obs, "norm")
