"""Share of chip 0's busy time in the traced slice under the recurrent mixers: in
and out projections, the conv and the recurrence kernels (`mamba*`, `ssm_*`,
`sel_*`). Read off each device op's `op_name` path (`perf/lib/scopes.py`). A
share is read, not steered: `better` only says which way the existing
`*_dev_pct` shares point.
"""

from perf.lib import scopes

UNIT = "%"
LAYER = "model step"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    return scopes.class_pct(obs, "mixer")
