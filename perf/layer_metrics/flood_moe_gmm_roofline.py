"""Least time to stream, once each, the two matrices of every held expert
that HAD a row, in every expert layer and decode step of the traced slice,
over the time of the `moe_gmm` kernel inside the decode program there:
memory-bound, bytes / 819 GB/s.

How many experts had a row is what the decode program counted (the engine's
`last_burst_experts`, handed over by the driver `serve_by_leaf`), not an
assumption: with random weights the tokens of a batch pick alike, and
"every held expert once a step" read 107% of the roofline on the chip (PR 26,
ISSUE 26's formula; it also asked for None under 96 decoding slots, which a
count makes needless). No count, no reading.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    family = hybrid.family_of(obs)
    if not hasattr(family, "expert_bytes"):
        return None
    got = hybrid.decode_kernel(obs, "moe_gmm")
    touched = hybrid.experts_touched_a_step(obs)
    if got is None or touched is None:
        return None
    secs, steps, _ = got
    least = steps * touched * family.expert_bytes(obs["config"]) \
        / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
