"""Least time to stream, once each, the three matrices of every expert that
HAD a row, in every expert layer and decode step of the traced slice, over
the time of the `moe_gmm_glu` kernel inside the decode program there:
memory-bound, bytes / 819 GB/s.

How many experts had a row is what the decode program counted (the engine's
`last_burst_experts`, handed over by the driver `serve_by_leaf`), as for
`flood_moe_gmm_roofline`. No count, no kernel, or a family without
`expert_bytes`: no reading.
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
    got = hybrid.decode_kernel(obs, "moe_gmm_glu")
    touched = hybrid.experts_touched_a_step(obs)
    if got is None or touched is None:
        return None
    secs, steps, _ = got
    least = steps * touched * family.expert_bytes(obs["config"]) \
        / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
