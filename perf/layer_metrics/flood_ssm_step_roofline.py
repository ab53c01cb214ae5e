"""Least time to read and write the recurrent state of the DECODING slots in
every Mamba layer and decode step of the traced slice, over the time of the
`ssm_step` kernel there: memory-bound, bytes / 819 GB/s. A true least: the
kernel rewrites every slot's state, decoding or not, and is charged for the
decoding ones (the ticks' `slots`) alone.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    family = hybrid.family_of(obs)
    if not hasattr(family, "ssm_state_bytes"):
        return None
    got = hybrid.decode_kernel(obs, "ssm_step")
    if got is None:
        return None
    secs, steps, slots = got
    cfg = obs["config"]
    least = steps * slots * family.counts(cfg)["M"] \
        * 2 * family.ssm_state_bytes(cfg) / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
