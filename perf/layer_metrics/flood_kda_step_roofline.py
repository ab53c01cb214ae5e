"""Least time to read and write the matrix state of the DECODING slots in
every Kimi Delta Attention layer and decode step of the traced slice, over
the time of the `kda_step` kernel inside the decode program there:
memory-bound, bytes / 819 GB/s (`perf/lib/kda.py step_bytes`). A true least,
as `flood_gdn_step_roofline`'s: the kernel rewrites every slot's state,
decoding or not, and also reads q, k, v, the decay a key lane and beta; it
is charged for the decoding slots' state (the ticks' `slots`) alone.
"""

from perf.lib import hybrid, kda

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    family = hybrid.family_of(obs)
    got = hybrid.decode_kernel(obs, "kda_step")
    if got is None or not hasattr(family, "kda_sizes"):
        return None   # no such op: another family's program, or the parent
    secs, steps, slots = got
    least = steps * slots * kda.step_bytes(obs["config"], family) \
        / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
