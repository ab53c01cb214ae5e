"""Least time to read and write the matrix state of the DECODING slots in
every Gated DeltaNet layer and decode step of the traced slice, over the time
of the `gdn_step` kernel inside the decode program there: memory-bound, bytes
/ 819 GB/s. A true least: the kernel rewrites every slot's state, decoding or
not, and also reads q, k, v, the decay and beta (under 1% of the state's
bytes); it is charged for the decoding slots' state (the ticks' `slots`)
alone.
"""

from perf.lib import hybrid

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    got = hybrid.decode_kernel(obs, "gdn_step")
    if got is None:   # no such op: another family's program, or the parent
        return None
    secs, steps, slots = got
    family, cfg = hybrid.family_of(obs), obs["config"]
    least = steps * slots * family.counts(cfg)["G"] \
        * 2 * family.ssm_state_bytes(cfg) / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
