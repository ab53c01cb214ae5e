"""Least time to move the live tokens' latent rows (576 useful bf16 values a
token and layer, read ONCE for all heads) plus each slot's absorbed query
and output, in the decode steps of the traced slice, over the time of the
`paged_decode_mla` kernel inside the decode program there: memory-bound,
bytes / 819 GB/s. The pool pads a row to 640 lanes and the kernel reads the
padding, so 90% is the most it can show.

The steps are those of the `decode_burst` runs the DEVICE trace holds and the
bytes of a step the mean over the slice's ticks, so a trace that lost its
tail (seen on the chip, PR 30: a slice of 430k op events came back whole, one
a little longer stopped at 3.8 of 5.9 s) shrinks both sides alike.

The MXU bound beside it: a cached token costs 2 x heads x (576 + 512)
multiply-adds a slot, step and layer (`mla_decode_flops_per_token`), 69.6
kFLOP against 1,152 B = 60 FLOP/B where the chip's ridge is 240; but 32
query rows fill a quarter of a 128-row MXU pass, so the dots of a chunk take
about as long as its copy, and the share says how well the two overlap.
"""

from perf.lib import flops, hybrid, readers

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    got = hybrid.decode_kernel(obs, "paged_decode_mla")
    sl = readers._slice(obs)
    if got is None or sl is None:
        return None
    secs, steps, _ = got
    _, t0, t1, off = sl
    origin, k = obs["t_origin"] + off, obs["burst"]
    live = slot_steps = 0.0
    for tick in obs["ticks"]:
        a = origin + tick["t"]
        if tick["slots"] and t0 <= a and a + tick["dt"] <= t1:
            live += k * tick["live"] + tick["slots"] * k * (k + 1) / 2
            slot_steps += k * tick["slots"]
    if slot_steps == 0:
        return None
    row, q_and_out = obs["decode_bytes"]
    # bytes of one (slot, step), times the slot-steps the device trace holds
    a_slot_step = flops.paged_decode_least_bytes(
        row, q_and_out, live, slot_steps) / slot_steps
    mean_slots = got[2]
    least = steps * mean_slots * a_slot_step / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
