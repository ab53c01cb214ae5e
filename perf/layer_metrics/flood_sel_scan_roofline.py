"""Least time to move what a prompt's selective scan must through HBM, over
the time of the `sel_scan` kernel in the traced slice: u and dt read once
and y written once for every REAL prompt token (a float32 value a channel
each, B and C beside them), the state read and written once a call, a
Mamba-1 layer; bytes / 819 GB/s. The kernel is bound by its exponentials and
multiply-adds over registers, not by these bytes, it computes the bucket's
padding too, and XLA re-lays its inputs out around it: the share says how
far from memory-bound the scan is, and its ceiling is far below 100
(PERF.md section 5 gives the reading).

The prompts counted are those admitted inside the slice and at least
`LAG_S` before its end (`obs["admits"]`, driver `serve_by_leaf_admits`: the
host's clock, and the device runs a prefill after the burst queued before
it), each at its real length; the kernel time is every `sel_scan` op of
the slice. So the bytes are a least, never more.
"""

from perf.lib import hybrid, readers, xtrace

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"

# a prefill dispatched this long before the slice ends has run inside it
LAG_S = 0.5


def read(obs: dict):
    sl = readers._slice(obs)
    if sl is None or not obs.get("admits"):
        return None
    trace, t0, t1, off = sl
    secs = sum(b - a for a, b in xtrace.clip(
        [["sel_scan", s, d] for s, d in hybrid.kernel_events(
            xtrace.device_planes(trace)[0], "sel_scan")], t0, t1))
    held = [n for a, b, n in obs["admits"]
            if t0 <= a + off and b + off <= t1 - LAG_S]
    if secs <= 0 or not held:   # no such op, or no prompt in the slice
        return None
    family, cfg = hybrid.family_of(obs), obs["config"]
    least = family.counts(cfg)["S"] * (
        sum(held) * family.scan_bytes_per_token(cfg)
        + len(held) * 2 * family.ssm_state_bytes(cfg)
    ) / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
