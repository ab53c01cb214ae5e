"""Least time the chip could take for what the `gdn_scan` kernel must do for
the REAL prompt tokens admitted in the traced slice, over the kernel's time
there: a Gated DeltaNet layer and token, the larger of its bytes / 819 GB/s
(a value head's rows of the chunk terms read once, its output row written
once) and its own operations / 197 TFLOP/s (three products against the
(128, 128) state and the chunk's mask against the rows d_t: the triangular
inverse is XLA's and not counted), plus the state read and written once a
call. The kernel computes the bucket's padding too and its float32 products
take several passes of the MXU, so the share says how far from the peaks the
carry is; its ceiling is well below 100 (PERF.md section 5 gives the reading).

The prompts counted are those admitted inside the slice and at least
`LAG_S` before its end (`obs["admits"]`, driver `serve_by_leaf_admits`: the
host's clock, and the device runs a prefill after the burst queued before
it), each at its real length; the kernel time is every `gdn_scan` op of the
slice. So the work is a least, never more.
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
        [["gdn_scan", s, d] for s, d in hybrid.kernel_events(
            xtrace.device_planes(trace)[0], "gdn_scan")], t0, t1))
    held = [n for a, b, n in obs["admits"]
            if t0 <= a + off and b + off <= t1 - LAG_S]
    if secs <= 0 or not held:   # no such op, or no prompt in the slice
        return None
    family, cfg, peaks = hybrid.family_of(obs), obs["config"], obs["peaks"]
    a_token = max(family.scan_bytes_per_token(cfg) / peaks["hbm_bytes_s"],
                  family.scan_flops_per_token(cfg) / peaks["bf16_flops_s"])
    least = family.counts(cfg)["G"] * (
        sum(held) * a_token
        + len(held) * 2 * family.ssm_state_bytes(cfg) / peaks["hbm_bytes_s"])
    return 100.0 * least / secs
