"""Least time the chip could take for what `kda_terms` and `kda_scan` must do
for the REAL tokens of the prompt chunks run in the traced slice, over the
two kernels' time there: a Kimi Delta Attention layer and token, the larger
of its operations / 197 TFLOP/s (the two (C, C) sums over the lower triangle,
the unit-lower inverse as a solve, T against the scaled keys and values, and
the carry's three products against the (128, 128) state: `perf/lib/kda.py
scan_flops_per_token`) and its least bytes / 819 GB/s (q, k, v, g read and
the output row written once), plus the state read and written once a chunk.
The kernels also compute a chunk's padding, the sums over whole squares (four
times, a sub-chunk of rows each), the inverse by blocks as ten matmuls and
every float32 product in several passes of the MXU, and they pass six terms
through HBM between them: the share says how far from the peaks the two are.

The chunks counted are those dispatched inside the slice and at least 0.5 s
before its end (`obs["chunks"]`, driver `serve_state_latent_by_leaf`); the
kernel time is every `kda_terms` and `kda_scan` op of the slice. So the work
is a least, never more.
"""

from perf.lib import hybrid, kda, sparse

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    family = hybrid.family_of(obs)
    chunks = sparse.chunks_in_slice(obs)
    if not chunks or not hasattr(family, "kda_sizes"):
        return None
    secs = (sparse.kernel_seconds(obs, "kda_terms") or 0.0) \
        + (sparse.kernel_seconds(obs, "kda_scan") or 0.0)
    if secs <= 0:   # no such op: the parent's program
        return None
    cfg, peaks = obs["config"], obs["peaks"]
    a_token = max(
        kda.scan_flops_per_token(cfg, family) / peaks["bf16_flops_s"],
        kda.scan_bytes_per_token(cfg, family) / peaks["hbm_bytes_s"])
    least = family.counts(cfg)["K"] * (
        sum(n for _, n in chunks) * a_token
        + len(chunks) * 2 * family.ssm_state_bytes(cfg)
        / peaks["hbm_bytes_s"])
    return 100.0 * least / secs
