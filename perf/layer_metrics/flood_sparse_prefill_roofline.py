"""Least time the chip could take for what `sparse_prefill` must do for the
REAL tokens of the prompt chunks run in the traced slice, over the kernel's
time there: a sparse layer and chunk, the larger of its operations / 197
TFLOP/s (q k and p v over the keys each row attends by the selection's own
rule: all visible ones up to `dense_len`, 64 blocks past it) and its least
bytes / 819 GB/s (q and out once, the context's K and V once). The kernel
also runs a chunk's padding, every key of a page some row of a tile picked
(masked for the rows that did not), and a grid step a list entry past a
tile's count: the share says how far the per-row selection is from free.

The chunks counted are those dispatched inside the slice and at least 0.5 s
before its end (`obs["chunks"]`, driver `serve_long_by_leaf`); the kernel
time is every `sparse_prefill` op of the slice. So the work is a least.
"""

from perf.lib import hybrid, sparse

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    chunks = sparse.chunks_in_slice(obs)
    secs = sparse.kernel_seconds(obs, "sparse_prefill")
    if not secs or not chunks:
        return None
    family, cfg, peaks = hybrid.family_of(obs), obs["config"], obs["peaks"]
    least = family.counts(cfg)["B"] * sum(
        max(family.prefill_flops(cfg, first, n) / peaks["bf16_flops_s"],
            family.prefill_bytes(cfg, first, n) / peaks["hbm_bytes_s"])
        for first, n in chunks)
    return 100.0 * least / secs
