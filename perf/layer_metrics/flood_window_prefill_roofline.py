"""Least time the chip could take for what `window_prefill` must do for the
REAL tokens of the prompt chunks run in the traced slice, over the kernel's
time there: over all the attention layers of a chunk, the larger of its
operations / 197 TFLOP/s (q k and p v over the keys each row attends by the
rule: `min(t + 1, window)` in a window layer, `t + 1` in a global one) and its
least bytes / 819 GB/s (q and out once a layer, the K and V some row attends
once). The kernel also runs a chunk's padding up to a whole tile of 128 rows,
every key of a page some row of a tile attends (masked for the rows that do
not: the diagonal's pages and the window's edge) and the tail of a step of
four pages: the share says how far the mask is from free.

The chunks counted are those dispatched inside the slice and at least 0.5 s
before its end (`obs["chunks"]`, driver `serve_window_by_leaf`); the kernel
time is every `window_prefill` op of the slice. So the work is a least.
"""

from perf.lib import hybrid, sparse

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    chunks = sparse.chunks_in_slice(obs)
    secs = sparse.kernel_seconds(obs, "window_prefill")
    if not secs or not chunks:
        return None
    family, cfg, peaks = hybrid.family_of(obs), obs["config"], obs["peaks"]
    if not hasattr(family, "prefill_flops"):
        return None
    least = sum(
        max(family.prefill_flops(cfg, first, n) / peaks["bf16_flops_s"],
            family.prefill_bytes(cfg, first, n) / peaks["hbm_bytes_s"])
        for first, n in chunks)
    return 100.0 * least / secs
