"""Least time to read the pages the window layers' walks read in the decode
steps of the traced slice (a page of one KV head is 64 tokens x 128 lanes of K
and of V, 32,768 B, and a walk reads a page of every KV head; plus q and out
of the decoding slots), over the time of the `window_walk` kernel inside the
slice's `decode_burst` runs: memory-bound, bytes / 819 GB/s. The pages are
the program's own count (`obs["window_bursts"]`: active slots, every window
layer and step, from the page of the window's first key to the query's own),
so a slot whose context passed the window is charged its window and no more.
"""

from perf.lib import hybrid, window

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    bursts = window.bursts_in_slice(obs)
    got = hybrid.decode_kernel(obs, "window_walk")
    if got is None or not bursts:
        return None
    secs, steps, slots = got
    family, cfg = hybrid.family_of(obs), obs["config"]
    runs = steps / obs["burst"]
    walked = runs * sum(b[0] for b in bursts) / len(bursts)
    least = (walked * cfg["num_key_value_heads"]
             * family.walk_page_bytes(cfg, obs["page"])
             + steps * slots * family.window_q_and_out_bytes(cfg)) \
        / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
