"""Least time to read the pages the sparse layers' walks were handed in the
decode steps of the traced slice (a page of one KV head is 64 tokens x 128
lanes of K and of V, 32,768 B; plus q and out of the decoding slots), over the
time of the `sparse_walk` kernel inside the slice's `decode_burst` runs:
memory-bound, bytes / 819 GB/s. The pages are the program's own count
(`obs["sparse_bursts"]`: active slots, both KV heads, every sparse layer and
step), so a slot past `dense_len` is charged its 64 picked pages and no more.
"""

from perf.lib import hybrid, sparse

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(obs: dict):
    bursts = sparse.bursts_in_slice(obs)
    got = hybrid.decode_kernel(obs, "sparse_walk")
    if got is None or not bursts:
        return None
    secs, steps, slots = got
    family, cfg = hybrid.family_of(obs), obs["config"]
    runs = steps / obs["burst"]
    walked = runs * sum(b[0] for b in bursts) / len(bursts)
    least = (walked * family.walk_page_bytes(cfg)
             + steps * slots * family.decode_bytes(cfg)[1]) \
        / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / secs
