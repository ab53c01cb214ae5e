"""Time the grouped page walk (`ops/decode_attention.py _paged_walk_kernel`
through `paged_decode_attention`) alone on the chip at the four grouped
cells' shapes, for chunks of 128 / 256 / 512 / 1,024 tokens and for the
chunk the module's own rule picks.

    python experiments/paged_walk_time.py [cell ...]   # default: all four

A shape is read from the cell's own files (`perf/configs/*.json`: query
heads, KV heads, head_dim; `perf/traffic/*.json` `engine`: page, slots,
table columns, pool pages). A case is one (shape, context, chunk): every
fourth slot retired as the engine leaves it (table row 0, length 0), the
others at `context` tokens, a few tokens apart so that they end at different
rows of a page, their pages scattered over the pool. Contexts: 100, 500,
1,000, 2,000 and 15,000 tokens from position 0 (`paged_decode`), and 4,096
tokens that END at the table's last page (`window_walk`: a window layer past
its window starts mid-table, mid-page); a context the shape's table cannot
hold is left out. `rule` is `_pages_per_chunk` as the module has it; the other
chunks replace that function for the case (P = tokens / page).

A row: `kernel_us` the device time of the ONE op a call, from a profiler
trace of 10 calls; `us_a_slot` that over all slots; `roofline_pct` the live
tokens' K and V rows plus q and out, over 819 GB/s, over that time (what
`flood_paged_decode_roofline` and `flood_window_walk_roofline` divide);
`compile_s` lowering and compiling the call on this host (what a longer
unrolled chunk costs the warm-up); `err` the largest difference from the
gather reference over the first four slots. One JSON line a case; the table
goes to chiprun_out/paged_walk_time[_tag].json a row at a time (PERF.md
section 6, PR 45). Copied into an older checkout's `experiments/` it times
that module (its `rule` is then its 128 tokens).
"""
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ddp_practice_tpu.ops import decode_attention as da  # noqa: E402

CELLS = {
    # cell: (config, traffic)
    "smallthinker": ("smallthinker_21b_pp7", "short_long_s32"),
    "qwen3next": ("qwen3next_80b_ep4", "mixed_flood_s128"),
    "nemo3s": ("nemotron3_super_ep4", "reason_flood_s128"),
    "jamba2": ("jamba2_3b", "batch_flood_s256"),
}
CHUNKS = (128, 256, 512, 1024)
CONTEXTS = (100, 500, 1000, 2000, "4096_mid", 15000)
HBM_BYTES_S = 819e9          # perf/lib/peaks.py, one v5e chip
RULE = da._pages_per_chunk


def shape_of(cell):
    config, traffic = CELLS[cell]
    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "perf", "traffic", traffic + ".json")) as f:
        e = json.load(f)["engine"]
    heads = c["num_attention_heads"]
    return dict(cell=cell, heads=heads, kvh=c["num_key_value_heads"],
                d=c.get("head_dim") or c["hidden_size"] // heads,
                page=e["page"], slots=e["max_slots"],
                columns=e["max_blocks_per_slot"], pool=e["num_blocks"])


def inputs(s):
    """q, the two pools (as ARGUMENTS of every call: closed over they would
    be constants of every program), and a table of scattered pages."""
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(45), 4)
    row = s["kvh"] * s["d"]
    q = jax.random.normal(k0, (s["slots"], 1, s["heads"] * s["d"]),
                          jnp.bfloat16)
    kp = jax.random.normal(k1, (s["pool"], s["page"], row), jnp.bfloat16)
    vp = jax.random.normal(k2, (s["pool"], s["page"], row), jnp.bfloat16)
    table = np.asarray(jax.random.permutation(k3, jnp.arange(1, s["pool"]))[
        :s["slots"] * s["columns"]]).reshape(s["slots"], s["columns"])
    return q, kp, vp, table


def spans(s, context):
    """(lengths, starts, retired, op name) of a case, or None where the
    table is too short: every fourth slot retired, the others `context`
    tokens."""
    held = s["columns"] * s["page"]
    slot = np.arange(s["slots"])
    if context == "4096_mid":
        if held < 4096 + 2 * s["page"]:
            return None
        lengths = held - 1 - 3 * (slot % 8)
        starts, name = lengths + 1 - 4096, "window_walk"
    else:
        if context > held:
            return None
        lengths = context - 1 - 3 * (slot % 8)
        starts, name = np.zeros_like(lengths), "paged_decode"
    retired = slot % 4 == 3
    return (np.where(retired, 0, lengths).astype(np.int32),
            np.where(retired, 0, starts).astype(np.int32), retired, name)


def kernel_us(fn, args, name, calls=10):
    """Device us a call of the ops whose own name (an event reads
    "%name.1 = ... operands": a consumer holds the name too) holds `name`,
    as the benchmark's readers sum them, from a trace."""
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        total, seen = 0.0, 0
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    mine = [e.duration_ns for e in line.events
                            if name in e.name.split(" = ")[0]]
                    total, seen = total + sum(mine), seen + len(mine)
    if seen != calls:
        raise RuntimeError(f"{seen} ops named {name} in {calls} calls")
    return total / calls / 1e3


def case(s, data, context, chunk, rows):
    q, kp, vp, table = data
    span = spans(s, context)
    if span is None:
        return
    lengths, starts, retired, name = span
    pages = None if chunk == "rule" else chunk // s["page"]
    if pages is not None and (
            pages < 1 or pages > s["columns"]
            or 4 * chunk * s["kvh"] * s["d"] * 2 > da._CHUNK_VMEM_BYTES):
        return
    seen = []

    def pick(*a, **k):
        seen.append(RULE(*a, **k) if pages is None else pages)
        return seen[-1]

    da._pages_per_chunk = pick
    kw = dict(n_heads=s["heads"], n_kv_heads=s["kvh"])
    pt = jnp.asarray(np.where(retired[:, None], 0, table), jnp.int32)
    args = (q, kp, vp, pt, jnp.asarray(lengths), jnp.asarray(starts))
    t0 = time.perf_counter()
    run = jax.jit(lambda *a: da.paged_decode_attention(
        *a, **kw, name=name)).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    da._pages_per_chunk = RULE
    got = run(*args)
    want = jax.jit(lambda *a: da.paged_attention_reference(*a, **kw))(
        *(a[:4] if a.shape[0] == s["slots"] else a for a in args))
    err = float(jnp.abs(got[:4].astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    us = kernel_us(run, args, name)
    live = int(np.sum((lengths - starts + 1)[~retired]))
    moved = live * s["kvh"] * s["d"] * 2 * 2 + 2 * q.size * 2
    row = {"cell": s["cell"], "context": context, "chunk": chunk,
           "pages": seen[0], "tokens": seen[0] * s["page"], "op": name,
           "kernel_us": round(us, 2),
           "us_a_slot": round(us / s["slots"], 3),
           "roofline_pct": round(moved / HBM_BYTES_S / (us * 1e-6) * 100, 2),
           "compile_s": round(compile_s, 2), "err": err,
           "device": jax.devices()[0].device_kind}
    print(json.dumps(row), flush=True)
    rows.append(row)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("paged_walk_time: no TPU here; a time comes only from the "
              "chip", file=sys.stderr)
        return 2
    cells = argv or list(CELLS)
    rows = []
    os.makedirs("chiprun_out", exist_ok=True)
    tag = "" if not argv else "_" + "_".join(argv)
    for cell in cells:
        s = shape_of(cell)
        print(json.dumps(s), flush=True)
        data = inputs(s)
        for context in CONTEXTS:
            for chunk in CHUNKS + ("rule",):
                case(s, data, context, chunk, rows)
                with open(f"chiprun_out/paged_walk_time{tag}.json",
                          "w") as f:   # a row at a time: a cut call keeps them
                    json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
