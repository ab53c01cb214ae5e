"""Time `window_prefill` alone on the chip at the window cell's shapes: a
2,048-token chunk (q (2,048, 4, 7, 128) bf16: 4 KV heads of 128, a group of
7) whose keys lie in 64-token pages of 4 x 128 lanes scattered over a pool
of the cell's size (3,105 pages for a window layer, 7,681 for a global one)
behind a 240-column table, at `pos0` 0 / 2,048 / 4,096 / 12,288 under a
window of 4,096 and under `NO_WINDOW`, all 2,048 rows real and a ragged 300,
for 2, 4, 8 and 16 pages a grid step and for the module's own rule.

    python experiments/window_prefill_time.py [pages ...]  # default rule 2 4 8 16

Two times a case: `kernel_ms`, the device time of the ops named
`window_prefill` alone, from a profiler trace of 8 calls (what
`flood_window_prefill_roofline` divides by); `call_ms`, the whole call (the
kernel, `tile_walks` and q's layout around it) on the host's clock, 16
dispatches in a row under one fence, min of 5 reps. `steps` are the grid
steps that ran (a tile's walk / pages, rounded up, over tiles and KV
heads), `walked` the pages of the tiles' walks, `executed` the pages the
steps computed (a tail included), `clear` the steps that ran without a
mask, `err` the kernel against `_prefill_reference` on the chunk's first
and last live tile. After the cases, one line a (window, pos0): the least
squares fit t(step) = f + keys x c over the swept page counts.

A module without `pages_per_step` (the parent of PR 46: copy this file into
its checkout's `experiments/`) is swept through its `WINDOW_FOLD`. One JSON
line a case; the table goes to chiprun_out/window_prefill_time[_tag].json
(PERF.md section 6, PR 46).
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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddp_practice_tpu.ops import window_attention as wa  # noqa: E402

S, KVH, GROUP, D = 2048, 4, 7, 128
BLOCK, COLUMNS = 64, 240
POOLS = {4096: 3105, wa.NO_WINDOW: 7681}
POS0 = (0, 2048, 4096, 12288)
RAGGED = 300
OLD = not hasattr(wa, "pages_per_step")
RULE = getattr(wa, "pages_per_step", None)


def inputs(pool, seed):
    """The two pools, wholly random (a table column may name any page), and
    the sequence's table: 240 distinct pages scattered over the pool."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    fill = lambda k: jax.random.normal(k, (pool, BLOCK, KVH * D),
                                       jnp.bfloat16)
    table = jax.random.permutation(k3, jnp.arange(1, pool))[:COLUMNS]
    return fill(k1), fill(k2), table.astype(jnp.int32)


def call_ms(fn, args, calls=16):
    """ms a call of the compiled `fn`: `calls` dispatches in a row (the
    device runs them back to back), one fence, min of 5 reps."""
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return min(ts) / calls * 1e3


def kernel_ms(fn, args, calls=8):
    """Device ms a call of the ops named `window_prefill`, from a trace."""
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        total = 0.0
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    total += sum(e.duration_ns for e in line.events
                                 if "window_prefill" in e.name)
    return total / calls / 1e6


def set_pages(pages):
    """Pages a step for what is traced next, or the module's own rule."""
    if OLD:
        wa.WINDOW_FOLD = pages
    elif pages == "rule":
        wa.pages_per_step = RULE
    else:
        wa.pages_per_step = lambda *a, **k: pages
    jax.clear_caches()


def counts(pages, pos0, window, real):
    """(pages, steps, walked, executed, clear) of one call, over tiles and
    KV heads."""
    tile = wa.WINDOW_TILE
    if not OLD:
        pages = wa.pages_per_step(BLOCK, KVH * D, GROUP * tile, COLUMNS)
        c = wa.walk_counts(pos0, 0, window, real, s=S, block=BLOCK,
                           columns=COLUMNS, pages=pages)
        return (pages,) + tuple(KVH * c[k] for k in (
            "steps", "walked", "executed", "clear"))
    _, cnt = wa.tile_walks(pos0, 0, window, real, tiles=S // tile, tile=tile,
                           block=BLOCK, columns=COLUMNS)
    steps = int(np.sum(-(-np.asarray(cnt) // pages)))
    return (pages, KVH * steps, KVH * int(np.sum(cnt)),
            KVH * steps * pages, 0)


# one program a pool's shape and page count: the positions, the window and
# the real rows are run-time scalars (`set_pages` drops what was traced)
run = jax.jit(lambda q, kp, vp, table, pos0, window, real: wa.window_prefill(
    q, kp, vp, table, pos0, window=window, real=real, impl="kernel"))
plain = jax.jit(wa._prefill_reference)


def case(pages, window, pos0, real, q, data, rows):
    """One row of the table. The pools go in as ARGUMENTS: closed over,
    their 1 GB would be constants of every program compiled here."""
    kp, vp, table = data
    tile = wa.WINDOW_TILE
    i32 = jnp.int32
    args = (q, kp, vp, table, i32(pos0), i32(window), i32(real))
    got = run(*args)
    err = 0.0
    for lo in (0, (real - 1) // tile * tile):
        cut = slice(lo, min(lo + tile, real))
        want = plain(q[lo:lo + tile], kp, vp, table, i32(pos0 + lo), i32(0),
                     i32(window))[:cut.stop - lo]
        err = max(err, float(jnp.abs(
            got[cut].astype(jnp.float32) - want.astype(jnp.float32)).max()))
    ms = kernel_ms(run, args)
    p, steps, walked, executed, clear = counts(pages, pos0, window, real)
    row = {"pages": pages, "pages_a_step": p,
           "window": "none" if window == wa.NO_WINDOW else window,
           "pos0": pos0, "real": real, "steps": steps, "walked": walked,
           "executed": executed, "clear": clear,
           "kernel_ms": round(ms, 4),
           "us_a_step": round(ms * 1e3 / max(steps, 1), 3),
           "call_ms": round(call_ms(run, args), 4),
           "err": err, "device": jax.devices()[0].device_kind}
    print(json.dumps(row), flush=True)
    rows.append(row)


def fits(rows):
    """t(step) = f + keys x c by least squares over the swept page counts,
    a (window, pos0) at all rows real."""
    out = []
    for window in ("none", 4096):
        for pos0 in POS0:
            pts = [(r["pages_a_step"] * BLOCK, r["us_a_step"]) for r in rows
                   if (r["window"], r["pos0"], r["real"]) == (window, pos0, S)
                   and r["pages"] != "rule" and r["steps"]]
            if len(pts) < 2:
                continue
            keys, us = np.array(pts).T
            c, f = np.polyfit(keys, us, 1)
            out.append({"fit": True, "window": window, "pos0": pos0,
                        "f_us": round(float(f), 3),
                        "c_us_256_keys": round(float(c) * 256, 3),
                        "points": pts})
    return out


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("window_prefill_time: no TPU here; a time comes only from the "
              "chip", file=sys.stderr)
        return 2
    sweep = [a if a == "rule" else int(a) for a in argv] or (
        [2, 4, 8, 16] if OLD else ["rule", 2, 4, 8, 16])
    if OLD:
        sweep = [p for p in sweep if p != "rule"]
    q = jax.random.normal(jax.random.PRNGKey(46), (S, KVH, GROUP, D),
                          jnp.bfloat16)
    rows = []
    os.makedirs("chiprun_out", exist_ok=True)
    tag = ("_parent" if OLD else "") + "_" + "_".join(str(p) for p in sweep)
    for window, pool in POOLS.items():
        data = inputs(pool, seed=pool)
        for pages in sweep:
            set_pages(pages)
            for pos0, real in [(p, S) for p in POS0] + [(POS0[-1], RAGGED)]:
                case(pages, window, pos0, real, q, data, rows)
                with open(f"chiprun_out/window_prefill_time{tag}.json",
                          "w") as f:   # a row at a time: a cut call keeps them
                    json.dump(rows, f, indent=1)
        del data
    for fit in fits(rows):
        print(json.dumps(fit), flush=True)
        rows.append(fit)
    with open(f"chiprun_out/window_prefill_time{tag}.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
