"""Time `sparse_prefill` alone on the chip at the long-document cell's
shapes: a 2,048-token chunk (q (2,048, 2, 16, 128) bf16, 2 KV heads of 128,
a group of 16) at `pos0` 4,096 / 12,288 / 30,720 of a 536-page table whose
pages lie scattered over the cell's pool (1 + 32 x 536 pages of 64 tokens),
under the REAL selection (`prefill_selection` over compressed keys made from
the same keys: with random keys the union of a tile's 128 rows' picks is
nearly every visible block, as in the cell), for 1, 2 and 4 list entries a
grid step (`PREFILL_FOLD`, set here before tracing).

    python experiments/sparse_prefill_time.py [fold ...]     # default 1 2 4

Two times a case: `kernel_ms`, the device time of the ops named
`sparse_prefill` alone, from a profiler trace of 8 calls (what
`flood_sparse_prefill_roofline` divides by); `call_ms`, the whole
`sparse_prefill` call (the kernel and the list building around it: union,
argsort, bias, q's layout) on the host's clock, 16 dispatches in a row under
one fence, min of 5 reps. `steps` are the grid steps that
ran (a tile's entries / fold, rounded up, over tiles and KV heads), `err` the
kernel against the plain form on the chunk's first and last tile.

A module without `PREFILL_FOLD` (the parent of PR 41: copy this file into
its checkout's `experiments/`) is timed as it is, fold "as_is". One JSON
line a case; the table goes to chiprun_out/sparse_prefill_time[_tag].json
(PERF.md section 6, PR 41).
"""
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddp_practice_tpu.ops import sparse_attention as sa  # noqa: E402

S, KVH, GROUP, D = 2048, 2, 16, 128
PAGES, SLOTS = 536, 32
POS0 = (4096, 12288, 30720)


def inputs():
    """q, the two pools, the sequence's compressed rows, its table."""
    spec = sa.SparseSpec()
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(41), 4)
    q = jax.random.normal(k0, (S, KVH, GROUP, D), jnp.bfloat16)
    length = PAGES * spec.block
    keys = jax.random.normal(k1, (length, KVH * D), jnp.bfloat16)
    values = jax.random.normal(k2, (length, KVH * D), jnp.bfloat16)
    pool = 1 + SLOTS * PAGES
    table = jax.random.permutation(k3, jnp.arange(1, pool))[:PAGES].astype(
        jnp.int32)
    paged = lambda x: jnp.zeros((pool, spec.block, KVH * D), x.dtype).at[
        table].set(x.reshape(PAGES, spec.block, KVH * D))
    j = jnp.arange(length // spec.stride)
    at = jnp.minimum(spec.stride * j[:, None] + jnp.arange(spec.kernel),
                     length - 1)
    rows = sa.compress(keys[at].reshape(-1, spec.kernel, KVH, D).swapaxes(
        1, 2))                                            # (J, kvh, d)
    return spec, q, paged(keys), paged(values), rows, table


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
    """Device ms a call of the ops named `sparse_prefill`, from a trace."""
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
                                 if "sparse_prefill" in e.name)
    return total / calls / 1e6


def case(fold, pos0, data, table_rows):
    """One row of the table. The pools go in as ARGUMENTS: closed over,
    their 1.1 GB would be constants of every program compiled here."""
    spec, q, kp, vp, rows, table = data
    if fold != "as_is":
        sa.PREFILL_FOLD = fold
    tile = sa.PREFILL_TILE
    picked = jax.jit(lambda q, rows: sa.prefill_selection(
        q, rows, pos0 + jnp.arange(S), jnp.int32(0), spec))(q, rows)
    counts = jnp.sum(jnp.any(picked.reshape(
        S // tile, tile, KVH, -1), axis=1), axis=-1)          # (T, kvh)
    run = jax.jit(lambda q, kp, vp, picked, table: sa.sparse_prefill(
        q, kp, vp, picked, table, pos0, block=spec.block, impl="kernel"))
    args = (q, kp, vp, picked, table)
    got = run(*args)
    plain = jax.jit(sa._sparse_prefill_reference, static_argnums=(6,))
    err = 0.0
    for lo in (0, S - tile):
        cut = slice(lo, lo + tile)
        want = plain(q[cut], kp, vp, picked[cut], table, pos0 + lo,
                     spec.block)
        err = max(err, float(jnp.abs(
            got[cut].astype(jnp.float32) - want.astype(jnp.float32)).max()))
    ms = kernel_ms(run, args)
    steps = int(jnp.sum(-(-counts // (1 if fold == "as_is" else fold))))
    row = {"fold": fold, "pos0": pos0, "entries": int(counts.sum()),
           "visible_mean": float(jnp.mean(
               (pos0 + jnp.arange(1, S // tile + 1) * tile - 1)
               // spec.block + 1)),
           "steps": steps, "kernel_ms": round(ms, 4),
           "us_a_step": round(ms * 1e3 / steps, 3),
           "call_ms": round(call_ms(run, args), 4),
           "err": err, "device": jax.devices()[0].device_kind}
    print(json.dumps(row), flush=True)
    table_rows.append(row)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("sparse_prefill_time: no TPU here; a time comes only from the "
              "chip", file=sys.stderr)
        return 2
    folds = [int(a) for a in argv] or [1, 2, 4]
    if not hasattr(sa, "PREFILL_FOLD"):
        folds = ["as_is"]
    data, rows = inputs(), []
    os.makedirs("chiprun_out", exist_ok=True)
    tag = "_" + "_".join(str(f) for f in folds)
    for fold in folds:
        for pos0 in POS0:
            case(fold, pos0, data, rows)
            with open(f"chiprun_out/sparse_prefill_time{tag}.json",
                      "w") as f:       # a row at a time: a cut call keeps them
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
