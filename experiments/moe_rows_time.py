"""Time the held experts' row movement alone on the chip: the fill of the
row-tile buffer and the combine out of it, as gathers over the whole layout
(`held_rows_fill_reference` / `held_rows_sum_reference`: every row of the
buffer in, every pick out) and as the kernels that move the held picks' rows
(`moe_rows_fill` / `moe_rows_sum`), beside the layout's own integer work.
Slope-fit over K in {8, 32} chained scans, min of 5 reps, scalar-readback
fenced, as `experiments/flash_time.py`.

    python experiments/moe_rows_time.py [qwen|nemo|kanana[:n] ...]

The cells' shapes: n 128 (a decode step) / 256 / 1,024 / 2,048 / 4,096;
`qwen` k 10 of 512 with 128 held at width 2,048, `nemo` k 22 of 512 with 128
held at width 1,024 (its buckets end at 768), `kanana` k 6 of 128, all held,
at width 2,048 (its chunks end at 1,024). One JSON line a case; the table
goes to chiprun_out/moe_rows_time.json (PERF.md section 6, PR 39).
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddp_practice_tpu.ops import moe  # noqa: E402

SHAPES = {  # name: (k, num_experts, held, width, the n's)
    "qwen": (10, 512, 128, 2048, (128, 256, 1024, 2048, 4096)),
    "nemo": (22, 512, 128, 1024, (128, 256, 768)),
    "kanana": (6, 128, 128, 2048, (128, 256, 1024)),
}


def timed(step, carry, K1=8, K2=32):
    """ms a call of `step` (carry -> carry of the same shape)."""
    def chain(K):
        @jax.jit
        def run(c):
            c, _ = lax.scan(lambda c, _: (step(c), ()), c, None, length=K)
            return jnp.float32(jnp.nan_to_num(
                c[:1].astype(jnp.float32)).sum())
        return run

    best = []
    for r in (chain(K1), chain(K2)):
        float(r(carry))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(r(carry))
            ts.append(time.perf_counter() - t0)
        best.append(min(ts))
    return (best[1] - best[0]) / (K2 - K1) * 1e3


def _touch(x, y):
    """x with one element that depends on every element of y: the chain's
    next call cannot start before this one has written all of y."""
    return x.at[0, 0].set(jnp.nan_to_num(
        y.astype(jnp.float32)).max().astype(x.dtype) * 0 + x[0, 0])


def case(name, n, rows):
    k, experts, held, d, _ = SHAPES[name]
    tile = moe._row_tile(n * k / experts)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(n), 3)
    _, choices = lax.top_k(jax.random.normal(k0, (n, experts)), k)
    choices = choices.astype(jnp.int32)
    weights = jax.random.uniform(k1, (n, k), jnp.float32)
    src = jax.random.normal(k2, (n, d), jnp.bfloat16)
    lay = jax.jit(lambda c: moe.held_tile_layout(
        c, offset=0, held=held, tile=tile))(choices)
    n_tiles = lay["tile_expert"].shape[0]
    used = int(lay["tiles_used"][0])
    picks = int(lay["pick_held"].sum())
    out = jax.jit(lambda s: moe.held_rows_fill_reference(s, lay))(src)
    # the kernels against the gathers, on the chip: rows to the bit, sums
    # to float32 rounding before the cast
    f32 = lambda a: a.astype(jnp.float32)
    fill_err = float(jnp.abs(f32(out[:used * tile]) - f32(
        moe.held_rows_fill_kernel(src, lay, tile=tile)[:used * tile])).max())
    sum_err = float(jnp.abs(
        f32(moe.held_rows_sum_reference(out, lay, weights, jnp.float32))
        - f32(moe.held_rows_sum_kernel(out, lay, weights, jnp.float32,
                                       tile=tile))).max())

    def layout(keys):
        def step(c):
            got = moe.held_tile_layout((choices + c[0, 0]) % experts,
                                       offset=0, held=held, tile=tile)
            return c.at[0, 0].set(
                sum(got[key].astype(jnp.int32).sum() for key in keys) % 2)
        return timed(step, jnp.zeros((1, 1), jnp.int32))

    ms = {
        "layout_gathers": layout(("row_token", "row_valid", "tile_expert",
                                  "tiles_used", "pick_row", "pick_held",
                                  "counts")),
        "layout_kernels": layout(("sorted_pick", "sorted_token",
                                  "tile_first_pick", "tile_rows",
                                  "tile_expert", "tiles_used", "counts")),
        "fill_gathers": timed(lambda s: _touch(
            s, moe.held_rows_fill_reference(s, lay)), src),
        "fill_kernel": timed(lambda s: _touch(
            s, moe.held_rows_fill_kernel(s, lay, tile=tile)[:used * tile]),
            src),
        "sum_gathers": timed(lambda o: _touch(
            o, moe.held_rows_sum_reference(o, lay, weights, o.dtype)), out),
        "sum_kernel": timed(lambda o: _touch(
            o, moe.held_rows_sum_kernel(o, lay, weights, o.dtype,
                                        tile=tile)), out),
    }
    moved = {"fill_gathers": n_tiles * tile, "sum_gathers": n * k,
             "fill_kernel": picks, "sum_kernel": picks}
    row = {"shape": name, "n": n, "k": k, "held": f"{held}/{experts}",
           "width": d, "tile": tile, "rows_layout": n_tiles * tile + n * k,
           "rows_held": picks, "tiles_used": used,
           "kernel_minus_gathers": {"fill": fill_err, "sum": sum_err},
           "ms": {key: round(v, 4) for key, v in ms.items()},
           "ns_a_row": {key: round(ms[key] * 1e6 / moved[key], 1)
                        for key in moved},
           "device": jax.devices()[0].device_kind}
    print(json.dumps(row), flush=True)
    rows.append(row)


def main(names):
    if jax.devices()[0].platform != "tpu":
        print("moe_rows_time: no TPU here; a time comes only from the chip",
              file=sys.stderr)
        return 2
    rows = []
    for arg in names or list(SHAPES):
        name, _, only = arg.partition(":")
        for n in SHAPES[name][-1]:
            if not only or n == int(only):
                case(name, n, rows)
    os.makedirs("chiprun_out", exist_ok=True)
    out = "chiprun_out/moe_rows_time%s.json" % (
        "_" + "_".join(names).replace(":", "") if names else "")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
