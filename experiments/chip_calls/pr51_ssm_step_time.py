"""Time `ssm_step` alone on the chip by how many groups a grid cell holds
(PR 51), at the two shapes that run it:

    mamba      `nemo3s_serve_flood`: 128 slots, 128 heads of 64 over a state
               of 128 in 8 groups (a group 16 tiles of 32 KB = 512 KB; 537 MB
               of float32 state read and written a call)
    lightning  `minicpm_sala_serve_long`: 32 slots, 32 heads of 128 over 128,
               a group a head (64 KB; 134 MB a call)

A form is `s1g<groups>u[v]`: PR 49's kernel, copied here as it was (grid
(slots, groups / <groups>), the body unrolled over a cell's groups and
heads), with the groups a cell handed in; `v` raises `vmem_limit_bytes` (a
block of 4 MiB each way, two deep, is the whole default 16 MiB). `s1g1u` is
PR 49's own cell at the mamba shape, `s1g16u` at lightning's. `tree` is
`ops/ssm.py ssm_step_kernel` as the checkout has it, `tree<KiB>` the same
with `_STEP_BYTES` set to that many KiB.

    python experiments/chip_calls/pr51_ssm_step_time.py [--shape mamba]
        [--slots 128] [--calls 24] [forms ...]

Both shapes where none is named. A form's `kernel_ms` is the median device
time of its op (named `ssm_step*`) over `--calls` calls of one trace with
the state donated, `roofline_pct` the state's bytes twice at 819 GB/s over
it; `equal_to_the_bit` compares y and the state with the shape's first form
(the parent's) on the same inputs. One JSON line a form; the table goes to
chiprun_out/pr51_ssm_step_time_<shape>.json (PERF.md section 6, PR 51). Off
the TPU the kernels are interpreted and nothing is timed: a rehearsal at
`--slots 2`, never a number.
"""
import argparse
import functools
import glob
import json
import math
import os
import re
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from ddp_practice_tpu.ops import ssm  # noqa: E402
from ddp_practice_tpu.ops.flash_attention import _dot_ta, _dot_tb  # noqa: E402
from ddp_practice_tpu.utils import backend  # noqa: E402

HBM_BYTES_S = 819e9     # one v5e chip (perf/lib/peaks.py)
F32 = jnp.float32
# slots, heads, head size, groups, state size; the forms swept by default,
# the parent's first
SHAPES = {
    "mamba": ((128, 128, 64, 8, 128),
              ["s1g1u", "s1g2u", "s1g4u", "s1g8uv", "tree", "tree2048"]),
    "lightning": ((32, 32, 128, 32, 128),
                  ["s1g16u", "s1g32u", "tree", "tree2048"]),
}


def _kernel(da_ref, xd_ref, b_ref, c_ref, h_ref, y_ref, ho_ref, *,
            groups, heads):
    """PR 49's `ops/ssm.py _step_kernel`, line for line."""
    n = h_ref.shape[-1]
    row0 = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0
    for j in range(groups):
        b8 = jnp.where(row0, jnp.broadcast_to(b_ref[j], (8, n)), 0.0)
        c8 = jnp.broadcast_to(c_ref[j], (8, n))
        for i in range(heads):
            x8 = jnp.where(row0, jnp.broadcast_to(
                xd_ref[j, i:i + 1, :], (8, xd_ref.shape[-1])), 0.0)
            new = h_ref[j, i] * da_ref[j, i:i + 1, :] + _dot_ta(x8, b8)
            ho_ref[j, i] = new
            y_ref[j, i:i + 1, :] = _dot_tb(c8, new)[:1]


def step(name, gb, vmem, x, dt, a, b_mat, c_mat, d_skip, state):
    """PR 49's `ops/ssm.py ssm_step_kernel` with the groups a cell handed
    in."""
    bsz, h, p = x.shape
    g, n = b_mat.shape[1:]
    hg = h // g
    x, dt = x.astype(F32), dt.astype(F32)
    da = jnp.broadcast_to(
        jnp.exp(dt * a.astype(F32))[..., None], (bsz, h, n)
    ).reshape(bsz, g, hg, n)
    xd = (x * dt[..., None]).reshape(bsz, g, hg, p)
    grp = lambda last: pl.BlockSpec((None, gb, hg, last),
                                    lambda i, j: (i, j, 0, 0))
    vec = pl.BlockSpec((None, gb, 1, n), lambda i, j: (i, j, 0, 0))
    st = pl.BlockSpec((None, gb, hg, p, n), lambda i, j: (i, j, 0, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_kernel, groups=gb, heads=hg),
        grid=(bsz, g // gb),
        in_specs=[grp(n), grp(p), vec, vec, st],
        out_specs=[grp(p), st],
        out_shape=[jax.ShapeDtypeStruct((bsz, g, hg, p), F32),
                   jax.ShapeDtypeStruct((bsz, g, hg, p, n), F32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 * 2**20 if vmem else None),
        interpret=not backend.on_tpu(),
        name=name,
    )(da, xd, b_mat.astype(F32)[:, :, None, :],
      c_mat.astype(F32)[:, :, None, :], state.reshape(bsz, g, hg, p, n))
    y = y.reshape(bsz, h, p) + x * d_skip.astype(F32)[None, :, None]
    return y, new.reshape(bsz, h, p, n)


def form(name):
    """The step function of a form's name."""
    if name.startswith("tree"):
        was = ssm._STEP_BYTES

        def tree(*args):    # read while tracing: the form's own budget
            ssm._STEP_BYTES = int(name[4:] or was // 1024) * 1024
            try:
                return ssm.ssm_step_kernel(*args)
            finally:
                ssm._STEP_BYTES = was

        return tree
    gb, vmem = re.fullmatch(r"s1g(\d+)u(v?)", name).groups()
    return functools.partial(step, "ssm_step_" + name, int(gb), bool(vmem))


def inputs(shape, seed):
    """Vectors as the mixers hand them (dt a softplus, A negative) and a
    random state."""
    slots, h, p, g, n = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    f = lambda k, *s: jax.random.normal(k, s, F32)
    return (f(ks[0], slots, h, p), jax.nn.softplus(f(ks[1], slots, h)),
            -jnp.exp(f(ks[2], h)), f(ks[3], slots, g, n), f(ks[4], slots, g, n),
            f(ks[5], h), f(ks[6], slots, h, p, n))


def device_ms(fn, args, calls):
    """Each call's device ms of the op named `ssm_step*`, from one trace of
    `calls` calls, each on the state the one before wrote."""
    from jax.profiler import ProfileData

    *vectors, state = args
    state = state + 0.0     # the donated copy: the caller keeps its own
    _, state = fn(*vectors, state)
    jax.block_until_ready(state)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                y, state = fn(*vectors, state)
            jax.block_until_ready((y, state))
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        kernel = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    kernel += [e.duration_ns / 1e6 for e in line.events
                               if "ssm_step" in e.name]
    return kernel


def sweep(name, slots, forms, calls, seed):
    """One shape's forms, a JSON line each; the rows."""
    shape = (slots or SHAPES[name][0][0],) + SHAPES[name][0][1:]
    args = inputs(shape, seed)
    least_ms = 2 * args[6].size * 4 / HBM_BYTES_S * 1e3
    rows, want = [], None
    for f in forms or SHAPES[name][1]:
        fn = form(f)
        grid = re.search(r"grid=\(([\d, ]+)\)", str(
            jax.make_jaxpr(fn)(*args))).group(1)
        cells = math.prod(int(k) for k in re.findall(r"\d+", grid))
        row = {"form": f, "shape": name, "slots": shape[0], "grid": grid,
               "cell_bytes_each_way": args[6].size * 4 // cells,
               "device": jax.devices()[0].device_kind}
        try:
            got = jax.block_until_ready(jax.jit(fn)(*args))
        except Exception as e:  # noqa: BLE001 — a block the compiler refuses
            row["refused"] = repr(e)[:300]
            print(json.dumps(row), flush=True)
            rows.append(row)
            continue
        want = want or got
        row["equal_to_the_bit"] = bool(jnp.array_equal(got[0], want[0])
                                       & jnp.array_equal(got[1], want[1]))
        del got
        if backend.on_tpu():
            kernel = device_ms(jax.jit(fn, donate_argnums=(6,)), args, calls)
            q1, med, q3 = statistics.quantiles(kernel, n=4)
            row.update(calls=calls, kernel_events=len(kernel),
                       kernel_ms=med, kernel_ms_q1=q1, kernel_ms_q3=q3,
                       roofline_pct=100 * least_ms / med)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if backend.on_tpu():
        os.makedirs("chiprun_out", exist_ok=True)
        with open(f"chiprun_out/pr51_ssm_step_time_{name}.json", "w") as f:
            json.dump({"rows": rows, "least_ms": least_ms}, f, indent=1)
    return rows


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--slots", type=int)
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--seed", type=int, default=51)
    ap.add_argument("forms", nargs="*")
    opts = ap.parse_args(argv)
    for name in [opts.shape] if opts.shape else ["mamba", "lightning"]:
        sweep(name, opts.slots, opts.forms, opts.calls, opts.seed)


if __name__ == "__main__":
    main(sys.argv[1:])
