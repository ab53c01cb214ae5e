"""Time `kda_step` alone on the chip at the `ling3_serve_reason` cell's shape
(128 slots, 32 heads, a 128 x 128 float32 state a head: 537 MB read and
written a call), once for every way PR 48 tried to make a head's three
column tiles (`col[a, b] = x[a]` for k, q and the decay):

    parent  PR 47's kernel, copied here as it was: `ops/gdn.py
            _lane_columns`, one depth-8 float32 "highest" matmul a head
    kept    the tree's `ops/kda.py _step_call` (form 1 of ISSUE 48): the
            cell's 48 vectors transposed once, a tile one lane broadcast
    form2   the wrapper hands the vectors transposed, (slots, cells,
            key_dim, 3 x 16), an XLA fusion before the kernel
    form3   still the MXU, one bf16 pass: each vector split exactly into
            three bf16 parts, nine rows of a depth-16 operand

    python experiments/chip_calls/pr48_kda_step_time.py [--slots 128] [--calls 24]

A form's `kernel_ms` is the median device time of its ops named `kda_step*`
over `--calls` calls of one trace, `call_ms` every device op of the call
(the exp of the decay, beta's broadcast, form 2's transposing fusion) a
call, `roofline_pct` the state's bytes twice at 819 GB/s over `kernel_ms`,
`o_diff` / `state_diff` the largest difference from the PARENT's kernel on
the same random inputs (0.0: equal to the bit). One JSON line a form; the
table goes to chiprun_out/pr48_kda_step_time.json (PERF.md section 6, PR
48). Off the TPU the kernels are interpreted and nothing is timed: a
rehearsal of the script at `--slots 2`, never a number.
"""
import argparse
import functools
import glob
import json
import os
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

from ddp_practice_tpu.ops import kda  # noqa: E402
from ddp_practice_tpu.ops.gdn import (  # noqa: E402
    F32, _column_selector, _head_block, _lane_columns)
from ddp_practice_tpu.utils import backend  # noqa: E402

HEADS, DK, DV = 32, 128, 128
HBM_BYTES_S = 819e9     # one v5e chip (perf/lib/peaks.py)
BF16 = jnp.bfloat16


def _head(i, dcol, kcol, qcol, v_ref, beta_ref, h_ref, o_ref, ho_ref):
    """The state's arithmetic of every form: the parent's lines, its order."""
    s = h_ref[i] * dcol
    read = jnp.sum(s * kcol, axis=0, keepdims=True)
    d = beta_ref[i:i + 1, :] * (v_ref[i:i + 1, :] - read)
    new = s + kcol * d
    ho_ref[i] = new
    o_ref[i:i + 1, :] = jnp.sum(new * qcol, axis=0, keepdims=True)


def parent_kernel(q_ref, k_ref, v_ref, da_ref, beta_ref, h_ref, o_ref,
                  ho_ref, *, heads):
    pick = _column_selector(3, h_ref.shape[-1])
    for i in range(heads):
        kcol, qcol, dcol = _lane_columns(
            [k_ref[i:i + 1, :], q_ref[i:i + 1, :], da_ref[i:i + 1, :]], pick)
        _head(i, dcol, kcol, qcol, v_ref, beta_ref, h_ref, o_ref, ho_ref)


def form2_kernel(t_ref, v_ref, beta_ref, h_ref, o_ref, ho_ref, *, heads):
    dk, dv = h_ref.shape[-2:]
    col = lambda r, i: jnp.broadcast_to(
        t_ref[:, r * heads + i:r * heads + i + 1], (dk, dv))
    for i in range(heads):
        _head(i, col(0, i), col(1, i), col(2, i), v_ref, beta_ref, h_ref,
              o_ref, ho_ref)


def form3_kernel(q_ref, k_ref, v_ref, da_ref, beta_ref, h_ref, o_ref,
                 ho_ref, *, heads):
    dk, dv = h_ref.shape[-2:]
    at = lax.broadcasted_iota(jnp.int32, (16, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (16, 3 * dv), 1)
    first = at // 3 * dv        # rows 3r .. 3r + 2 are vector r's parts
    pick = ((lane >= first) & (lane < first + dv) & (at < 9)
            ).astype(F32).astype(BF16)

    def parts(x):               # hi + mid + lo == x, each exact in bf16
        hi = x.astype(BF16).astype(F32)
        mid = (x - hi).astype(BF16).astype(F32)
        return [hi, mid, (x - hi - mid).astype(BF16).astype(F32)]

    for i in range(heads):
        x = jnp.zeros((16, dk), F32)
        for r, p in enumerate(parts(da_ref[i:i + 1, :])
                              + parts(k_ref[i:i + 1, :])
                              + parts(q_ref[i:i + 1, :])):
            x = jnp.where(at == r, jnp.broadcast_to(p, (16, dk)), x)
        cols = lax.dot_general(x.astype(BF16), pick, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)
        _head(i, *(cols[:, r * dv:(r + 1) * dv] for r in range(3)),
              v_ref, beta_ref, h_ref, o_ref, ho_ref)


def _call(kernel, name, transposed, q, k, v, g, beta, state):
    """`ops/kda.py _step_call`'s grid, blocks and alias around `kernel`;
    `transposed`: form 2's one operand in place of q, k and the decay."""
    bsz, h, dv = v.shape
    dk = k.shape[-1]
    bh, _ = _head_block(h, h)
    key = pl.BlockSpec((None, bh, dk), lambda i, j: (i, j, 0))
    val = pl.BlockSpec((None, bh, dv), lambda i, j: (i, j, 0))
    st = pl.BlockSpec((None, bh, dk, dv), lambda i, j: (i, j, 0, 0))
    vectors = [q.astype(F32), k.astype(F32), v.astype(F32),
               jnp.exp(g.astype(F32))]
    specs = [key, key, val, key]
    if transposed:
        rows = jnp.stack([vectors[3], vectors[1], vectors[0]], 1)
        rows = rows.reshape(bsz, 3, h // bh, bh, dk)
        vectors = [rows.transpose(0, 2, 4, 1, 3).reshape(
            bsz, h // bh, dk, 3 * bh), vectors[2]]
        specs = [pl.BlockSpec((None, None, dk, 3 * bh),
                              lambda i, j: (i, j, 0, 0)), val]
    return pl.pallas_call(
        functools.partial(kernel, heads=bh),
        grid=(bsz, h // bh),
        in_specs=[*specs, val, st],
        out_specs=[val, st],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={len(specs) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=not backend.on_tpu(),
        name=name,
    )(*vectors, jnp.broadcast_to(beta.astype(F32)[..., None], (bsz, h, dv)),
      state)


FORMS = {
    "parent": functools.partial(_call, parent_kernel, "kda_step_parent",
                                False),
    "kept": kda.kda_step_kernel,
    "form2": functools.partial(_call, form2_kernel, "kda_step_form2", True),
    "form3": functools.partial(_call, form3_kernel, "kda_step_form3", False),
}


def inputs(slots, seed):
    """Vectors as the mixer hands them (q, k of unit length, q scaled; the
    decay's log in [-5, 0]; beta in (0, 1)) and a random state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (slots, HEADS, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (slots, HEADS, DK)))
    v = jax.random.normal(ks[2], (slots, HEADS, DV))
    g = -5.0 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (slots, HEADS, DK)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (slots, HEADS)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (slots, HEADS, DK, DV))


def device_ms(fn, args, calls):
    """(each call's device ms of the ops named `kda_step*`, every device
    op's ms a call), from one trace of `calls` calls."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        kernel, everything = [], 0.0
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    everything += e.duration_ns
                    if "kda_step" in e.name:
                        kernel.append(e.duration_ns / 1e6)
    return kernel, everything / calls / 1e6


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("forms", nargs="*", default=list(FORMS))
    opts = ap.parse_args(argv)
    args = inputs(opts.slots, opts.seed)
    least_ms = 2 * args[5].size * 4 / HBM_BYTES_S * 1e3
    want = jax.block_until_ready(jax.jit(FORMS["parent"])(*args))
    rows = []
    for name in opts.forms:
        fn = jax.jit(FORMS[name])
        got = jax.block_until_ready(fn(*args))
        row = {"form": name, "slots": opts.slots, "heads": HEADS,
               "device": jax.devices()[0].device_kind,
               "o_diff": float(jnp.abs(got[0] - want[0]).max()),
               "state_diff": float(jnp.abs(got[1] - want[1]).max()),
               "equal_to_the_bit": bool(jnp.array_equal(got[0], want[0])
                                        & jnp.array_equal(got[1], want[1]))}
        del got
        if backend.on_tpu():
            kernel, call = device_ms(fn, args, opts.calls)
            q1, med, q3 = statistics.quantiles(kernel, n=4)
            row.update(calls=opts.calls, kernel_events=len(kernel),
                       kernel_ms=med, kernel_ms_q1=q1,
                       kernel_ms_q3=q3, call_ms=call,
                       ns_a_head=med * 1e6 / (opts.slots * HEADS),
                       roofline_pct=100 * least_ms / med)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if backend.on_tpu():
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/pr48_kda_step_time.json", "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
