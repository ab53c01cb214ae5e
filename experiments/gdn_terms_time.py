"""Time the Gated DeltaNet chunk terms alone on the chip, at the mixed
cell's widths (16 key heads serving 32 value heads of 128 x 128, chunks of
64; perf/configs/qwen3next_80b_ep4.json) and three of its prompt buckets:
the terms as XLA ops (`_chunk_terms`) and as the kernel `gdn_terms`, the
carry kernel `gdn_scan` over terms that are given, and the whole call with
either half in front of the carry. Slope-fit over K in {8, 32} chained
calls, min of 5 reps, scalar-readback fenced, as
`experiments/moe_rows_time.py`: a single call of 256 tokens reads the
host's dispatch, not the device. Every input is the chain's carry (an
input that never changes lets XLA lift what depends on it alone out of the
loop: all of the terms but two), and what a call returns is held whole by
an optimization barrier before one element of each array of it feeds the
next call (an array nobody reads is not computed, barrier or no barrier).

    python experiments/gdn_terms_time.py [256 1024 4096]

Beside the times, the error of the module's `gdn_scan` on the chip against
the recurrence one position at a time (`gdn_scan_reference`), from a zero
state, on two inputs: `random` (unit q and k that share a mean direction,
`unit(z + 0.35 base)`, q times 128^-0.5, v rounded to bf16,
g = -softplus(0.9 z), beta = sigmoid(0.9 z)) and `repeated` (one token of
those all along the prompt, g -0.05, beta 0.95: a chunk's triangular system
dense and far from the identity). A module without `gdn_terms_kernel` (the
parent of PR 42: copy this file into its checkout's `experiments/`) is timed
as it is. One JSON line a length; the table goes to
chiprun_out/gdn_terms_time.json (PERF.md section 6, PR 42).
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddp_practice_tpu.ops import gdn  # noqa: E402

HK, HV, DK, DV = 16, 32, 128, 128
LENGTHS = (256, 1024, 4096)
F32 = jnp.float32


def inputs(kind, l):
    """(q, k, v, g, beta) of one prompt of `l` positions."""
    ks = jax.random.split(jax.random.PRNGKey(l), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    near = lambda key, base: unit(
        jax.random.normal(key, (1, l, HK, DK)) + 0.35 * base)
    q = near(ks[0], jax.random.normal(ks[5], (DK,))) * DK ** -0.5
    k = near(ks[1], jax.random.normal(ks[6], (DK,)))
    v = jax.random.normal(ks[2], (1, l, HV, DV)).astype(
        jnp.bfloat16).astype(F32)
    g = -jax.nn.softplus(0.9 * jax.random.normal(ks[3], (1, l, HV)))
    beta = jax.nn.sigmoid(0.9 * jax.random.normal(ks[4], (1, l, HV)))
    if kind == "repeated":
        q, k, v = (jnp.broadcast_to(x[:, :1], x.shape) for x in (q, k, v))
        g, beta = jnp.full_like(g, -0.05), jnp.full_like(beta, 0.95)
    return q, k, v, g, beta


def timed(step, carry, K1=8, K2=32):
    """ms a call of `step` (carry -> carry of the same shapes)."""
    def chain(K):
        @jax.jit
        def run(c):
            c, _ = lax.scan(lambda c, _: (step(c), ()), c, None, length=K)
            return sum(jnp.nan_to_num(x.reshape(-1)[:1]).sum() for x in c)
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


def chained(fn):
    """A chain's step: `fn` of the carry, all of what it returns computed,
    one element of each array of it added (times 1e-30: times 0 is folded
    away, and an array nobody reads is not computed) to one element of
    every carried array."""
    def step(carry):
        out = lax.optimization_barrier(fn(*carry))
        z = sum(jnp.nan_to_num(x.reshape(-1)[-1])
                for x in jax.tree.leaves(out)) * 1e-30
        return tuple(x.at[(0,) * x.ndim].add(z) for x in carry)
    return step


def case(l, rows):
    ins = inputs("random", l)
    h0 = jnp.zeros((1, HV, DK, DV), F32)
    c = gdn.CHUNK
    xla = lambda *a: gdn._chunk_terms(*a, c)
    terms = jax.jit(xla)(*ins)
    order = ("w", "u0", "qg", "p", "kend", "dend")   # the carry's operands

    def carry_alone(h, *given):
        o, new = gdn._carry_kernel(dict(zip(order, given)), h)
        return lax.optimization_barrier((new, o))[0]

    ms = {
        "terms_xla": timed(chained(xla), ins),
        "carry_kernel": timed(
            lambda cy: (carry_alone(*cy),) + cy[1:],
            (h0,) + tuple(terms[n] for n in order)),
        "scan_xla_terms": timed(chained(
            lambda *a: gdn._carry_kernel(xla(*a[:5]), a[5])), ins + (h0,)),
        # the module's own call, both halves as it chooses them on the chip
        "scan": timed(chained(lambda *a: gdn.gdn_scan(*a)), ins + (h0,)),
    }
    if hasattr(gdn, "gdn_terms_kernel"):
        ms["terms_kernel"] = timed(chained(
            lambda *a: gdn.gdn_terms_kernel(*a, c)), ins)
    row = {"tokens": l, "ms": {k: round(v, 4) for k, v in ms.items()},
           "us_a_token": {k: round(v * 1e3 / l, 4) for k, v in ms.items()},
           "device": jax.devices()[0].device_kind}
    for kind in ("random", "repeated"):
        args = inputs(kind, l) + (h0,)
        o, h = jax.jit(gdn.gdn_scan)(*args)
        want_o, want_h = jax.jit(gdn.gdn_scan_reference)(*args)
        gap = lambda x, y: [float(jnp.abs(x - y).max()),
                            float(jnp.abs(y).max())]
        row["err_" + kind] = {"out": gap(o, want_o), "state": gap(h, want_h)}
    print(json.dumps(row), flush=True)
    rows.append(row)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("gdn_terms_time: no TPU here; a time comes only from the chip",
              file=sys.stderr)
        return 2
    rows = []
    for l in [int(a) for a in argv] or LENGTHS:
        case(l, rows)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gdn_terms_time.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
