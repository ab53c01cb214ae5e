"""Time the flash kernels on the chip. Slope-fit over K in {16, 64} chained
scans, min of 5 reps, scalar-readback fenced.

    python experiments/flash_time.py            # lm_base shapes: the streaming
                                                # kernels vs the bundled jax one
    python experiments/flash_time.py vit [impl:BxS[:fwd][:causal] ...]
                                                # ViT-B/16's attention, one
        # layer, forward + backward off the flat qkv projection: XLA's
        # _attention, the streaming kernels, the whole-sequence kernels over
        # G, and the sweep over sequence length that set SHORT_SEQ_MIN
        # (PERF.md section 6, PR 29)
    python experiments/flash_time.py bwd        # the packed backward alone at
        # the LM cells' shape: the two kernels (dq: 3 dots a tile, dk/dv: 4)
        # beside the one that makes all three from 5 (PERF.md section 5, PR 31)
"""
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, "/root/repo")

PEAK = 197e12


def timed(fn, args, K1=16, K2=64):
    def chain(K):
        @jax.jit
        def run(q, k, v):
            def body(c, _):
                return fn(c, k, v), ()
            o, _ = lax.scan(body, q, None, length=K)
            return jnp.float32(o.astype(jnp.float32).sum())
        return run

    r1, r2 = chain(K1), chain(K2)
    float(r1(*args)); float(r2(*args))
    best = []
    for r in (r1, r2):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(r(*args))
            ts.append(time.perf_counter() - t0)
        best.append(min(ts))
    return (best[1] - best[0]) / (K2 - K1) * 1e3


def timed1(fn, x):
    """`timed` for a chain over ONE array (qkv -> d loss / d qkv)."""
    return timed(lambda c, _k, _v: fn(c), (x, x[:1], x[:1]))


def vit(only=(), out="chiprun_out/flash_time_vit.json"):
    """ViT-B/16's attention core a layer (b 128, s 196, 12 heads of 64,
    bf16), gradient with respect to the flat (b, s, 3*h*d) projection."""
    from ddp_practice_tpu.ops import flash_attention as fa
    from ddp_practice_tpu.ops.attention import _attention

    h, d = 12, 64

    def xla(qkv, causal):
        b, s, _ = qkv.shape
        x = qkv.reshape(b, s, 3, h, d)
        return _attention(x[:, :, 0], x[:, :, 1], x[:, :, 2], causal=causal)

    impls = {
        "xla": xla,
        "streaming": lambda qkv, causal: fa.flash_attention_qkv(
            qkv, h, causal=causal),
        "short": lambda qkv, causal: fa.flash_short_qkv(
            qkv, h, causal=causal),
    }
    def sliced(qkv, causal):
        b, s, _ = qkv.shape
        x = qkv.reshape(b, s, 3, h, d)
        return fa.flash_short(x[:, :, 0], x[:, :, 1], x[:, :, 2],
                              causal=causal)

    impls["short_sliced"] = sliced
    for g in (2, 4, 8, 16):
        impls[f"short_g{g}"] = functools.partial(
            lambda qkv, causal, g: fa.flash_short_qkv(
                qkv, h, causal=causal, images_per_cell=g), g=g)

    def case(name, b, s, causal=False, grad=True):
        qkv = jax.random.normal(jax.random.PRNGKey(0), (b, s, 3 * h * d),
                                jnp.bfloat16)
        fwd = lambda x: impls[name](x, causal)
        if grad:
            # cotangent of the out projection's shape and dtype
            w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d),
                                  jnp.bfloat16)
            fn = jax.grad(lambda x: (fwd(x) * w).astype(jnp.float32).sum())
        else:
            # the chain's carry stays the projection: one element of the
            # output is written into it in place
            fn = lambda x: lax.dynamic_update_slice(
                x, fwd(x)[:1, :1, 0, :1].astype(x.dtype), (0, 0, 0))
        ms = timed1(fn, qkv)
        useful = (7 if grad else 2) * 2.0 * b * h * s * s * d * (
            0.5 if causal else 1.0)
        row = {"impl": name, "b": b, "s": s, "causal": causal,
               "what": "fwd+bwd" if grad else "fwd", "ms": round(ms, 4),
               "useful_tflops": round(useful / ms / 1e9, 2)}
        print(json.dumps(row), flush=True)
        return row

    def errors(b=16, s=196):
        """Output and gradient of each bf16 path against float32 XLA."""
        k0, k1 = jax.random.split(jax.random.PRNGKey(2))
        qkv = jax.random.normal(k0, (b, s, 3 * h * d), jnp.float32)
        w = jax.random.normal(k1, (b, s, h, d), jnp.float32)
        qkv = qkv.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)

        def both(name, dtype):
            f = lambda x: impls[name](x, False).astype(jnp.float32)
            out, vjp = jax.vjp(f, qkv.astype(dtype))
            return out, vjp(w)[0].astype(jnp.float32)

        o32, g32 = jax.jit(functools.partial(both, "xla", jnp.float32))()
        rel = lambda a, r: float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r))
        for name in ("xla", "streaming", "short"):
            o, g = jax.jit(functools.partial(both, name, jnp.bfloat16))()
            row = {"impl": name, "what": "rel. l2 error vs float32 xla",
                   "out": rel(o, o32), "dqkv": rel(g, g32)}
            print(json.dumps(row), flush=True)
            rows.append(row)

    rows = []
    if only:
        # e.g. `vit short_g4:128x196 short:392x64:fwd`
        for spec in only:
            name, shape, *what = spec.split(":")
            b, s = map(int, shape.split("x"))
            rows.append(case(name, b, s, causal="causal" in what,
                             grad="fwd" not in what))
        return rows
    errors()
    # step 1 of ISSUE 29: the cell's shape
    for name in ("xla", "streaming", "short_g2", "short_g4", "short_g8",
                 "short_g16", "short", "short_sliced"):
        rows.append(case(name, 128, 196))
    for name in ("xla", "short_g8"):
        rows.append(case(name, 128, 196, grad=False))
    # the range: the same ~25k tokens a step at other lengths
    for b, s in ((392, 64), (196, 128), (98, 256), (64, 384), (44, 576)):
        for name in ("xla", "short"):
            rows.append(case(name, b, s))
    for name in ("xla", "streaming", "short"):
        rows.append(case(name, 98, 256, causal=True))
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)


def bwd(out_path="chiprun_out/flash_time_bwd.json", b=8, s=2048, h=12, d=64):
    """The packed streaming backward a layer at the LM cells' shape (b 8,
    s 2048, 12 heads of 64, causal, bf16), chained over the cotangent:
    dq alone and dk/dv alone (XLA drops the call whose results nobody
    reads), the two together, and the one kernel that replaced them."""
    from ddp_practice_tpu.ops import flash_attention as fa

    hd = h * d
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (b, s, hd), jnp.bfloat16)
                   for kk in keys)
    kw = dict(n_heads=h, causal=True, block_q=512, block_k=1024,
              interpret=fa._interpret())
    out, lse = fa._flash_fwd_packed(q, k, v, **kw)

    def grads(do, one_kernel):
        return fa._packed_bwd_calls(q, k, v, do, out, lse, fused_qkv=False,
                                    one_kernel=one_kernel, **kw)

    def keep(first, *rest):
        # the chain's carry is one gradient; an element of each other one
        # is written into it in place, so its kernel is not dropped
        for x in rest:
            first = lax.dynamic_update_slice(first, x[:1, :1, :1], (0, 0, 0))
        return first

    cases = {
        "dq_3dots": (3, lambda g: grads(g, False)[0]),
        "dkdv_4dots": (4, lambda g: keep(*grads(g, False)[1:])),
        "two_kernels_7dots": (7, lambda g: keep(*grads(g, False))),
        "one_kernel_5dots": (5, lambda g: keep(*grads(g, True))),
    }
    executed, useful = fa.causal_tile_counts(s, s)
    rows = []
    # both sides' results, once, before any timing
    one, two = jax.jit(lambda g: (grads(g, True), grads(g, False)))(do)
    for name, x, y in zip(("dq", "dk", "dv"), one, two):
        diff = jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)).max()
        rows.append({"grad": name, "max_abs_diff_one_vs_two": float(diff)})
        print(json.dumps(rows[-1]), flush=True)
    for name, (dots, fn) in cases.items():
        ms = timed1(fn, do)
        # dots that hold work: 2*s*s*d a head over the unmasked scores;
        # executed: over the 256-wide sub-tiles the schedule runs
        fl = dots * 2.0 * b * h * d * 256 * 256
        rows.append({
            "case": name, "ms": round(ms, 4),
            "useful_tflops": round(fl * useful / ms / 1e9, 2),
            "executed_tflops": round(fl * executed / ms / 1e9, 2)})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rows, f, indent=1)


def main():
    from ddp_practice_tpu.ops.flash_attention import flash_attention_with_lse
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as jax_flash)

    bh, s, d = 96, 2048, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (bh, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (bh, s, d), jnp.bfloat16)

    def ours_fwd(q, k, v):
        o, _ = flash_attention_with_lse(q, k, v, causal=True)
        return o

    def ours_fwdbwd(q, k, v):
        f = lambda q: flash_attention_with_lse(q, k, v, causal=True)[0].sum()
        return jax.grad(f)(q)

    bs = BlockSizes(
        block_q=512, block_k_major=1024, block_k=1024, block_b=1,
        block_q_major_dkv=512, block_k_major_dkv=1024,
        block_k_dkv=1024, block_q_dkv=512,
        block_k_major_dq=1024, block_k_dq=1024, block_q_dq=512,
    )

    def official_fwd(q, k, v):
        o = jax_flash(q.reshape(8, 12, s, d), k.reshape(8, 12, s, d),
                      v.reshape(8, 12, s, d), causal=True,
                      sm_scale=1.0 / d ** 0.5, block_sizes=bs)
        return o.reshape(bh, s, d)

    def official_fwdbwd(q, k, v):
        f = lambda q: official_fwd(q, k, v).sum()
        return jax.grad(f)(q)

    # executed-dot flops at blocks (512, 1024), causal
    vis = 6 / 8
    fwd_fl = bh * 2 * 2.0 * s * s * d * vis
    bwd_fl = bh * 7 * 2.0 * s * s * d * vis  # s,dv,dp,dk + s,dp,dq

    for name, fn, fl in [
        ("ours fwd", ours_fwd, fwd_fl),
        ("jaxk fwd", official_fwd, fwd_fl),
        ("ours fwd+bwd", ours_fwdbwd, fwd_fl + bwd_fl),
        ("jaxk fwd+bwd", official_fwdbwd, fwd_fl + bwd_fl),
    ]:
        ms = timed(fn, (q, k, v))
        tf = fl / (ms / 1e3) / 1e12
        print(f"{name:14s}: {ms:7.3f} ms   executed {tf:6.1f} TF/s"
              f"  ({100 * tf * 1e12 / PEAK:.1f}% of bf16 peak)")


if __name__ == "__main__":
    if sys.argv[1:2] == ["vit"]:
        vit(sys.argv[2:])
    elif sys.argv[1:2] == ["bwd"]:
        bwd()
    else:
        main()
