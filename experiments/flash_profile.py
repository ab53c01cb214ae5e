"""Profiler-based (host-clock-noise-immune) timing of the flash kernels.

Captures an xprof trace of K chained iterations and reads per-op DEVICE
time via utils/xprof.op_summary — the same method behind the round-3
roofline numbers. Reports ms/iter for our fwd, our fwd+bwd, and the
bundled jax kernel at identical shapes/blocks.
"""
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, "/root/repo")
from ddp_practice_tpu.utils.xprof import op_summary

PEAK = 197e12
K = 32


def device_ms(fn, args, label):
    @jax.jit
    def run(q, k, v):
        def body(c, _):
            return fn(c, k, v), ()
        o, _ = lax.scan(body, q, None, length=K)
        return jnp.float32(o.astype(jnp.float32).sum())

    float(run(*args))  # compile + warm
    tmp = tempfile.mkdtemp(prefix=f"xp_{label}_")
    with jax.profiler.trace(tmp):
        float(run(*args))
    s = op_summary(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    total_ms = s["total_ps"] / 1e9 / K
    by_op = sorted(s["ops"].items(), key=lambda kv: -kv[1])[:6]
    detail = {nm: ps / 1e9 / K for (cat, nm), ps in by_op}
    return total_ms, detail


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
        f = lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=True)[0].sum()
        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        # all three grads feed the carry so no kernel is dead-code-eliminated
        return lax.clamp(-1.0, (dq + dk + dv).astype(jnp.float32),
                         1.0).astype(q.dtype)

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
        f = lambda q, k, v: official_fwd(q, k, v).sum()
        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        return lax.clamp(-1.0, (dq + dk + dv).astype(jnp.float32),
                         1.0).astype(q.dtype)

    vis = 6 / 8
    fwd_fl = bh * 2 * 2.0 * s * s * d * vis
    bwd_fl = bh * 7 * 2.0 * s * s * d * vis

    for name, fn, fl in [
        ("ours fwd", ours_fwd, fwd_fl),
        ("ours fwd+bwd", ours_fwdbwd, fwd_fl + bwd_fl),
        ("jaxk fwd", official_fwd, fwd_fl),
        ("jaxk fwd+bwd", official_fwdbwd, fwd_fl + bwd_fl),
    ]:
        ms, detail = device_ms(fn, (q, k, v), name.replace(" ", "_"))
        tf = fl / (ms / 1e3) / 1e12
        print(f"{name:14s}: {ms:7.3f} ms/iter  executed {tf:6.1f} TF/s"
              f"  ({100 * tf * 1e12 / PEAK:.1f}% of bf16 peak)")
        for nm, m in detail.items():
            print(f"    {nm[:60]:60s} {m:7.3f} ms")


if __name__ == "__main__":
    main()
