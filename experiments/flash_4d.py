"""Does a 4D-grid flash kernel reading (b, s, h, d) directly (strided DMA)
beat the fold-transpose path? Times the model-boundary view: input is
(b, s, h*d) as produced by the qkv matmul, output must be (b, s, h*d)."""
import functools
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, "/root/repo")
from ddp_practice_tpu.ops.flash_attention import (
    _fwd_kernel, _LANES, _kv_index_map)
from ddp_practice_tpu.utils.xprof import op_summary

K = 24


def fwd4d(q, k, v, *, causal=True, block_q=512, block_k=1024):
    """q/k/v: (b, s, h, d) — no transpose; grid (b, h, q-blocks, k-blocks)."""
    b, seq_q, h, d = q.shape
    seq_k = k.shape[1]
    sm_scale = 1.0 / d ** 0.5
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k,
    )
    offset = seq_k - seq_q if causal else 0
    if causal:
        def kv_map(b_, h_, i, j):
            vis = (i * block_q + block_q - 1 + offset) >= (j * block_k)
            return (b_, lax.select(vis, j, 0), h_, 0)
    else:
        def kv_map(b_, h_, i, j):
            return (b_, j, h_, 0)

    # patch program ids: kernel uses program_id(1)=q-block, (2)=k-block;
    # in the 4D grid they are (2) and (3) — wrap the kernel.
    def kernel4(q_ref, k_ref, v_ref, o_ref, lse_ref, m, l, acc):
        # reuse the 3D kernel by shifting ids via closure: easiest is to
        # re-derive the same body with ids 2/3. Import-free inline:
        return _fwd_kernel_ids(q_ref, k_ref, v_ref, o_ref, lse_ref, m, l,
                               acc, sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k, causal=causal, seq_q=seq_q,
                               seq_k=seq_k)

    out, lse = pl.pallas_call(
        kernel4,
        grid=(b, h, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, None, d),
                         lambda b_, h_, i, j: (b_, i, h_, 0)),
            pl.BlockSpec((None, block_k, None, d), kv_map),
            pl.BlockSpec((None, block_k, None, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, None, d),
                         lambda b_, h_, i, j: (b_, i, h_, 0)),
            pl.BlockSpec((None, block_q, None, 1),
                         lambda b_, h_, i, j: (b_, i, h_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, seq_q, h, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
    )(q, k, v)
    return out


def _fwd_kernel_ids(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                    acc_scr, *, sm_scale, block_q, block_k, causal, seq_q,
                    seq_k):
    """_fwd_kernel with grid ids at (2, 3) instead of (1, 2)."""
    from ddp_practice_tpu.ops import flash_attention as fa

    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)
    offset = seq_k - seq_q if causal else 0
    d = v_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    visible = (
        (qi * block_q + block_q - 1 + offset) >= (kj * block_k)
        if causal else (kj >= 0)
    )

    @pl.when(visible)
    def _compute():
        q = (q_ref[:] * sm_scale).astype(q_ref.dtype)
        s = fa._dot_tb(q, k_ref[:])
        if causal:
            s = s + fa._tile_penalty(
                qi * block_q + offset - kj * block_k, block_q, block_k)
        m_prev = m_scr[:]
        l_prev = l_scr[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - fa._widen(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_corr = alpha * l_prev
        l_next = l_corr + jnp.sum(p, axis=1)[:, None]
        l_inv = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
        m_scr[:] = m_next
        l_scr[:] = l_next
        pv = lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = (
            acc_scr[:] * fa._widen(l_corr * l_inv, d) + pv * fa._widen(l_inv, d)
        )

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)
        l_col = l_scr[:, :1]
        lse_ref[:] = m_scr[:, :1] + jnp.log(jnp.maximum(l_col, 1e-30))


def device_ms(fn, args):
    @jax.jit
    def run(x, *rest):
        def body(c, _):
            return fn(c, *rest), ()
        o, _ = lax.scan(body, x, None, length=K)
        return jnp.float32(o.astype(jnp.float32).sum())

    float(run(*args))
    tmp = tempfile.mkdtemp(prefix="xp_4d_")
    with jax.profiler.trace(tmp):
        float(run(*args))
    s = op_summary(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    cats = {c: v["ps"] / 1e9 / K for c, v in s["categories"].items()}
    return s["total_ps"] / 1e9 / K, cats


def main():
    from ddp_practice_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 8, 2048, 12, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    # model boundary: flat (b, s, h*d) activations
    qf = jax.random.normal(kq, (b, s, h * d), jnp.bfloat16)
    kf = jax.random.normal(kk, (b, s, h * d), jnp.bfloat16)
    vf = jax.random.normal(kv, (b, s, h * d), jnp.bfloat16)

    def path_fold(qf, kf, vf):
        q = qf.reshape(b, s, h, d)
        k = kf.reshape(b, s, h, d)
        v = vf.reshape(b, s, h, d)
        o = flash_attention(q, k, v, causal=True)  # transposes inside
        return o.reshape(b, s, h * d)

    def path_4d(qf, kf, vf):
        q = qf.reshape(b, s, h, d)
        k = kf.reshape(b, s, h, d)
        v = vf.reshape(b, s, h, d)
        o = fwd4d(q, k, v, causal=True)
        return o.reshape(b, s, h * d)

    # numerics
    ref = path_fold(qf, kf, vf)
    got = path_4d(qf, kf, vf)
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                - got.astype(jnp.float32))))
    print(f"max abs diff 4d vs fold: {err:.2e}")

    for name, fn in [("fold+transpose", path_fold), ("4d-direct", path_4d)]:
        ms, cats = device_ms(fn, (qf, kf, vf))
        fmt = ", ".join(f"{c}: {v:.3f}" for c, v in sorted(
            cats.items(), key=lambda kv: -kv[1])[:4])
        print(f"{name:15s}: {ms:7.3f} ms/iter   [{fmt}]")


if __name__ == "__main__":
    main()
