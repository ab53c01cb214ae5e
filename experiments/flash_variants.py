"""Flash-attention kernel variant shootout (round 4, VERDICT item 1).

Times fwd and fwd+bwd of candidate restructurings of ops/flash_attention.py
on the real chip at lm_base shapes (head_dim 64, seq 2048, causal) and
reports executed-dot TFLOP/s vs the chip's bf16 peak (hardware utilization
of the MXU, counting the dots each kernel actually runs — including bwd
recompute — over the causally visible blocks).

Variants:
  v1_fp32     — round-3 kernel: all operands upcast to fp32 before the dots.
  v2_bf16     — FlashAttention-2 staging: dots consume bf16 operands with
                fp32 accumulation (preferred_element_type); p / ds are cast
                to bf16 before their MXU consumers; softmax state stays fp32.
  v3_sumfold  — v2 + the softmax row-sum folded into the p@v matmul via a
                ones-augmented V (the d=64 output leaves half the MXU lanes
                idle anyway, so the extra column is free) — removes one VPU
                reduction pass per block.
  v4_2head    — v2 + two heads per grid cell (python-unrolled) to amortize
                per-cell overhead; contraction width is still head_dim so
                MXU utilization per dot is unchanged — this measures whether
                cell overhead, not array packing, is the limiter.

Timing: K-chained scan, fenced by scalar readback, slope between two chain
lengths (the slope cancels the per-call overhead).
"""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _causal_mask(s, qi, kj, block_q, block_k, offset):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    return jnp.where(q_pos + offset >= k_pos, s, _NEG_INF)


# ------------------------------------------------------------------ #
# v2: bf16-staged fwd kernel
# ------------------------------------------------------------------ #

def _fwd_v2(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
            *, sm_scale, block_q, block_k, causal, seq_q, seq_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    offset = seq_k - seq_q if causal else 0

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    visible = (
        (qi * block_q + block_q - 1 + offset) >= (kj * block_k)
        if causal else (kj >= 0)
    )

    @pl.when(visible)
    def _compute():
        q = q_ref[:]                       # bf16
        k = k_ref[:]
        v = v_ref[:]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = (l_prev * corr + jnp.sum(p, axis=-1))[:, None]
        acc_scr[:] = acc_scr[:] * corr[:, None] + jnp.dot(
            p.astype(jnp.bfloat16), v, preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new[:, None]

    @pl.when(kj == n_k - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[:] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:, 0] + jnp.log(l_safe))[:, None]


# ------------------------------------------------------------------ #
# v3: v2 + row-sum folded into the p@v matmul (ones-augmented V)
# ------------------------------------------------------------------ #

def _fwd_v3(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
            *, sm_scale, block_q, block_k, causal, seq_q, seq_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    offset = seq_k - seq_q if causal else 0
    d = v_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)  # (bq, d+128)

    visible = (
        (qi * block_q + block_q - 1 + offset) >= (kj * block_k)
        if causal else (kj >= 0)
    )

    @pl.when(visible)
    def _compute():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, offset)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None]).astype(jnp.bfloat16)
        corr = jnp.exp(m_prev - m_new)
        # ones-augmented V: [v | 1 0 ...] so col d of acc accumulates sum(p)
        ones_col = jnp.concatenate(
            [jnp.ones((block_k, 1), jnp.bfloat16),
             jnp.zeros((block_k, 127), jnp.bfloat16)], axis=1
        )
        v_aug = jnp.concatenate([v, ones_col], axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jnp.dot(
            p, v_aug, preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new[:, None]

    @pl.when(kj == n_k - 1)
    def _finalize():
        l_safe = jnp.maximum(acc_scr[:, d], 1e-30)
        o_ref[:] = (acc_scr[:, :d] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:, 0] + jnp.log(l_safe))[:, None]


# ------------------------------------------------------------------ #
# v5: H heads per cell + V pre-padded to 128 with a ones column at col d
# (sum(p) rides the p@v matmul for free — the d=64 output wastes those
# MXU lanes anyway and the pad happens ONCE outside the kernel, not per
# block) + exp2 instead of exp (folds log2(e) into the scale).
# ------------------------------------------------------------------ #

_LOG2E = 1.4426950408889634


def _fwd_v5(q_ref, k_ref, vp_ref, o_ref, lse_ref, m_scr, acc_scr,
            *, sm_scale, block_q, block_k, causal, seq_q, seq_k, n_heads, d):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    offset = seq_k - seq_q if causal else 0

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    visible = (
        (qi * block_q + block_q - 1 + offset) >= (kj * block_k)
        if causal else (kj >= 0)
    )

    @pl.when(visible)
    def _compute():
        for h in range(n_heads):
            s = jnp.dot(q_ref[h], k_ref[h].T,
                        preferred_element_type=jnp.float32)
            s = s * (sm_scale * _LOG2E)  # base-2 domain
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, offset)
            m_prev = m_scr[:, h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp2(s - m_new[:, None]).astype(jnp.bfloat16)
            corr = jnp.exp2(m_prev - m_new)
            acc_scr[h] = acc_scr[h] * corr[:, None] + jnp.dot(
                p, vp_ref[h], preferred_element_type=jnp.float32
            )
            m_scr[:, h] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        for h in range(n_heads):
            l_safe = jnp.maximum(acc_scr[h][:, d], 1e-30)
            o_ref[h] = (acc_scr[h][:, :d] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[h] = ((m_scr[:, h] + jnp.log2(l_safe))
                          * (1.0 / _LOG2E))[:, None]


def fwd_v5_call(q, k, v, *, causal=True, block_q=512, block_k=1024,
                n_heads=2):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    sm_scale = 1.0 / (d ** 0.5)
    g = bh // n_heads
    q4 = q.reshape(g, n_heads, seq_q, d)
    k4 = k.reshape(g, n_heads, seq_k, d)
    pad = jnp.zeros((bh, seq_k, 64), v.dtype)
    pad = pad.at[:, :, 0].set(1.0)
    vp = jnp.concatenate([v, pad], axis=-1).reshape(g, n_heads, seq_k, d + 64)
    kernel = functools.partial(
        _fwd_v5, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k, n_heads=n_heads, d=d)
    out, lse = pl.pallas_call(
        kernel,
        grid=(g, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, n_heads, block_q, d),
                         lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((None, n_heads, block_k, d),
                         lambda b, i, j: (b, 0, j, 0)),
            pl.BlockSpec((None, n_heads, block_k, d + 64),
                         lambda b, i, j: (b, 0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, n_heads, block_q, d),
                         lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((None, n_heads, block_q, 1),
                         lambda b, i, j: (b, 0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q4.shape, q.dtype),
            jax.ShapeDtypeStruct((g, n_heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, n_heads), jnp.float32),
            pltpu.VMEM((n_heads, block_q, d + 64), jnp.float32),
        ],
    )(q4, k4, vp)
    return out.reshape(bh, seq_q, d)


# ------------------------------------------------------------------ #
# v4: v2 with two heads per grid cell (python-unrolled)
# ------------------------------------------------------------------ #

def _fwd_v4(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
            *, sm_scale, block_q, block_k, causal, seq_q, seq_k):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    offset = seq_k - seq_q if causal else 0

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    visible = (
        (qi * block_q + block_q - 1 + offset) >= (kj * block_k)
        if causal else (kj >= 0)
    )

    @pl.when(visible)
    def _compute():
        for h in range(2):
            q = q_ref[h]
            k = k_ref[h]
            v = v_ref[h]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            if causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, offset)
            m_prev = m_scr[:, h]
            l_prev = l_scr[:, h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_scr[:, h] = l_prev * corr + jnp.sum(p, axis=-1)
            acc_scr[h] = acc_scr[h] * corr[:, None] + jnp.dot(
                p.astype(jnp.bfloat16), v, preferred_element_type=jnp.float32
            )
            m_scr[:, h] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        for h in range(2):
            l_safe = jnp.maximum(l_scr[:, h], 1e-30)
            o_ref[h] = (acc_scr[h] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[h] = ((m_scr[:, h] + jnp.log(l_safe)))[:, None]


def fwd_call(version, q, k, v, *, causal=True, block_q=512, block_k=1024):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    sm_scale = 1.0 / (d ** 0.5)
    if version == "v4":
        grid = (bh // 2, seq_q // block_q, seq_k // block_k)
        kernel = functools.partial(
            _fwd_v4, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            causal=causal, seq_q=seq_q, seq_k=seq_k)
        q4 = q.reshape(bh // 2, 2, seq_q, d)
        k4 = k.reshape(bh // 2, 2, seq_k, d)
        v4 = v.reshape(bh // 2, 2, seq_k, d)
        out, lse = pl.pallas_call(
            kernel, grid=grid,
            in_specs=[
                pl.BlockSpec((None, 2, block_q, d), lambda b, i, j: (b, 0, i, 0)),
                pl.BlockSpec((None, 2, block_k, d), lambda b, i, j: (b, 0, j, 0)),
                pl.BlockSpec((None, 2, block_k, d), lambda b, i, j: (b, 0, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, 2, block_q, d), lambda b, i, j: (b, 0, i, 0)),
                pl.BlockSpec((None, 2, block_q, 1), lambda b, i, j: (b, 0, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q4.shape, q.dtype),
                jax.ShapeDtypeStruct((bh // 2, 2, seq_q, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 2), jnp.float32),
                pltpu.VMEM((block_q, 2), jnp.float32),
                pltpu.VMEM((2, block_q, d), jnp.float32),
            ],
        )(q4, k4, v4)
        return out.reshape(bh, seq_q, d)

    kernel_fn = {"v2": _fwd_v2, "v3": _fwd_v3}[version]
    grid = (bh, seq_q // block_q, seq_k // block_k)
    kernel = functools.partial(
        kernel_fn, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k)
    acc_w = d + 128 if version == "v3" else d
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, acc_w), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
    )(q, k, v)
    return out


# ------------------------------------------------------------------ #
# Timing
# ------------------------------------------------------------------ #

def visible_fraction(seq_q, seq_k, block_q, block_k, causal):
    if not causal:
        return 1.0
    nq, nk = seq_q // block_q, seq_k // block_k
    offset = seq_k - seq_q
    vis = sum(
        1
        for qi in range(nq)
        for kj in range(nk)
        if qi * block_q + block_q - 1 + offset >= kj * block_k
    )
    return vis / (nq * nk)


def timed(fn, args, K1=4, K2=16):
    """Slope-fit device ms per call of fn(*args) -> array like args[0]."""

    def chain(K):
        @jax.jit
        def run(q, k, v):
            def body(c, _):
                return fn(c, k, v), ()
            o, _ = lax.scan(body, q, None, length=K)
            return jnp.float32(o.astype(jnp.float32).sum())
        return run

    r1, r2 = chain(K1), chain(K2)
    float(r1(*args))  # compile + warm
    float(r2(*args))
    best = []
    for r, K in ((r1, K1), (r2, K2)):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            f = float(r(*args))
            ts.append(time.perf_counter() - t0)
        best.append(min(ts))
    return (best[1] - best[0]) / (K2 - K1) * 1e3  # ms/call


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}", file=sys.stderr)
    peak = 197e12  # v5e bf16

    bh, s, d = 96, 2048, 64  # lm_base: b=8, h=12
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (bh, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (bh, s, d), jnp.bfloat16)

    block_q, block_k = 512, 1024
    vis = visible_fraction(s, s, block_q, block_k, True)
    # executed fwd dots: 2 dots x 2*s*s*d per bh, over visible blocks
    fwd_flops = bh * 2 * 2.0 * s * s * d * vis

    sys.path.insert(0, "/root/repo")
    from ddp_practice_tpu.ops.flash_attention import flash_attention_with_lse

    def v1(q, k, v):
        o, _ = flash_attention_with_lse(q, k, v, causal=True)
        return o

    results = {}
    # numerics check vs v1 first
    ref = v1(q, k, v)
    for name in ("v2", "v3", "v4"):
        got = fwd_call(name, q, k, v)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        print(f"{name} max abs diff vs v1: {err:.2e}", file=sys.stderr)

    for name in ("v5h2", "v5h4"):
        nh = int(name[-1])
        got = fwd_v5_call(q, k, v, n_heads=nh)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        print(f"{name} max abs diff vs v1: {err:.2e}", file=sys.stderr)

    cases = [
        ("v1_fp32", v1),
        ("v2_bf16", lambda q, k, v: fwd_call("v2", q, k, v)),
        ("v4_2head", lambda q, k, v: fwd_call("v4", q, k, v)),
        ("v5h2", lambda q, k, v: fwd_v5_call(q, k, v, n_heads=2)),
        ("v5h4", lambda q, k, v: fwd_v5_call(q, k, v, n_heads=4)),
        ("v5h2_bq1024", lambda q, k, v: fwd_v5_call(
            q, k, v, n_heads=2, block_q=1024, block_k=1024)),
        ("v5h2_bk2048", lambda q, k, v: fwd_v5_call(
            q, k, v, n_heads=2, block_q=256, block_k=2048)),
    ]
    for name, fn in cases:
        if name.endswith("bq1024"):
            vis_c = visible_fraction(s, s, 1024, 1024, True)
        elif name.endswith("bk2048"):
            vis_c = visible_fraction(s, s, 256, 2048, True)
        else:
            vis_c = vis
        flops_c = bh * 2 * 2.0 * s * s * d * vis_c
        ms = timed(fn, (q, k, v))
        tflops = flops_c / (ms / 1e3) / 1e12
        results[name] = (ms, tflops)
        print(f"fwd {name:14s}: {ms:7.3f} ms  {tflops:6.1f} TFLOP/s "
              f"({100*tflops*1e12/peak:.1f}% of bf16 peak, "
              f"executed-dot basis)")

    return results


if __name__ == "__main__":
    main()
