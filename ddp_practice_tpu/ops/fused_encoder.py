"""Fused transformer encoder layer as one Pallas TPU kernel (forward).

Why this exists (BENCHMARKS.md "Why ViT-Tiny sits at ~17%"): at d=192 the
per-op XLA pipeline is HBM-bound — every matmul in the layer reads and
writes (tokens, d)-shaped tensors to HBM at intensity ~77 FLOP/byte, well
under the v5e ridge (~240). Fusing the WHOLE layer — LN1 → QKV →
attention → proj + residual → LN2 → MLP + residual — into one kernel
reads the token tensor from HBM once and writes it once; every
intermediate lives in VMEM, lifting intrinsic intensity to ~600 FLOP/byte
(compute-bound). The reference consumes the CUDA analogue of this idea
through cuDNN's fused blocks (SURVEY §2.2); on TPU it has to be a Pallas
kernel because XLA will not fuse across matmuls.

Shape contract: short fixed sequences that fit VMEM whole (the ViT
regime: S = 64 tokens at 32²/patch 4). The grid tiles the BATCH — each
cell processes `img_tile` images; weights (~0.7 MB at d=192) are
broadcast to every cell and stay VMEM-resident. Long-sequence models keep
the streaming flash-attention kernels (ops/flash_attention.py) instead —
different regime, different kernel.

Backward: also one Pallas kernel (`jax.custom_vjp`; residuals are just
(x, params) — remat semantics, O(x) training memory). Each backward grid
cell RECOMPUTES its tile's forward intermediates in VMEM (LN stats,
attention probabilities, gelu pre-activations — one extra forward's
FLOPs at fused-kernel efficiency, far cheaper than reading them from
HBM at d=192 intensity) and then runs the hand-derived transposes in
VMEM too. Weight gradients accumulate across grid cells directly in the
revisited output blocks (every cell maps its dW block to (0, 0); the
TPU grid is sequential, so the block lives in VMEM for the whole sweep
and flushes once). A `reference_apply` unfused backward is kept as an
option (`bwd_impl="reference"`) and is what the numerics tests compare
against.

Runs compiled on TPU; `interpret=True` under the CPU backend so the same
tests cover it everywhere (the flash-attention pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.utils import backend

_LN_EPS = 1e-6  # flax.linen.LayerNorm default


def _interpret() -> bool:
    return not backend.on_tpu()


def _layer_norm(xt, scale, bias):
    """fp32 LayerNorm over the last dim -> (affine out, normalized, rstd)."""
    mu = jnp.mean(xt, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xt - mu), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + _LN_EPS)
    yhat = (xt - mu) * r
    return yhat * scale + bias, yhat, r


def _layer_norm_bwd(dya, yhat, r, scale):
    """Cotangent of the LN input given the affine output's; plus the
    scale/bias grads. dya/yhat: (t, d); r: (t, 1)."""
    dscale = jnp.sum(dya * yhat, axis=0, keepdims=True)
    dbias = jnp.sum(dya, axis=0, keepdims=True)
    dxhat = dya * scale
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * yhat, axis=-1, keepdims=True)
    dx = r * (dxhat - m1 - yhat * m2)
    return dx, dscale, dbias


_GELU_C = 0.7978845608028654  # sqrt(2/pi)
_GELU_A = 0.044715


def _gelu_grad(x, t):
    """d gelu(x)/dx given t = tanh(c(x + a x^3)) (tanh approximation —
    what flax nn.gelu computes)."""
    return 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * _GELU_C * (
        1.0 + 3.0 * _GELU_A * x * x
    )


def _mm(a, w, cd):
    return jax.lax.dot(a.astype(cd), w.astype(cd),
                       preferred_element_type=jnp.float32)


def _bdot(a, b, contract_a, contract_b, cd):
    """Batched (leading-dim) dot in the compute dtype, fp32 accumulate."""
    return jax.lax.dot_general(
        a.astype(cd), b.astype(cd),
        (((contract_a,), (contract_b,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _fwd_core(xt, imgs, s, ln1_s, ln1_b, wqkv, bqkv, wproj, bproj,
              ln2_s, ln2_b, w_in, b_in, w_out, b_out,
              *, num_heads, head_dim, compute_dtype, causal=False,
              seq_merge=1):
    """The whole layer on a (t, d) fp32 token tile; returns every
    intermediate the backward needs (the fwd kernel uses `out` only and
    the compiler drops the rest).

    Attention runs per head in a Python loop (heads are few at small d)
    with images as the dot_general batch dim: Mosaic has no 4D head
    transpose, but 64-aligned column slices + major-dim reshapes lower
    cleanly. Head outputs lane-concat into o_all for a single K=d
    projection dot (three K=64 dots measured ~21% MXU efficiency).
    Matmuls take compute-dtype (bf16) operands with fp32 accumulation —
    the MXU contract, matching the unfused policy; LN/softmax/residual
    math runs in fp32, while bulky intermediates whose only consumers
    are cd-casting dots (qkv, hg) are stored in the compute dtype
    (bit-identical results, half the backward tile's VMEM).
    """
    cd = compute_dtype
    f32 = jnp.float32
    t, d = xt.shape
    h, hd = num_heads, head_dim
    y1a, y1hat, r1 = _layer_norm(xt, ln1_s, ln1_b)
    # qkv is stored in the compute dtype: its only consumers are the
    # per-head slices, whose dots cast to cd anyway (bit-identical), and
    # an f32 (t, 3d) buffer was ~1.2 MB of the backward tile's VMEM
    qkv = (_mm(y1a, wqkv, cd) + bqkv).astype(cd)      # (t, 3*h*hd)
    scale = 1.0 / (hd ** 0.5)
    # seq_merge m > 1 folds m images into ONE attention sequence of m*s
    # positions under a static block-diagonal additive mask: exp(-1e30)
    # zeroes every cross-image probability, so softmax rows, o, and all
    # five backward dots are EXACT per image while the MXU sees (m*s)-
    # sized operands instead of latency-dominated (s, hd) tiles (at
    # s=64/hd=64 each dot is ~16 cycles of useful work against ~10x that
    # in pipeline latency — the round-4 ablation measured the per-head
    # dots at 12% efficiency, 17% of the forward kernel). The executed
    # attention FLOPs grow m-fold; the measured win at the ViT shape
    # (m=2..4) is what picks the default in _pick_seq_merge.
    m = seq_merge
    im, sm = imgs // m, s * m
    # one (sm, sm) additive penalty shared by every merged row and head:
    # same-image blocks pass (with the causal triangle inside each block
    # when asked — within a diagonal block qpos >= kpos IS intra-image
    # causality), everything else is -1e30
    penalty = None
    if causal or m > 1:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (sm, sm), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (sm, sm), 1)
        ok = (qpos // s) == (kpos // s)
        if causal:
            ok = ok & (qpos >= kpos)
        penalty = jnp.where(ok, 0.0, -1e30)[None]
    heads = []
    outs = []
    for hi in range(h):
        def head_slice(base):
            col = base + hi * hd
            return qkv[:, col: col + hd].reshape(im, sm, hd)

        q = head_slice(0)
        k = head_slice(h * hd)
        v = head_slice(2 * h * hd)
        scores = _bdot(q, k, 2, 2, cd) * scale        # (im, sm, sm)
        if penalty is not None:
            scores = scores + penalty
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        o = _bdot(p, v, 2, 1, cd)                     # (im, sm, hd)
        outs.append(o.reshape(t, hd))
        heads.append((q, k, v, p))
    # concatenated head outputs -> ONE (t, d) @ (d, d) projection: three
    # K=64 per-head dots ran at ~21% MXU efficiency (round-4 standalone
    # shape probe); the lane-concat is a VPU copy, the K=192 dot ~3x
    # denser
    o_all = jnp.concatenate(outs, axis=1)             # (t, h*hd)
    x2 = xt + _mm(o_all, wproj, cd) + bproj
    y2a, y2hat, r2 = _layer_norm(x2, ln2_s, ln2_b)
    hpre = _mm(y2a, w_in, cd) + b_in                  # (t, mlp)
    tanh = jnp.tanh(_GELU_C * (hpre + _GELU_A * hpre * hpre * hpre))
    # hg in compute dtype: both consumers (the fc_out matmul here and
    # dw_out in the backward) cast to cd — identical results, half the
    # (t, mlp) buffer
    hg = (0.5 * hpre * (1.0 + tanh)).astype(cd)
    out = x2 + _mm(hg, w_out, cd) + b_out
    return dict(
        y1a=y1a, y1hat=y1hat, r1=r1, qkv=qkv, heads=heads, o_all=o_all,
        x2=x2, y2a=y2a, y2hat=y2hat, r2=r2, hpre=hpre, tanh=tanh, hg=hg,
        out=out,
    )


def _weights_f32(ln1_s, ln1_b, wqkv, bqkv, wproj, bproj, ln2_s, ln2_b,
                 w_in, b_in, w_out, b_out):
    f32 = jnp.float32
    return (
        ln1_s[0].astype(f32), ln1_b[0].astype(f32), wqkv[:], bqkv[0]
        .astype(f32), wproj[:], bproj[0].astype(f32), ln2_s[0].astype(f32),
        ln2_b[0].astype(f32), w_in[:], b_in[0].astype(f32), w_out[:],
        b_out[0].astype(f32),
    )


def _fused_kernel(
    x_ref, ln1_s, ln1_b, wqkv, bqkv, wproj, bproj, ln2_s, ln2_b,
    w_in, b_in, w_out, b_out, o_ref,
    *, num_heads, head_dim, compute_dtype, causal, seq_merge,
):
    """Forward grid cell: the full encoder layer for `img_tile` images."""
    imgs, s, d = x_ref.shape
    xt = x_ref[:].astype(jnp.float32).reshape(imgs * s, d)
    core = _fwd_core(
        xt, imgs, s,
        *_weights_f32(ln1_s, ln1_b, wqkv, bqkv, wproj, bproj, ln2_s,
                      ln2_b, w_in, b_in, w_out, b_out),
        num_heads=num_heads, head_dim=head_dim, compute_dtype=compute_dtype,
        causal=causal, seq_merge=seq_merge,
    )
    o_ref[:] = core["out"].reshape(imgs, s, d).astype(o_ref.dtype)


def _fused_bwd_kernel(
    x_ref, g_ref, ln1_s, ln1_b, wqkv, bqkv, wproj, bproj, ln2_s, ln2_b,
    w_in, b_in, w_out, b_out,
    dx_ref, dln1_s, dln1_b, dwqkv, dbqkv, dwproj, dbproj, dln2_s, dln2_b,
    dw_in, db_in, dw_out, db_out,
    *, num_heads, head_dim, compute_dtype, causal, seq_merge,
):
    """Backward grid cell: recompute the tile's forward in VMEM, then the
    hand-derived transposes. Weight-gradient outputs map every cell to
    block (0, 0): the TPU grid is sequential and Pallas keeps revisited
    output blocks in VMEM, so `ref[:] += ...` accumulates across the
    whole sweep and flushes once at the end (`@pl.when(cell 0)` zeroes)."""
    cd = compute_dtype
    f32 = jnp.float32
    imgs, s, d = x_ref.shape
    h, hd = num_heads, head_dim
    t = imgs * s
    xt = x_ref[:].astype(f32).reshape(t, d)
    g = g_ref[:].astype(f32).reshape(t, d)
    ws = _weights_f32(ln1_s, ln1_b, wqkv, bqkv, wproj, bproj, ln2_s,
                      ln2_b, w_in, b_in, w_out, b_out)
    (l1s, l1b, Wqkv, Bqkv, Wproj, Bproj, l2s, l2b,
     Win, Bin, Wout, Bout) = ws
    core = _fwd_core(
        xt, imgs, s, *ws,
        num_heads=num_heads, head_dim=head_dim, compute_dtype=cd,
        causal=causal, seq_merge=seq_merge,
    )

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for ref in (dln1_s, dln1_b, dwqkv, dbqkv, dwproj, dbproj, dln2_s,
                    dln2_b, dw_in, db_in, dw_out, db_out):
            ref[:] = jnp.zeros(ref.shape, ref.dtype)

    def mmT_left(a, b):
        # a^T @ b without materializing the transpose: contract dim 0
        return jax.lax.dot_general(
            a.astype(cd), b.astype(cd), (((0,), (0,)), ((), ())),
            preferred_element_type=f32,
        )

    def mmT_right(a, w):
        # a @ w^T: contract both dim 1
        return jax.lax.dot_general(
            a.astype(cd), w.astype(cd), (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )

    # ---- MLP branch (out = x2 + hg @ Wout + Bout)
    dw_out[:] += mmT_left(core["hg"], g)
    db_out[:] += jnp.sum(g, axis=0, keepdims=True)
    dhg = mmT_right(g, Wout)                          # (t, mlp)
    dhpre = dhg * _gelu_grad(core["hpre"], core["tanh"])
    dw_in[:] += mmT_left(core["y2a"], dhpre)
    db_in[:] += jnp.sum(dhpre, axis=0, keepdims=True)
    dy2a = mmT_right(dhpre, Win)                      # (t, d)
    dx2_ln, ds2, db2 = _layer_norm_bwd(dy2a, core["y2hat"], core["r2"], l2s)
    dln2_s[:] += ds2
    dln2_b[:] += db2
    dx2 = g + dx2_ln

    # ---- attention branch (x2 = xt + o_all @ Wproj + Bproj)
    dbproj[:] += jnp.sum(dx2, axis=0, keepdims=True)
    dwproj[:] += mmT_left(core["o_all"], dx2)
    do_all = mmT_right(dx2, Wproj)                    # (t, h*hd)
    scale = 1.0 / (hd ** 0.5)
    dqkv_cols = []
    for hi, (q, k, v, p) in enumerate(core["heads"]):
        # heads live in the seq_merge layout (imgs/m, m*s, hd); the five
        # grad dots below are exact there — every cross-image term rides
        # a zero of p (see _fwd_core)
        im, sm = q.shape[0], q.shape[1]
        do = do_all[:, hi * hd: (hi + 1) * hd].reshape(im, sm, hd)
        dp = _bdot(do, v, 2, 2, cd)                   # (im, sm, sm)
        dv = _bdot(p, do, 1, 1, cd)                   # (im, sm, hd)
        dsc = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dsc = dsc * scale
        dq = _bdot(dsc, k, 2, 1, cd)                  # (im, sm, hd)
        dk = _bdot(dsc, q, 1, 1, cd)                  # (im, sm, hd)
        dqkv_cols.append((dq.reshape(t, hd), dk.reshape(t, hd),
                          dv.reshape(t, hd)))
    # columns in qkv order: all q heads, all k heads, all v heads. The
    # bias grad sums the f32 pieces FIRST; the concatenated dqkv is then
    # stored in the compute dtype — both its consumers are dots that cast
    # to cd anyway (bit-identical grads), and an f32 (t, 3*h*hd) buffer
    # was ~1.7 MB of the tile's VMEM stack
    cols = (
        [c[0] for c in dqkv_cols] + [c[1] for c in dqkv_cols]
        + [c[2] for c in dqkv_cols]
    )
    dbqkv[:] += jnp.concatenate(
        [jnp.sum(c, axis=0, keepdims=True) for c in cols], axis=1
    )
    dqkv = jnp.concatenate(
        [c.astype(cd) for c in cols], axis=1,
    )                                                  # (t, 3*h*hd)
    dwqkv[:] += mmT_left(core["y1a"], dqkv)
    dy1a = mmT_right(dqkv, Wqkv)
    dx1_ln, ds1, db1 = _layer_norm_bwd(dy1a, core["y1hat"], core["r1"], l1s)
    dln1_s[:] += ds1
    dln1_b[:] += db1
    dx = dx2 + dx1_ln
    dx_ref[:] = dx.reshape(imgs, s, d).astype(dx_ref.dtype)


# merged attention positions ceiling shared by _pick_seq_merge and
# _auto_tile's budget estimate — retune in ONE place
_MERGE_TARGET = 128


def _pick_seq_merge(s, tile, target: int = _MERGE_TARGET):
    """Images per merged attention sequence: the largest power of two m
    dividing the tile with m*s <= target. 128 merged positions is the
    measured sweet spot at the ViT shape (s=64: m=2, -2% fwd / -1.5% bwd
    vs unmerged) — bigger merges pay more masked-out FLOPs than they
    save; sequences already >= target (the causal LM shapes) keep m=1."""
    m = 1
    while (
        m * 2 * s <= target and tile % (m * 2) == 0
    ):
        m *= 2
    return m


def _vmem_params(interpret):
    """Explicit 17 MB scoped-VMEM declaration for the fused kernels.

    Under the DEFAULT declaration XLA checks each kernel against a flat
    16 MB scoped budget, and inside a real train step the backward cell
    at its measured-best tile (8 images — 12% faster than 4) plus XLA's
    own S(1) buffers around the call (next-layer weight prefetches, the
    dW result tuple) lands at 16.06 MB — a 66 KB overflow that fails the
    e2e compile even though the standalone kernel fits. An explicit
    vmem_limit_bytes switches XLA to its program-wide scoped-vmem
    accounting against the physical budget (~128 MB on v5e), where the
    whole step needs ~127.9 MB and passes with the declaration at 17 MB
    (measured: 15/14 MB declarations FAIL that program-wide check —
    the limit scales with the declaration — and the default fails the
    flat check; 17 MB is the empirical window on v5e).

    The window is v5e-calibrated; other shapes/TPU generations can
    retune without editing the kernel via DDP_TPU_FUSED_VMEM_MB
    (advisor round 4)."""
    if interpret:
        return None
    import os

    raw = os.environ.get("DDP_TPU_FUSED_VMEM_MB", "17")
    try:
        mb = int(raw)
        if mb <= 0:
            raise ValueError(raw)
    except ValueError:
        raise ValueError(
            f"DDP_TPU_FUSED_VMEM_MB={raw!r}: want a positive integer "
            "(MB of scoped VMEM to declare for the fused encoder kernels)"
        ) from None
    return pltpu.CompilerParams(vmem_limit_bytes=mb * 1024 * 1024)


def _fit_tile(n, tile):
    tile = min(tile, n)
    while n % tile:
        tile -= 1
    return max(tile, 1)


def _auto_tile(imgs, s, compute_dtype, *, fwd: bool, d: int = 192,
               mlp_dim: int = 768, num_heads: int = 3,
               strict: bool = False):
    """Default images-per-cell honoring the 16 MB scoped-VMEM budget.

    Calibrated on v5e at the ViT-Tiny shape (d=192, mlp 768, h=3, s=64):
    the forward fits 2048 bf16-compute tokens per cell (tile 32 at s=64 —
    the bench shape), the backward 512 (more live intermediates; tile 8
    measured 12% faster than 4 at the bench shape, 16 OOMs — paid for
    by compute-dtype stores of qkv/hg/dqkv, which is also why fp32
    compute keeps its original smaller calibrated budget rather than a
    halved one). Other shapes scale the budget by relative live bytes
    per token: ~11d (residual/LN/qkv/head streams) + 3*mlp
    (hpre/tanh/hg) + h*s*seq_merge (the per-head probability tiles,
    (m*s, m*s) under merging — the term that blows up at LM sequence
    lengths; round-4 lm_tiny s=256 OOM'd the fixed budget by 3%)."""
    bytes_ = jnp.dtype(compute_dtype).itemsize
    # prospective seq_merge at this s (like _pick_seq_merge before the
    # tile-divisibility cut): merged per-head probability tiles are
    # (m*s, m*s) — m x the per-token bytes
    def m_est(seq):
        m = 1
        while m * 2 * seq <= _MERGE_TARGET:
            m *= 2
        return m

    ref_cost = 11 * 192 + 3 * 768 + 3 * 64 * m_est(64)
    cost = 11 * d + 3 * mlp_dim + num_heads * s * m_est(s)
    if bytes_ <= 2:
        base = 2048 if fwd else 512
    else:
        # fp32 compute: the compute-dtype stores (qkv/hg/dqkv) that pay
        # for the doubled bf16 backward tile free nothing here, so keep
        # the original calibrated fp32 budget
        base = 1024 if fwd else 128
    tokens = base * ref_cost // cost
    if strict:
        # feasibility probe (fused_shape_supported): 0 = the budget does
        # not admit even one full sequence per cell
        return tokens // s
    return max(1, tokens // s)


def fused_shape_supported(*, seq_len: int, d: int, mlp_dim: int,
                          num_heads: int, compute_dtype) -> bool:
    """True when the fused kernels can run this encoder shape at all.

    The auto-selection predicate (EncoderBlock fused="auto"): mirrors the
    kernel's hard constraints without raising — head_dim 64-aligned
    column slices (_prep), whole-weight VMEM residency
    (_check_vmem_residency), and a backward VMEM budget that admits at
    least one full sequence per grid cell (_auto_tile's token budget;
    long-sequence models fail here and keep the streaming flash kernels
    instead). Callers that want loud failures pass fused=True and get
    the original ValueErrors."""
    if not _head_dim_ok(d, num_heads):
        return False
    try:
        _check_vmem_residency(d, mlp_dim, compute_dtype)
    except ValueError:
        return False
    # backward (the tighter budget) must fit >= 1 sequence per cell
    return _auto_tile(
        seq_len, seq_len, compute_dtype, fwd=False, d=d, mlp_dim=mlp_dim,
        num_heads=num_heads, strict=True,
    ) >= 1


def _check_vmem_residency(d, mlp_dim, compute_dtype):
    """The kernel keeps ALL weights VMEM-resident; past ~8 MB of weights
    there is no room left for a useful tile. Fail loudly — this is the
    small-d kernel (d=192-class); wide models are compute-bound under
    per-op XLA anyway (BENCHMARKS.md: ViT-Base trains at ~55% unfused)."""
    w_bytes = (d * 3 * d + d * d + 2 * d * mlp_dim) * jnp.dtype(
        compute_dtype
    ).itemsize
    if w_bytes > 8 * 1024 * 1024:
        raise ValueError(
            f"fused encoder layer: weights at d={d}, mlp={mlp_dim} need "
            f"{w_bytes / 2**20:.1f} MB of VMEM residency — over the "
            "budget. This kernel targets the small-d HBM-bound regime; "
            "use the per-op path for wide models"
        )


def _head_dim_ok(d: int, num_heads: int) -> bool:
    """The in-kernel head walk's alignment contract — ONE definition
    shared by _prep's loud gate and fused_shape_supported's silent
    auto-selection predicate."""
    return d % num_heads == 0 and (d // num_heads) % 64 == 0


def _prep(x, params, num_heads, img_tile, compute_dtype):
    """(dims, weight mats, weight specs) shared by the fwd/bwd wrappers."""
    imgs, s, d = x.shape
    if d % num_heads:
        raise ValueError(f"d={d} % heads={num_heads}")
    if not _head_dim_ok(d, num_heads):
        raise ValueError(
            f"fused encoder layer needs head_dim a multiple of 64 (got "
            f"{d // num_heads}): the in-kernel head walk slices qkv "
            "columns at head_dim offsets and Mosaic only lowers "
            "64-aligned column slices — pick a head count with "
            "head_dim >= 64 (e.g. --num_heads 4 for d=256)"
        )
    tile = _fit_tile(imgs, img_tile)
    cd = compute_dtype

    def w2(a, shape):
        return jnp.asarray(a).reshape(shape).astype(cd)

    attn, mlp = params["attn"], params["mlp"]
    mats = [
        w2(params["ln1"]["scale"], (1, d)), w2(params["ln1"]["bias"], (1, d)),
        w2(attn["qkv"]["kernel"], (d, 3 * d)),
        w2(attn["qkv"]["bias"], (1, 3 * d)),
        w2(attn["out"]["kernel"], (d, d)), w2(attn["out"]["bias"], (1, d)),
        w2(params["ln2"]["scale"], (1, d)), w2(params["ln2"]["bias"], (1, d)),
        w2(mlp["fc_in"]["kernel"], (d, -1)), w2(mlp["fc_in"]["bias"], (1, -1)),
        w2(mlp["fc_out"]["kernel"], (-1, d)), w2(mlp["fc_out"]["bias"], (1, d)),
    ]
    _check_vmem_residency(d, mats[8].shape[1], compute_dtype)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    w_specs = [full(tuple(m.shape)) for m in mats]
    return imgs, s, d, tile, mats, w_specs


def fused_encoder_forward(
    x, params, *, num_heads: int, compute_dtype=jnp.bfloat16,
    img_tile: int = 0, interpret=None, causal: bool = False,
):
    """Pallas forward of one encoder layer. x: (imgs, s, d); params: the
    flax EncoderBlock param subtree (ln1/attn/ln2/mlp). img_tile 0 =
    auto (VMEM-budget-aware, _auto_tile)."""
    if interpret is None:
        interpret = _interpret()
    img_tile = img_tile or _auto_tile(
        x.shape[0], x.shape[1], compute_dtype, fwd=True, d=x.shape[2],
        mlp_dim=jnp.asarray(params["mlp"]["fc_in"]["kernel"]).shape[-1],
        num_heads=num_heads,
    )
    imgs, s, d, tile, mats, w_specs = _prep(
        x, params, num_heads, img_tile, compute_dtype
    )
    kernel = functools.partial(
        _fused_kernel, num_heads=num_heads, head_dim=d // num_heads,
        compute_dtype=compute_dtype, causal=causal,
        seq_merge=_pick_seq_merge(s, tile),
    )
    return pl.pallas_call(
        kernel,
        grid=(imgs // tile,),
        in_specs=[pl.BlockSpec((tile, s, d), lambda i: (i, 0, 0))] + w_specs,
        out_specs=pl.BlockSpec((tile, s, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_vmem_params(interpret),
        interpret=interpret,
    )(x, *mats)


def fused_encoder_backward(
    x, g, params, *, num_heads: int, compute_dtype=jnp.bfloat16,
    img_tile: int = 0, interpret=None, causal: bool = False,
):
    """Pallas backward: (dx, dparams-tree). Recompute + transpose per grid
    cell; weight grads accumulate across cells in revisited fp32 blocks.
    img_tile 0 = auto — a much tighter budget than the forward's (the
    backward holds ~3x the live intermediates; see _auto_tile)."""
    if interpret is None:
        interpret = _interpret()
    img_tile = img_tile or _auto_tile(
        x.shape[0], x.shape[1], compute_dtype, fwd=False, d=x.shape[2],
        mlp_dim=jnp.asarray(params["mlp"]["fc_in"]["kernel"]).shape[-1],
        num_heads=num_heads,
    )
    imgs, s, d, tile, mats, w_specs = _prep(
        x, params, num_heads, img_tile, compute_dtype
    )
    mlp_dim = mats[8].shape[1]
    f32 = jnp.float32
    kernel = functools.partial(
        _fused_bwd_kernel, num_heads=num_heads, head_dim=d // num_heads,
        compute_dtype=compute_dtype, causal=causal,
        seq_merge=_pick_seq_merge(s, tile),
    )
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    dw_shapes = [
        (1, d), (1, d), (d, 3 * d), (1, 3 * d), (d, d), (1, d),
        (1, d), (1, d), (d, mlp_dim), (1, mlp_dim), (mlp_dim, d), (1, d),
    ]
    x_spec = pl.BlockSpec((tile, s, d), lambda i: (i, 0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(imgs // tile,),
        in_specs=[x_spec, x_spec] + w_specs,
        out_specs=[x_spec] + [full(sh) for sh in dw_shapes],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)]
        + [jax.ShapeDtypeStruct(sh, f32) for sh in dw_shapes],
        compiler_params=_vmem_params(interpret),
        interpret=interpret,
    )(x, g.astype(x.dtype), *mats)
    dx = outs[0]
    (dl1s, dl1b, dwqkv, dbqkv, dwproj, dbproj, dl2s, dl2b,
     dwin, dbin, dwout, dbout) = outs[1:]

    def like(mat, leaf):
        return mat.reshape(jnp.shape(leaf)).astype(jnp.asarray(leaf).dtype)

    attn, mlp = params["attn"], params["mlp"]
    dparams: dict = {
        "ln1": {"scale": like(dl1s, params["ln1"]["scale"]),
                "bias": like(dl1b, params["ln1"]["bias"])},
        "attn": {
            "qkv": {"kernel": like(dwqkv, attn["qkv"]["kernel"]),
                    "bias": like(dbqkv, attn["qkv"]["bias"])},
            "out": {"kernel": like(dwproj, attn["out"]["kernel"]),
                    "bias": like(dbproj, attn["out"]["bias"])},
        },
        "ln2": {"scale": like(dl2s, params["ln2"]["scale"]),
                "bias": like(dl2b, params["ln2"]["bias"])},
        "mlp": {
            "fc_in": {"kernel": like(dwin, mlp["fc_in"]["kernel"]),
                      "bias": like(dbin, mlp["fc_in"]["bias"])},
            "fc_out": {"kernel": like(dwout, mlp["fc_out"]["kernel"]),
                       "bias": like(dbout, mlp["fc_out"]["bias"])},
        },
    }
    if hasattr(params, "unfreeze"):  # match a FrozenDict input's structure
        from flax.core import freeze

        dparams = freeze(dparams)
    return dx, dparams


def fused_encoder_layer(x, params, *, num_heads: int, reference_apply=None,
                        compute_dtype=jnp.bfloat16, img_tile: int = 0,
                        bwd_impl: str = "kernel", causal: bool = False):
    """Differentiable fused layer: Pallas forward AND backward.

    Residuals are just (x, params) — remat semantics. bwd_impl="kernel"
    (default) runs the fused Pallas backward; "reference" recomputes
    `reference_apply(params, x)` under jax.vjp instead — the unfused flax
    block, bit-exact unfused gradients, used by the numerics tests as the
    ground truth the kernel is pinned against. `img_tile` tunes the
    FORWARD only; the backward always auto-sizes (its VMEM budget is ~3x
    tighter — _auto_tile).
    """
    if bwd_impl not in ("kernel", "reference"):
        raise ValueError(f"bwd_impl {bwd_impl!r} (kernel|reference)")
    if bwd_impl == "reference" and reference_apply is None:
        raise ValueError("bwd_impl='reference' needs reference_apply")

    @jax.custom_vjp
    def layer(x, p):
        return fused_encoder_forward(
            x, p, num_heads=num_heads, compute_dtype=compute_dtype,
            img_tile=img_tile, causal=causal,
        )

    def fwd(x, p):
        return layer(x, p), (x, p)

    def bwd(res, g):
        x, p = res
        if bwd_impl == "kernel":
            return fused_encoder_backward(
                x, g, p, num_heads=num_heads, compute_dtype=compute_dtype,
                causal=causal,
            )
        _, vjp = jax.vjp(lambda xx, pp: reference_apply(pp, xx), x, p)
        return vjp(g)

    layer.defvjp(fwd, bwd)
    return layer(x, params)
