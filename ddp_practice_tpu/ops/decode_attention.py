"""Single-token KV-cache decode attention as a packed Pallas TPU kernel.

Why this exists (round 4, measured): the decode KV cache used to be stored
as (b, L, h, head_dim). On TPU that shape's minor dims (h=12, hd=64) are
tile-padded, and XLA cannot update such a buffer in place — every
per-token `dynamic_update_slice` lowered to a full cache relayout copy,
53.6% of the bs=8 decode step (experiments/decode_profile.py). Probing
update patterns (experiments/decode_layouts.py) showed in-place DUS DOES
engage when the dynamic index is on a major dim and the minor dims are
unpadded: a FLAT (b, L, h*hd) cache updates in 0.2 us instead of 24 us.

XLA attention cannot consume the flat cache per head without a reshape
(which re-introduces the relayout), but a Pallas kernel can — the same
trick as ops/flash_attention.py's packed family: the kv tile is a
(block_l, h*hd) slice of the UNTRANSPOSED cache and the kernel walks
heads via 64-aligned column slices. So decode runs:

    cache: flat (b, L, h*hd), written in place by dynamic_update_slice
    step attention: this kernel, directly on the flat cache

Kernel structure — grid (batch, L-blocks), one cell covers ALL heads (a
head-split grid dim would multiply DMA cell count; the head walk is a
python-unrolled loop over column slices):

    q (1, h*hd) -> per head: broadcast to 8 sublane rows (1-row matvecs
      cannot use the MXU; rows 1-7 compute identical results and are
      discarded — the round-3 q8 trick, now inside the kernel for every
      batch size)
    s = q8 @ k_block^T  per head                     # MXU
    mask: k_pos <= cur  (and k_pos >= attn_start[b] for left-padded
      prompts) — cur/attn_start arrive via scalar prefetch
    online softmax accumulate across L-blocks (lane-replicated state,
      normalized acc — same scheme as the flash kernels)

L-blocks past `cur` are skipped: `@pl.when` gates the compute and the
index map pins their DMA to block 0 (Pallas elides DMAs whose block
index is unchanged), so a step at position p reads O(p) cache bytes, not
O(L) — the einsum path always paid O(L).

The reference has no decode path at all (its model is a CNN classifier);
this backs the generation stack (inference.py), whose API the LM family
needs for parity with torch generation loops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.flash_attention import (
    _LANES,
    _NEG_INF,
    _dot_tb,
    _heads_per_pack,
    _softmax_accumulate,
)
from ddp_practice_tpu.utils import backend


def _online_softmax_cell(
    cur, start, j, n_j,
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, block, n_heads, d,
):
    """One grid cell of the multi-block online-softmax decode walk,
    shared by the flat (`_kernel`) and paged (`_paged_kernel`) kernels —
    the only thing that differs between them is where `cur` comes from
    (pool-global scalar vs per-slot length) and how the kv tile was
    addressed (contiguous vs page table), both settled by the caller.
    `cur`/`start` are this cell's cursor scalars (start None = no
    left-padding mask); key positions are `j * block + offset`."""

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j * block <= cur)
    def _compute():
        k_pos = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (8, block), 1
        )
        valid = k_pos <= cur
        if start is not None:
            valid &= k_pos >= start
        penalty = jnp.where(valid, 0.0, _NEG_INF)
        for hh in range(n_heads):
            lo, hi = hh * d, (hh + 1) * d
            qs = (q_ref[:, lo:hi] * sm_scale).astype(q_ref.dtype)  # (1, d)
            q8 = jnp.broadcast_to(qs, (8, d))
            s = _dot_tb(q8, k_ref[:, lo:hi]) + penalty    # (8, block) f32
            m_scr[hh], l_scr[hh], acc_scr[:, lo:hi] = _softmax_accumulate(
                s, v_ref[:, lo:hi], m_scr[hh], l_scr[hh], acc_scr[:, lo:hi]
            )

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[:] = acc_scr[:1].astype(o_ref.dtype)


def _kernel(
    cur_ref, start_ref,              # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref, o_ref,      # blocks
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_l, n_heads, d, has_start,
):
    b_idx = pl.program_id(0)
    j = pl.program_id(1)
    _online_softmax_cell(
        cur_ref[0], start_ref[b_idx] if has_start else None,
        j, pl.num_programs(1),
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
        sm_scale=sm_scale, block=block_l, n_heads=n_heads, d=d,
    )


def _kernel_single(
    cur_ref, start_ref,
    q_ref, k_ref, v_ref, o_ref,
    *, sm_scale, L, n_heads, d, has_start, compute_dtype=None,
):
    """Single-block fast path (whole cache in one tile): plain softmax,
    no online state, no scratch carry — at large batch the multi-block
    kernel's per-cell state machinery dominates the step (bs=64 profile,
    round 4), and a cache that fits one tile needs none of it.

    compute_dtype: dtype the K/V tiles are cast to before the dots —
    needed when the cache is stored quantized (int8), where the MXU
    can't consume the raw tile."""
    b_idx = pl.program_id(0)
    cur = cur_ref[0]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (8, L), 1)
    valid = k_pos <= cur
    if has_start:
        valid &= k_pos >= start_ref[b_idx]
    penalty = jnp.where(valid, 0.0, _NEG_INF)
    cd = compute_dtype or q_ref.dtype
    for hh in range(n_heads):
        lo, hi = hh * d, (hh + 1) * d
        qs = (q_ref[:, lo:hi] * sm_scale).astype(cd)
        q8 = jnp.broadcast_to(qs, (8, d))
        s = _dot_tb(q8, k_ref[:, lo:hi].astype(cd)) + penalty  # (8, L) f32
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(cd), v_ref[:, lo:hi].astype(cd),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[:, lo:hi] = (pv[:1] / l[:1]).astype(o_ref.dtype)


def _kernel_single_quant(
    cur_ref, start_ref,
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
    *, sm_scale, L, n_heads, d, has_start, compute_dtype,
):
    """Single-tile kernel over an INT8 cache with per-(head, position)
    scales, shapes (h, L). The scales never touch the int8 tiles
    directly: the K scale multiplies the score row AFTER the q.k dot
    (s_h(l) = ks(h,l) * <q_h, k_int8(l)>), and the V scale folds into
    the probability vector BEFORE the p.v dot — two (8, L) VPU
    multiplies replace any dequantized (L, d) materialization, so the
    MXU still consumes plain tiles and HBM still streams 1 byte/elem."""
    b_idx = pl.program_id(0)
    cur = cur_ref[0]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (8, L), 1)
    valid = k_pos <= cur
    if has_start:
        valid &= k_pos >= start_ref[b_idx]
    penalty = jnp.where(valid, 0.0, _NEG_INF)
    cd = compute_dtype
    for hh in range(n_heads):
        lo, hi = hh * d, (hh + 1) * d
        qs = (q_ref[:, lo:hi] * sm_scale).astype(cd)
        q8 = jnp.broadcast_to(qs, (8, d))
        s = _dot_tb(q8, k_ref[:, lo:hi].astype(cd))      # (8, L) f32
        ks = ks_ref[hh, :].reshape(1, L)                 # (1, L) f32
        s = s * ks + penalty
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        vs = vs_ref[hh, :].reshape(1, L)
        pv = lax.dot_general(
            (p * vs).astype(cd), v_ref[:, lo:hi].astype(cd),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[:, lo:hi] = (pv[:1] / l[:1]).astype(o_ref.dtype)


def decode_attention_packed(
    q: jnp.ndarray,        # (b, 1, h*hd) — the current token's queries
    k_cache: jnp.ndarray,  # (b, L, h*hd) flat cache
    v_cache: jnp.ndarray,
    cur: jnp.ndarray,      # int32 scalar: position of the current token
    attn_start=None,       # optional (b,) int32: first valid key position
    *,
    n_heads: int,
    k_scale=None,          # (b, h, L) f32 — int8-cache dequant scales
    v_scale=None,
    block_l: int = 256,
    single_block_max: int = 1024,
) -> jnp.ndarray:
    """One decode step of masked attention over the flat KV cache.

    Valid keys for every query are positions [attn_start[b], cur] (cur
    INCLUSIVE — the current token attends to itself; the caller writes
    its K/V at `cur` before calling). Returns (b, 1, h*hd).

    Caches up to `single_block_max` positions run the one-tile plain-
    softmax kernel; longer caches run the multi-block online-softmax
    kernel, where `block_l` trades DMA granularity against grid
    overhead: reads round up to whole blocks past `cur` and skipped
    blocks cost ~nothing.

    k_scale/v_scale mark an INT8 cache (models/vit.py
    kv_cache_dtype="int8"): tiles stream at 1 byte/element and the
    per-(head, position) scales fold into the score row / probability
    vector inside the kernel (_kernel_single_quant) — decode traffic
    is the bandwidth roofline, so halving cache bytes is the lever the
    round-5 MBU work turned (BENCHMARKS.md decode section).
    """
    b, sq, hd_total = q.shape
    if sq != 1:
        raise ValueError(
            f"decode_attention_packed is the single-token step kernel "
            f"(got {sq} query rows); prefill takes the masked XLA path"
        )
    L = k_cache.shape[1]
    d = hd_total // n_heads
    if _heads_per_pack(n_heads, d) is None:
        raise ValueError(
            f"heads={n_heads}, head_dim={d} don't pack into 128-lane tiles"
        )
    sm_scale = 1.0 / (d ** 0.5)
    has_start = attn_start is not None
    quant = k_scale is not None
    if quant and v_scale is None:
        raise ValueError("int8 cache needs BOTH k_scale and v_scale")

    cur1 = jnp.asarray(cur, jnp.int32).reshape(1)
    start = (
        jnp.asarray(attn_start, jnp.int32)
        if has_start else jnp.zeros((b,), jnp.int32)
    )
    interpret = not backend.on_tpu()
    sem = pltpu.CompilerParams

    if quant and L > single_block_max:
        # long-cache int8 falls back to a dequantized pass through the
        # multi-block kernel below: correct, but it materializes a bf16
        # cache copy — the quantized multi-block kernel is future work
        # (the bench regime L<=1024 never takes this branch)
        scale_k = jnp.swapaxes(k_scale, 1, 2).repeat(d, axis=-1)
        scale_v = jnp.swapaxes(v_scale, 1, 2).repeat(d, axis=-1)
        k_cache = (k_cache.astype(jnp.float32) * scale_k).astype(q.dtype)
        v_cache = (v_cache.astype(jnp.float32) * scale_v).astype(q.dtype)
        quant = False

    if L <= single_block_max:
        if quant:
            kernel = functools.partial(
                _kernel_single_quant, sm_scale=sm_scale, L=L,
                n_heads=n_heads, d=d, has_start=has_start,
                compute_dtype=q.dtype,
            )
            scale_spec = pl.BlockSpec((None, n_heads, L),
                                      lambda b_, *_: (b_, 0, 0))
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2,
                    grid=(b,),
                    in_specs=[
                        pl.BlockSpec((None, 1, hd_total),
                                     lambda b_, *_: (b_, 0, 0)),
                        pl.BlockSpec((None, L, hd_total),
                                     lambda b_, *_: (b_, 0, 0)),
                        pl.BlockSpec((None, L, hd_total),
                                     lambda b_, *_: (b_, 0, 0)),
                        scale_spec,
                        scale_spec,
                    ],
                    out_specs=pl.BlockSpec((None, 1, hd_total),
                                           lambda b_, *_: (b_, 0, 0)),
                ),
                out_shape=jax.ShapeDtypeStruct((b, 1, hd_total), q.dtype),
                compiler_params=sem(dimension_semantics=("parallel",)),
                interpret=interpret,
            )(cur1, start, q, k_cache, v_cache, k_scale, v_scale)
        kernel = functools.partial(
            _kernel_single, sm_scale=sm_scale, L=L, n_heads=n_heads, d=d,
            has_start=has_start,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec((None, 1, hd_total),
                                 lambda b_, *_: (b_, 0, 0)),
                    pl.BlockSpec((None, L, hd_total),
                                 lambda b_, *_: (b_, 0, 0)),
                    pl.BlockSpec((None, L, hd_total),
                                 lambda b_, *_: (b_, 0, 0)),
                ],
                out_specs=pl.BlockSpec((None, 1, hd_total),
                                       lambda b_, *_: (b_, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((b, 1, hd_total), q.dtype),
            compiler_params=sem(dimension_semantics=("parallel",)),
            interpret=interpret,
        )(cur1, start, q, k_cache, v_cache)

    block_l = min(block_l, L)
    while L % block_l:
        block_l //= 2

    def kv_map(b_, j, cur_ref, start_ref):
        return (b_, lax.select(j * block_l <= cur_ref[0], j, 0), 0)

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, block_l=block_l, n_heads=n_heads, d=d,
        has_start=has_start,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, L // block_l),
            in_specs=[
                pl.BlockSpec((None, 1, hd_total),
                             lambda b_, j, *_: (b_, 0, 0)),
                pl.BlockSpec((None, block_l, hd_total), kv_map),
                pl.BlockSpec((None, block_l, hd_total), kv_map),
            ],
            out_specs=pl.BlockSpec((None, 1, hd_total),
                                   lambda b_, j, *_: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_heads, 8, _LANES), jnp.float32),
                pltpu.VMEM((n_heads, 8, _LANES), jnp.float32),
                pltpu.VMEM((8, hd_total), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd_total), q.dtype),
        compiler_params=sem(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cur1, start, q, k_cache, v_cache)
    return out


# --------------------------------------------------------------------- paged
# PagedAttention-style decode (serve/kv_pages.py): K/V live in a pool of
# fixed-size blocks shared by all slots, and each slot reaches its own
# history through a per-slot PAGE TABLE of block indices. Positions are
# slot-local — position p of slot b lives in pool block
# `page_table[b, p // block_size]` at row `p % block_size` — so there is
# no shared cursor and a step's attention span is the slot's own
# occupied pages, not a pool-global [0, max_len).


def _paged_kernel(
    len_ref, start_ref, pt_ref,          # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref, o_ref,          # blocks
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_size, n_heads, d, has_start,
):
    """Grid (batch, blocks-per-slot); the kv tile of cell (b, j) is pool
    block `pt_ref[b, j]` — the page-table indirection happens in the
    BlockSpec index map, so the body is `_online_softmax_cell` with a
    per-SLOT cursor (`len_ref[b]`) instead of the pool-global scalar.
    Blocks past the slot's length are skipped: `@pl.when` gates the
    compute and the index map pins their DMA to the slot's block 0
    (unchanged index -> Pallas elides the copy), so a slot with `p`
    occupied positions pays O(p) cache reads however large the pool or
    the per-slot capacity."""
    b_idx = pl.program_id(0)
    j = pl.program_id(1)
    _online_softmax_cell(
        len_ref[b_idx], start_ref[b_idx] if has_start else None,
        j, pl.num_programs(1),
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
        sm_scale=sm_scale, block=block_size, n_heads=n_heads, d=d,
    )


def gather_pages(pages: jnp.ndarray, page_table: jnp.ndarray,
                 n_heads: int, k_scale=None):
    """Materialize each slot's pages as one contiguous span:
    (num_blocks, block_size, h*hd) pool + (b, mb) table ->
    (b, mb*block_size, h, d). With `k_scale` ((num_blocks, h,
    block_size) fp32 — the int8 pool's per-block scale pages) the span
    is dequantized per (position, head) on the way out. Shared by the
    reference attention below and the model's paged PREFILL path
    (models/vit.py `_paged_decode` s > 1)."""
    b = page_table.shape[0]
    bs, hh = pages.shape[1], pages.shape[2]
    d = hh // n_heads
    mb = page_table.shape[1]
    span = mb * bs
    k = jnp.take(pages, page_table, axis=0).reshape(b, span, n_heads, d)
    if k_scale is not None:
        # (b, mb, h, bs) -> per-position (b, span, h)
        sc = jnp.take(k_scale, page_table, axis=0)
        sc = jnp.swapaxes(sc, 2, 3).reshape(b, span, n_heads)
        k = k.astype(jnp.float32) * sc[..., None]
    return k


def paged_attention_reference(
    q: jnp.ndarray,           # (b, 1, h*hd)
    k_pages: jnp.ndarray,     # (num_blocks, block_size, h*hd) pool
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # (b, max_blocks_per_slot) int32
    lengths: jnp.ndarray,     # (b,) int32: slot-local position of the
                              # current token (attends itself — inclusive)
    attn_start=None,          # optional (b,) int32 slot-local first key
    *,
    n_heads: int,
    k_scale=None,             # (num_blocks, h, block_size) f32 — int8
    v_scale=None,             # pool per-block dequant scale pages
) -> jnp.ndarray:
    """XLA gather path: materialize each slot's pages as a contiguous
    (b, max_blocks_per_slot * block_size) span and run masked attention.

    The span is the PER-SLOT capacity (sized to the request's own
    context budget), not the pool — the slot engine's cost driver was
    the pool-global [0, max_len) scan, which this path already removes.
    It is also the correctness oracle for `_paged_kernel` (and its int8
    variant) and the serving path on backends without the kernel (CPU
    tests; unpackable head shapes). An int8 pool dequantizes through
    its scale pages during the gather."""
    from ddp_practice_tpu.ops.attention import attention_with_mask

    b = q.shape[0]
    hh = k_pages.shape[2]
    d = hh // n_heads
    span = page_table.shape[1] * k_pages.shape[1]
    k = gather_pages(k_pages, page_table, n_heads, k_scale)
    v = gather_pages(v_pages, page_table, n_heads, v_scale)
    pos = jnp.arange(span, dtype=jnp.int32)[None, :]
    valid = pos <= lengths[:, None]
    if attn_start is not None:
        valid &= pos >= attn_start[:, None]
    cd = k_pages.dtype if k_scale is None else jnp.float32
    out = attention_with_mask(
        q.reshape(b, 1, n_heads, d).astype(cd),
        k.astype(cd), v.astype(cd), valid[:, None, None, :],
    )
    return out.reshape(b, 1, hh).astype(q.dtype)


def _paged_kernel_quant(
    len_ref, start_ref, pt_ref,              # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_size, n_heads, d, has_start, compute_dtype,
):
    """`_paged_kernel` over an INT8 block pool with per-block scale
    pages: the (h, block_size) scale tiles ride the SAME page-table
    index map as the K/V tiles they dequantize, the K scale multiplies
    the score row after the q.k dot and the V scale folds into the
    probability row before the p.v dot (`_softmax_accumulate(vs_row=)`) —
    no dequantized tile ever materializes, so HBM still streams
    1 byte/element for the cache walk."""
    b_idx = pl.program_id(0)
    j = pl.program_id(1)
    cur = len_ref[b_idx]
    n_j = pl.num_programs(1)
    cd = compute_dtype

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j * block_size <= cur)
    def _compute():
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (8, block_size), 1
        )
        valid = k_pos <= cur
        if has_start:
            valid &= k_pos >= start_ref[b_idx]
        penalty = jnp.where(valid, 0.0, _NEG_INF)
        for hh in range(n_heads):
            lo, hi = hh * d, (hh + 1) * d
            qs = (q_ref[:, lo:hi] * sm_scale).astype(cd)
            q8 = jnp.broadcast_to(qs, (8, d))
            s = _dot_tb(q8, k_ref[:, lo:hi].astype(cd))   # (8, bs) f32
            ks = ks_ref[hh, :].reshape(1, block_size)
            s = s * ks + penalty
            vs = vs_ref[hh, :].reshape(1, block_size)
            (m_scr[hh], l_scr[hh],
             acc_scr[:, lo:hi]) = _softmax_accumulate(
                s, v_ref[:, lo:hi].astype(cd),
                m_scr[hh], l_scr[hh], acc_scr[:, lo:hi], vs_row=vs,
            )

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[:] = acc_scr[:1].astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    attn_start=None,
    *,
    n_heads: int,
    k_scale=None,
    v_scale=None,
    impl: str = "auto",
) -> jnp.ndarray:
    """One paged decode step; returns (b, 1, h*hd). See the module-level
    paged section for the layout.

    impl: "auto" runs the Pallas kernel on TPU when the heads pack into
    128-lane tiles and the gather reference otherwise (on CPU the
    reference IS the fast path — interpret-mode pays python emulation
    per grid cell, and the reference's gather is one fused XLA op);
    "kernel" forces the kernel (interpret-mode on CPU — the numerics-
    test hook); "reference" forces the gather path.

    k_scale/v_scale mark an INT8 block pool (serve/kv_pages.py
    make_paged_cache over a kv_cache_dtype="int8" model): per-block
    (num_blocks, h, block_size) fp32 scale pages, walked through the
    same page table and folded into the score/probability rows inside
    `_paged_kernel_quant` — cache bytes/token halve while the numerics
    stay pinned to the dequantizing gather reference.
    """
    b, sq, hd_total = q.shape
    if sq != 1:
        raise ValueError(
            f"paged_decode_attention is the single-token step (got {sq} "
            f"query rows); prefill runs through a contiguous scratch "
            f"cache and scatters whole blocks (serve/kv_pages.py)"
        )
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("int8 page pool needs BOTH k_scale and v_scale")
    bs = k_pages.shape[1]
    d = hd_total // n_heads
    packable = _heads_per_pack(n_heads, d) is not None and bs % 8 == 0
    if impl == "reference" or (impl == "auto" and (
            not packable or not backend.on_tpu())):
        return paged_attention_reference(
            q, k_pages, v_pages, page_table, lengths, attn_start,
            n_heads=n_heads, k_scale=k_scale, v_scale=v_scale,
        )
    if not packable:
        raise ValueError(
            f"impl='kernel' needs packable heads (h={n_heads}, d={d}) "
            f"and a block_size multiple of 8 (got {bs})"
        )
    sm_scale = 1.0 / (d ** 0.5)
    has_start = attn_start is not None
    mb = page_table.shape[1]
    lens = jnp.asarray(lengths, jnp.int32)
    start = (
        jnp.asarray(attn_start, jnp.int32)
        if has_start else jnp.zeros((b,), jnp.int32)
    )
    pt = jnp.asarray(page_table, jnp.int32)

    def kv_map(b_, j, len_ref, start_ref, pt_ref):
        j_sel = lax.select(j * bs <= len_ref[b_], j, 0)
        return (pt_ref[b_, j_sel], 0, 0)

    common = dict(
        grid=(b, mb),
        out_specs=pl.BlockSpec((None, 1, hd_total),
                               lambda b_, j, *_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_heads, 8, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, 8, _LANES), jnp.float32),
            pltpu.VMEM((8, hd_total), jnp.float32),
        ],
    )
    q_spec = pl.BlockSpec((None, 1, hd_total), lambda b_, j, *_: (b_, 0, 0))
    kv_spec = pl.BlockSpec((None, bs, hd_total), kv_map)
    if quant:
        scale_spec = pl.BlockSpec((None, n_heads, bs), kv_map)
        kernel = functools.partial(
            _paged_kernel_quant, sm_scale=sm_scale, block_size=bs,
            n_heads=n_heads, d=d, has_start=has_start,
            compute_dtype=q.dtype,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                in_specs=[q_spec, kv_spec, kv_spec,
                          scale_spec, scale_spec],
                **common,
            ),
            out_shape=jax.ShapeDtypeStruct((b, 1, hd_total), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=not backend.on_tpu(),
        )(lens, start, pt, q, k_pages, v_pages, k_scale, v_scale)
    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, block_size=bs,
        n_heads=n_heads, d=d, has_start=has_start,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[q_spec, kv_spec, kv_spec],
            **common,
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd_total), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=not backend.on_tpu(),
    )(lens, start, pt, q, k_pages, v_pages)
