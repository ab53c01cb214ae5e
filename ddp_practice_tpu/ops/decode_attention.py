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

The PAGED kernel (second half of this file; serve/kv_pages.py's pool) is
built the other way round, because a skipped grid cell is not free: on
the chip a dead cell costs 0.13 us and a live 16-token page with its
twelve-head loop 0.71 us (PERF.md section 6, PR 25). Its grid runs over
SLOTS only. One cell walks one slot's live pages, from the page of
`attn_start` to the page of `len` and nowhere else, eight 16-token pages
(128 tokens) at a time: the pools stay in HBM and each page comes by its
own async copy into a two-deep VMEM buffer, the next chunk (or the next
slot's first) in flight while this one is computed. The heads are not
looped over: the query becomes 16 block-diagonal rows, so a chunk is two
matmuls (`_paged_walk_kernel`). The int8 pool keeps the older
one-page-a-cell walk (`_paged_kernel_quant`).

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


def _kernel(
    cur_ref, start_ref,              # scalar prefetch (SMEM)
    q_ref, k_ref, v_ref, o_ref,      # blocks
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_l, n_heads, d, has_start,
):
    """One grid cell (batch row, L-block) of the multi-block
    online-softmax walk over the flat cache; key positions are
    `j * block_l + offset`."""
    b_idx = pl.program_id(0)
    j = pl.program_id(1)
    cur = cur_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j * block_l <= cur)
    def _compute():
        k_pos = j * block_l + jax.lax.broadcasted_iota(
            jnp.int32, (8, block_l), 1
        )
        valid = k_pos <= cur
        if has_start:
            valid &= k_pos >= start_ref[b_idx]
        penalty = jnp.where(valid, 0.0, _NEG_INF)
        for hh in range(n_heads):
            lo, hi = hh * d, (hh + 1) * d
            qs = (q_ref[:, lo:hi] * sm_scale).astype(q_ref.dtype)  # (1, d)
            q8 = jnp.broadcast_to(qs, (8, d))
            s = _dot_tb(q8, k_ref[:, lo:hi]) + penalty  # (8, block_l) f32
            m_scr[hh], l_scr[hh], acc_scr[:, lo:hi] = _softmax_accumulate(
                s, v_ref[:, lo:hi], m_scr[hh], l_scr[hh], acc_scr[:, lo:hi]
            )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[:] = acc_scr[:1].astype(o_ref.dtype)


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
# occupied pages, not a pool-global [0, max_len). The work is O(live
# tokens): neither the pool's size nor the table's width is paid for.


# Tokens a chunk of the MHA walk (block-diagonal query, every head's lanes in
# one 1,536 B row) aims at: the contraction depth of the p @ V product on a
# 128 x 128 MXU, and eight 16-token pages.
_CHUNK_TOKENS = 128
# Bytes of ONE pool a chunk of the GROUPED walk aims at, and the most pages
# it copies. A chunk costs ~0.3 us whatever it moves (loop step, copy starts
# and waits, one-tile matmuls and a softmax chain that cannot pipeline): at
# 128 tokens of a 1,024 B row that is as long as the copy itself, and the
# walk read 47-48% of what HBM allows whatever the context. But every
# column of the tile is scored, live or not (~1.4 ns a column: a 100-token
# slot under a 1,024-token tile takes 2.0 us where a 128-token tile takes
# 0.76), and every page of the chunk is a `pl.when` at three sites, two
# pools each, that costs 50-80 ns taken or not. The kernel alone at the four
# grouped cells' shapes (experiments/paged_walk_time.py, PERF.md section 6,
# PR 45; us a call, 24 of 32 slots live, chunk of 128 / 256 / 512 / 1,024
# tokens): 4 KV heads of 128 on 64-token pages, 4,096 tokens from mid-table
# 524 / 443 / 316 / 318, 500 tokens 72 / 64 / 49 / 66, 100 tokens 24 / 38 /
# 44 / 65; one KV head of 128, 1,000 tokens 725 / 463 / 343 / 317; on
# 16-token pages (8 / 16 / 32 / 64 pages) 500 tokens 303 / 297 / 272 / 466.
# Eight pages of 64 KB hold the gain; past eight the page sites and the dead
# columns take back what the longer copy gives, and on 16-token pages there
# is nothing to give (its walk is bound by its 8 KB copies).
_GROUPED_CHUNK_BYTES = 512 << 10
_GROUPED_CHUNK_PAGES = 8
# VMEM the walk's four chunk buffers (K and V, two deep) may take: half of
# the 16 MiB a kernel gets by default.
_CHUNK_VMEM_BYTES = 8 << 20


# Rows a group of query heads is padded to a multiple of: a float32 sublane
# tile (the scores and the softmax state are float32; 20 heads -> 24 rows)
_GROUP_ROWS = 8


def _pages_per_chunk(block_size: int, hd_total: int, dtype,
                     tokens: int = _CHUNK_TOKENS, *,
                     table_pages: int | None = None) -> int:
    """Pages P the walk fetches and computes at a time. `tokens` is what a
    caller's chunk aims at (128 <= P * block_size < 256 whatever the page
    size, one page when a page is longer). A GROUPED walk hands in the width
    of its page table instead (`table_pages`), and its chunk aims at bytes:
    `_GROUPED_CHUNK_BYTES` of one pool, at most `_GROUPED_CHUNK_PAGES`
    pages, never more than the table holds (a slot's longest context).
    Either is halved while the four (P * block_size, h*hd) buffers would
    pass the VMEM budget (very wide models)."""
    row = hd_total * jnp.dtype(dtype).itemsize
    if table_pages is None:
        p = -(-tokens // block_size)
    else:
        p = max(1, min(_GROUPED_CHUNK_BYTES // (block_size * row),
                       _GROUPED_CHUNK_PAGES, table_pages))
    while p > 1 and 4 * p * block_size * row > _CHUNK_VMEM_BYTES:
        p //= 2
    return p


def _paged_walk_kernel(
    len_ref, start_ref, pt_ref,          # scalar prefetch (SMEM)
    q_ref, k_hbm, v_hbm, o_ref,          # q/out blocks; the pools, in HBM
    k_buf, v_buf, sem, first_buf,        # scratch
    *, sm_scale, block_size, pages, d, rows, kv_heads=None, v_lanes=None,
    lane_heads=None,
):
    """Grid (slots,): one cell walks ONE slot's live pages, columns
    `attn_start // block_size` to `len // block_size` of its page-table
    row and no others, `pages` at a time. The pools stay in HBM; a
    chunk's pages come by one async copy each into a (pages *
    block_size, h*hd) VMEM buffer, K and V, two buffers deep: chunk c+1
    is in flight while chunk c is computed, and a slot's last chunk
    starts the next slot's first, so only the call's first chunk is
    waited for with nothing to do (`first_buf` carries the buffer
    parity from cell to cell, which is why the grid is "arbitrary").

    The tile body is the online softmax of the flat kernel with the
    heads batched: the query is laid out as `rows` block-diagonal rows
    (row h holds head h's d lanes, zeros elsewhere), so ONE q @ K^T
    over all h*hd lanes gives every head's scores (the zeros add
    nothing: bf16 products, float32 sums, as a per-head dot) and ONE
    p @ V gives (rows, h*hd), of which row h's own d lanes are head h's
    output. Two matmuls a chunk where a head loop makes 2 * h, each on
    an (8, d) query tile: that loop, not the bytes, set the old
    kernel's time (PERF.md section 6, PR 25).

    Grouped queries (`kv_heads` set: fewer KV heads than query heads) need
    no block diagonal: q and the output come as (heads, d) blocks, and the
    `rows` query heads that share KV head j are the rows of ONE matmul
    against that head's own d lanes of the chunk, so a chunk is two
    matmuls a KV head and K and V are read once for the whole group.

    A LATENT pool (`v_hbm` and `v_buf` None, `v_lanes` set; kv_heads 1:
    absorbed multi-head latent attention, `_latent_walk_kernel`) is the
    grouped walk with ONE pool: a row is a token's key, `d` lanes wide,
    and its first `v_lanes` lanes are the token's value, so a page comes
    by one copy and the chunk's value tile is a lane slice of its key
    tile.

    A LIST walk (`lane_heads` set, kv_heads 1: ops/sparse_attention.py
    `sparse_walk`): the grid is (slots * lane_heads,), row c of `pt_ref` is
    the pages ONE KV head of a slot attends, and a page comes as that head's
    own d lanes of the pool's row (head c % lane_heads).

    Pages of a chunk past the slot's last are not fetched; their rows
    keep what an earlier chunk left (zeros at first), which is finite,
    so the mask's -1e30 turns them into exact zeros."""
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    mb = pt_ref.shape[1]
    bs = block_size
    tile = pages * bs

    def span(slot):
        """First and last live page-table column of `slot`."""
        last = jnp.minimum(len_ref[slot] // bs, mb - 1)
        return jnp.clip(start_ref[slot] // bs, 0, last), last

    pools = ((k_hbm, k_buf, 0),) if v_hbm is None \
        else ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))

    def copies(slot, first, last, c, buf, go):
        """Start (`go`) or wait for chunk c of `slot` in buffer `buf`."""
        for i in range(pages):
            col = first + c * pages + i

            @pl.when(col <= last)
            def _(i=i, col=col):
                page = pt_ref[slot, col]
                rows_i = pl.ds(i * bs, bs)
                for hbm, dst, which in pools:
                    src = hbm.at[page] if lane_heads is None else hbm.at[
                        page, :, pl.ds((slot % lane_heads) * d, d)]
                    dma = pltpu.make_async_copy(
                        src, dst.at[buf, rows_i], sem.at[which, buf]
                    )
                    if go:
                        dma.start()
                    else:
                        dma.wait()

    first, last = span(b)
    n_chunks = (last - first) // pages + 1

    @pl.when(b == 0)
    def _open():
        for _, dst, _ in pools:
            dst[...] = jnp.zeros(dst.shape, dst.dtype)
        first_buf[0] = 0
        copies(b, first, last, 0, 0, True)

    buf0 = first_buf[0]
    nxt_slot = jnp.minimum(b + 1, n_slots - 1)
    nxt_first, nxt_last = span(nxt_slot)

    grouped = kv_heads is not None
    # a retired slot's pinned length may lie past its table
    cur, start = jnp.minimum(len_ref[b], mb * bs - 1), start_ref[b]
    offs = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
    if grouped:
        qs = (q_ref[...] * sm_scale).astype(q_ref.dtype)    # (h, d)
        lanes = [(j * rows, j * d) for j in range(kv_heads)]
        width = v_lanes or d
    else:
        hd_total = q_ref.shape[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, hd_total), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, hd_total), 0)
        own = (lane >= row * d) & (lane < (row + 1) * d)  # row h: head h
        qs = (q_ref[...] * sm_scale).astype(q_ref.dtype)    # (1, h*hd)
        q_bd = jnp.where(
            own, jnp.broadcast_to(qs.astype(jnp.float32), own.shape), 0.0
        ).astype(q_ref.dtype)
        lanes, width = [None], hd_total

    def chunk(c, states):
        buf = (buf0 + c) % 2
        ends = c + 1 == n_chunks

        @pl.when(jnp.logical_not(ends) | (b + 1 < n_slots))
        def _prefetch():
            copies(jnp.where(ends, nxt_slot, b),
                   jnp.where(ends, nxt_first, first),
                   jnp.where(ends, nxt_last, last),
                   jnp.where(ends, 0, c + 1), 1 - buf, True)

        copies(b, first, last, c, buf, False)
        k_pos = (first + c * pages) * bs + offs
        penalty = jnp.where((k_pos <= cur) & (k_pos >= start), 0.0, _NEG_INF)
        if not grouped:
            s = _dot_tb(q_bd, k_buf[buf]) + penalty      # (rows, tile) f32
            return (_softmax_accumulate(s, v_buf[buf], *states[0]),)
        out = []
        for (r0, l0), state in zip(lanes, states):
            s = _dot_tb(qs[r0:r0 + rows],
                        k_buf[buf, :, l0:l0 + d]) + penalty
            v_tile = k_buf[buf, :, :v_lanes] if v_buf is None \
                else v_buf[buf, :, l0:l0 + d]
            out.append(_softmax_accumulate(s, v_tile, *state))
        return tuple(out)

    states = lax.fori_loop(0, n_chunks, chunk, tuple((
        jnp.full((rows, _LANES), -jnp.inf, jnp.float32),
        jnp.zeros((rows, _LANES), jnp.float32),
        jnp.zeros((rows, width), jnp.float32),
    ) for _ in lanes))
    first_buf[0] = (buf0 + n_chunks) % 2
    if grouped:
        for (r0, _), (_, _, acc) in zip(lanes, states):
            o_ref[r0:r0 + rows, :] = acc.astype(o_ref.dtype)
    else:
        o_ref[...] = jnp.sum(
            jnp.where(own, states[0][2], 0.0), axis=0, keepdims=True
        ).astype(o_ref.dtype)


def _latent_walk_kernel(len_ref, start_ref, pt_ref, q_ref, c_hbm, o_ref,
                        c_buf, sem, first_buf, **kw):
    """`_paged_walk_kernel` over ONE pool (see its docstring)."""
    _paged_walk_kernel(len_ref, start_ref, pt_ref, q_ref, c_hbm, None, o_ref,
                       c_buf, None, sem, first_buf, **kw)


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
    n_kv_heads=None,          # KV heads in the pool (None = n_heads)
    k_scale=None,             # (num_blocks, h, block_size) f32 — int8
    v_scale=None,             # pool per-block dequant scale pages
) -> jnp.ndarray:
    """XLA gather path: materialize each slot's pages as a contiguous
    (b, max_blocks_per_slot * block_size) span and run masked attention.

    The span is the PER-SLOT capacity (sized to the request's own
    context budget), not the pool — a flat cache's cost driver is the
    batch-wide [0, max_len) scan, which this path already removes.
    It is also the correctness oracle for `_paged_walk_kernel` (and the
    int8 kernel) and the serving path on backends without the kernel (CPU
    tests; unpackable head shapes). An int8 pool dequantizes through
    its scale pages during the gather."""
    from ddp_practice_tpu.ops.attention import attention_with_mask

    b = q.shape[0]
    kvh = n_kv_heads or n_heads
    hh = q.shape[2]
    d = hh // n_heads
    span = page_table.shape[1] * k_pages.shape[1]
    k = gather_pages(k_pages, page_table, kvh, k_scale)
    v = gather_pages(v_pages, page_table, kvh, v_scale)
    if kvh != n_heads:   # query head i reads KV head i // group
        k = jnp.repeat(k, n_heads // kvh, axis=2)
        v = jnp.repeat(v, n_heads // kvh, axis=2)
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
    """The int8 block pool's kernel, one page a grid cell: grid (slots,
    table columns), the kv tile of cell (b, j) is pool block
    `pt_ref[b, j]` through the BlockSpec index map, and the per-block
    (h, block_size) scale tiles ride the SAME map as the K/V tiles they
    dequantize. Cells past the slot's length are gated off and their
    DMA pinned to column 0 (unchanged index -> no copy), but each is
    still a grid step: the bf16 pool left this walk for
    `_paged_walk_kernel`, and this one follows when its scale pages can
    ride a chunk's copies. The K scale multiplies
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
    n_kv_heads=None,
    k_scale=None,
    v_scale=None,
    impl: str = "auto",
    name: str = "paged_decode",
) -> jnp.ndarray:
    """One paged decode step; returns (b, 1, h*hd). See the module-level
    paged section for the layout.

    The kernel is `_paged_walk_kernel`: one grid cell a slot, the
    slot's live pages fetched `_pages_per_chunk` at a time by
    double-buffered async copies, whatever the table's width. The
    traced device op is ONE, named `paged_decode` (`paged_decode_int8`
    for the int8 pool): perf/lib/readers.py sums the ops whose name
    holds "paged_decode". A layer whose walk readers should tell apart
    gives it a `name` of its own (a window layer's `window_walk`: the same
    kernel from a later `attn_start`, through its own group's table).

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
    kvh = n_kv_heads or n_heads
    group = n_heads // kvh
    if group == 1:
        packable = _heads_per_pack(n_heads, d) is not None and bs % 8 == 0
    else:
        # the group's query heads are the sublane rows of a matmul (padded
        # to whole sublane tiles where the group is no multiple of 8) and
        # a KV head's lanes a whole-tile slice of the chunk; no int8 pool
        packable = d % _LANES == 0 and bs % 8 == 0 and not quant
    if impl == "reference" or (impl == "auto" and (
            not packable or not backend.on_tpu())):
        return paged_attention_reference(
            q, k_pages, v_pages, page_table, lengths, attn_start,
            n_heads=n_heads, n_kv_heads=kvh, k_scale=k_scale,
            v_scale=v_scale,
        )
    if not packable:
        raise ValueError(
            f"impl='kernel' needs packable heads (h={n_heads}, d={d}) "
            f"and a block_size multiple of 8 (got {bs})"
        )
    sm_scale = 1.0 / (d ** 0.5)
    has_start = attn_start is not None
    lens = jnp.asarray(lengths, jnp.int32)
    start = (
        jnp.asarray(attn_start, jnp.int32)
        if has_start else jnp.zeros((b,), jnp.int32)
    )
    pt = jnp.asarray(page_table, jnp.int32)
    q_spec = pl.BlockSpec((None, 1, hd_total), lambda b_, *_: (b_, 0, 0))
    out_shape = jax.ShapeDtypeStruct((b, 1, hd_total), q.dtype)
    interpret = not backend.on_tpu()
    if quant:
        # the int8 pool keeps the one-page-a-cell walk (_paged_kernel_quant)
        def kv_map(b_, j, len_ref, start_ref, pt_ref):
            j_sel = lax.select(j * bs <= len_ref[b_], j, 0)
            return (pt_ref[b_, j_sel], 0, 0)

        kv_spec = pl.BlockSpec((None, bs, hd_total), kv_map)
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
                grid=(b, page_table.shape[1]),
                in_specs=[q_spec, kv_spec, kv_spec,
                          scale_spec, scale_spec],
                out_specs=q_spec,
                scratch_shapes=[
                    pltpu.VMEM((n_heads, 8, _LANES), jnp.float32),
                    pltpu.VMEM((n_heads, 8, _LANES), jnp.float32),
                    pltpu.VMEM((8, hd_total), jnp.float32),
                ],
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret,
            name="paged_decode_int8",
        )(lens, start, pt, q, k_pages, v_pages, k_scale, v_scale)
    kv_total = kvh * d
    # fewer KV heads than query heads: the chunk follows the pool's shape
    pages = _pages_per_chunk(
        bs, kv_total, k_pages.dtype,
        table_pages=page_table.shape[1] if group > 1 else None)
    if group > 1:
        # q and out as (heads, d) blocks: a free reshape out here, and in
        # the kernel a KV head's query heads are then whole rows. A group
        # that is no multiple of 8 (20 query heads on one KV head) is
        # padded with zero rows: they cost MXU rows the pass had spare,
        # score nothing but zeros and are cut from the output below
        rows = -(-group // _GROUP_ROWS) * _GROUP_ROWS
        q = q.reshape(b, kvh, group, d)
        if rows != group:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, rows - group), (0, 0)))
        q = q.reshape(b, kvh * rows, d)
        q_spec = pl.BlockSpec((None, kvh * rows, d),
                              lambda b_, *_: (b_, 0, 0))
        out_shape = jax.ShapeDtypeStruct((b, kvh * rows, d), q.dtype)
        kernel = functools.partial(
            _paged_walk_kernel, sm_scale=sm_scale, block_size=bs,
            pages=pages, d=d, rows=rows, kv_heads=kvh,
        )
    else:
        kernel = functools.partial(
            _paged_walk_kernel, sm_scale=sm_scale, block_size=bs,
            pages=pages, d=d,
            rows=-(-n_heads // 16) * 16,   # whole bf16 sublane tiles
        )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    chunk_buf = pltpu.VMEM((2, pages * bs, kv_total), k_pages.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=[
                chunk_buf, chunk_buf,
                pltpu.SemaphoreType.DMA((2, 2)),   # (K | V, buffer)
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=out_shape,
        # cells run in order: each starts the next one's first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=name,
    )(lens, start, pt, q, k_pages, v_pages)
    if group > 1 and rows != group:
        out = out.reshape(b, kvh, rows, d)[:, :, :group]
    return out.reshape(b, 1, hd_total)


# ----------------------------------------------- absorbed latent attention
# Multi-head latent attention keeps ONE row a token and layer: the
# normalised latent c (its first `v_lanes` lanes) and the one rotated key
# all heads share. With the key expansion absorbed into the query and the
# value expansion applied after, a decode step is multi-query attention over
# that row: score_h = q~_h . row, out_h = sum_s p_h(s) row(s)[:v_lanes]
# (models/mla_lm.py). The walk reads each live row once, for all heads.

# Tokens a chunk of the latent walk aims at: a row is read once for all
# heads, so a chunk is two matmuls however long it is, and a longer one
# spreads the loop's fixed cost over more bytes.
_MLA_CHUNK_TOKENS = 1024


def paged_mla_reference(q, latent_pages, page_table, lengths,
                        attn_start=None, *, v_lanes: int, sm_scale: float):
    """XLA gather path of `paged_decode_mla`: each slot's pages as one
    span, masked softmax in float32."""
    span = jnp.take(latent_pages, page_table, axis=0)    # (b, mb, bs, w)
    b = q.shape[0]
    span = span.reshape(b, -1, span.shape[-1])
    pos = jnp.arange(span.shape[1], dtype=jnp.int32)[None, :]
    valid = pos <= lengths[:, None]
    if attn_start is not None:
        valid &= pos >= attn_start[:, None]
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(span.dtype), span,
                        preferred_element_type=jnp.float32) * sm_scale
    scores = jnp.where(valid[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", probs.astype(span.dtype),
                     span[..., :v_lanes], preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_decode_mla(q, latent_pages, page_table, lengths, attn_start=None,
                     *, v_lanes: int, sm_scale: float, impl: str = "auto"):
    """One absorbed latent-attention decode step over a paged latent pool.

    q (b, heads, w): each head's absorbed query over the pool's row, zeros
    in whatever lanes of the row are padding; latent_pages (num_blocks,
    block_size, w); returns (b, heads, v_lanes), each head's sum of the
    rows' first `v_lanes` lanes (the caller expands it to the head's
    value). `lengths` is each slot's current position, inclusive, and
    `attn_start` its first, as in `paged_decode_attention`.

    The kernel is `_paged_walk_kernel` with one pool and one "KV head":
    ONE device op named `paged_decode_mla`, one copy a page (a row is key
    and value). impl as in `paged_decode_attention`."""
    b, heads, w = q.shape
    bs = latent_pages.shape[1]
    packable = (w % _LANES == 0 and v_lanes % _LANES == 0
                and heads % 8 == 0 and bs % 8 == 0)
    if impl == "reference" or (impl == "auto" and (
            not packable or not backend.on_tpu())):
        return paged_mla_reference(
            q, latent_pages, page_table, lengths, attn_start,
            v_lanes=v_lanes, sm_scale=sm_scale)
    if not packable:
        raise ValueError(
            f"impl='kernel' needs a row ({w}) and a value ({v_lanes}) of "
            f"whole lane tiles, heads ({heads}) and block_size ({bs}) "
            f"multiples of 8")
    lens = jnp.asarray(lengths, jnp.int32)
    start = (jnp.zeros((b,), jnp.int32) if attn_start is None
             else jnp.asarray(attn_start, jnp.int32))
    pages = _pages_per_chunk(bs, w, latent_pages.dtype, _MLA_CHUNK_TOKENS)
    kernel = functools.partial(
        _latent_walk_kernel, sm_scale=sm_scale, block_size=bs, pages=pages,
        d=w, rows=heads, kv_heads=1, v_lanes=v_lanes)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, heads, w), lambda b_, *_: (b_, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, v_lanes),
                                   lambda b_, *_: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, w), latent_pages.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, v_lanes), q.dtype),
        # cells run in order: each starts the next one's first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=not backend.on_tpu(),
        name="paged_decode_mla",
    )(lens, start, jnp.asarray(page_table, jnp.int32), q, latent_pages)
