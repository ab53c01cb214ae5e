"""Block-sparse attention over a paged cache (InfLLM-v2's trainable sparse
attention, as MiniCPM4 / MiniCPM-SALA state it): past `dense_len` visible
tokens a query attends only `topk` BLOCKS of its context, picked by scoring
compressed keys.

    compressed key   K~_j = mean(k[stride*j : stride*j + kernel])   a KV head
    a query at position t sees n = t + 1 tokens; row j is valid when
    stride*j + kernel <= n
    a_{h,j} = softmax_j(q_h . K~_j / sqrt(d))      over the valid rows
    r_j     = sum of a_{h,j} over the query heads of the KV head's group
    b_m     = max(r_j : 4m - 1 <= j <= 4m + 3)     max-pool (5, 4, pad 1):
                                                   a block is 4 strides
    b_m     = +inf for the first `init_blocks` blocks and for the blocks
              that hold the last `window` tokens (the query's own included)
    the `topk` highest b_m are picked (the forced ones count among them);
    o_h     = causal softmax of q_h k / sqrt(d) over the picked blocks'
              tokens, every head of the group over the same blocks
    n <= dense_len: every visible block, plain causal attention.

A block IS a page of the paged cache (`block` = the engine's page size), so a
pick is a page-table column, and a third pool rides the table beside K and
V: the compressed keys, `block / stride` rows a page (K only), row j in the
page of block j // 4 whatever tokens its window reaches into.

Three device ops, named for the readers (PERF.md section 3):

    sparse_select   decode: a slot's 2 x 16 query rows against its
                    compressed rows through the page table, group sum,
                    pool, forced blocks, top-k -> (slots, kv heads, W)
                    physical pages in ascending order and each list's
                    length in tokens. Plain XLA under a jit of that name.
    sparse_walk     decode: `ops/decode_attention.py _paged_walk_kernel`
                    fed a list in place of a table row, one KV head a grid
                    cell. A list is walked as if it were a sequence of its
                    own: entry i holds "positions" [i*block, (i+1)*block),
                    the last entry is the query's own page, and the length
                    handed over is (entries - 1) * block + t % block, so the
                    walk's own mask cuts the page's tail. A slot under
                    `dense_len` gets the list of all its pages: one kernel
                    serves both and nothing here is named `paged_decode`.
    sparse_prefill  a chunk of queries at positions [pos0, pos0 + s)
                    against the slot's pages (the chunk's own keys are
                    written before): a flash kernel over (KV head, tile of
                    128 query positions, `PREFILL_FOLD` entries of the
                    tile's list) cells that runs only the pages SOME row of
                    the tile picked (their list comes by scalar prefetch,
                    a page an index map, joined in VMEM to one 256-key
                    tile a step; ONE kernel whatever the context, its list
                    axis bounded at run time by what the chunk's end can
                    see) and masks by row inside, so the result
                    is the per-row selection above and no (chunk x context)
                    score matrix exists. Gathering a row's 64 pages instead
                    would move 2.1 MB a row and KV head, ~120 GB a 14k
                    prompt: slower than dense flash (PERF.md section 6).

Off the TPU all three are plain jax.numpy (`*_reference`), which is also
what the tests hold the kernels to.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.decode_attention import (
    _GROUP_ROWS,
    _paged_walk_kernel,
)
from ddp_practice_tpu.ops.flash_attention import (
    _LANES,
    _NEG_INF,
    _dot_tb,
    _widen,
)
from ddp_practice_tpu.utils import backend

# the pool over compressed rows that scores a block: (kernel, stride, pad)
POOL = (5, 4, 1)
# query positions a grid cell of `sparse_prefill` holds (x the group's heads
# = the rows of its matmuls)
PREFILL_TILE = 128
# entries of a tile's pick list (pages) a grid step of `sparse_prefill` folds:
# 4 pages of 64 are 256 keys under one update of the softmax state, whose 512
# lane reductions and 2,048-row rescale cost a step the same whatever its
# keys. Timed alone against 1 and 2 on the chip by
# `experiments/sparse_prefill_time.py` (PERF.md section 6, PR 41)
PREFILL_FOLD = 4
# the "position" of a dead list entry's keys: past every query
_DEAD_POS = 2 ** 30


class SparseSpec(NamedTuple):
    """The sizes of the selection (module docstring). `block` is the page."""

    block: int = 64
    kernel: int = 32
    stride: int = 16
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192
    topk: int = 64

    def check(self) -> "SparseSpec":
        if self.block != POOL[1] * self.stride:
            raise ValueError(
                f"a block ({self.block}) is {POOL[1]} strides of the "
                f"compressed keys ({self.stride}): the pool's stride")
        if self.dense_len < self.topk * self.block:
            raise ValueError(
                f"dense_len {self.dense_len} must hold topk {self.topk} "
                f"blocks of {self.block}: a sparse row has topk to pick")
        if self.init_blocks + -(-self.window // self.block) + 1 > self.topk:
            raise ValueError("the forced blocks must fit among the topk")
        return self

    @property
    def rows(self) -> int:
        """Compressed rows a page holds."""
        return self.block // self.stride

    @property
    def list_pages(self) -> int:
        """Columns of a walk list: the picks, or a dense slot's pages."""
        return max(self.topk, -(-self.dense_len // self.block))


def compress(window):
    """(..., kernel, lanes) keys -> (..., lanes): their float32 mean in the
    keys' type."""
    return jnp.mean(window.astype(jnp.float32), axis=-2).astype(window.dtype)


def due_rows(spec: SparseSpec, lo, hi, count: int):
    """Compressed rows whose window ENDS in positions (lo, hi]: (j, due)
    for `count` candidates from the first; `due` false past the last."""
    first = jnp.maximum(lo - spec.kernel, -spec.stride) // spec.stride + 1
    j = first[..., None] + jnp.arange(count, dtype=jnp.int32)
    return j, spec.stride * j + spec.kernel <= hi[..., None]


def block_scores(q, index, n, start, spec: SparseSpec):
    """b_m of the module docstring. q (b, s, kvh, g, d); index (b, J, kvh, d)
    the sequence's compressed rows, J = rows * blocks; n (b, s) visible
    tokens a row; start (b,) first real position. Returns (b, s, kvh,
    J // rows) float32: -inf where a block has no valid row."""
    d = q.shape[-1]
    jj = jnp.arange(index.shape[1], dtype=jnp.int32)
    valid = (spec.stride * jj + spec.kernel <= n[..., None]) \
        & (spec.stride * jj >= start[:, None, None])         # (b, s, J)
    valid = valid[:, :, None, None, :]
    logits = jnp.einsum("bsngd,bjnd->bsngj", q, index,
                        preferred_element_type=jnp.float32) * d ** -0.5
    logits = jnp.where(valid, logits, -jnp.inf)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(logits - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    a = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    r = jnp.where(valid[:, :, :, 0], jnp.sum(a, axis=3), -jnp.inf)
    kern, stride, pad = POOL
    blocks = index.shape[1] // stride
    r = jnp.pad(r, ((0, 0),) * 3 + ((pad, kern - stride - pad),),
                constant_values=-jnp.inf)
    return functools.reduce(jnp.maximum, (
        r[..., i:i + stride * blocks:stride] for i in range(kern)))


def _forced(scores, pos, start, spec: SparseSpec):
    """`scores` with +inf at the blocks every row attends: the first
    `init_blocks` and those that hold the last `window` tokens."""
    m = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    pos = pos[..., None]
    first = start[:, None, None] // spec.block
    near = jnp.maximum(pos - spec.window + 1, 0) // spec.block
    forced = (m >= first) & (m <= pos // spec.block) \
        & ((m < first + spec.init_blocks) | (m >= near))     # (b, s, M)
    return jnp.where(forced[:, :, None, :], jnp.inf, scores)


def select_blocks(scores, pos, start, spec: SparseSpec):
    """Forced blocks and the top-k: scores (b, s, kvh, M) from
    `block_scores`, pos (b, s) each row's position -> picks (b, s, kvh,
    topk) block indices, highest score first (ties and the forced blocks:
    lowest index first)."""
    scores = _forced(scores, pos, start, spec)
    return lax.top_k(scores, min(spec.topk, scores.shape[-1]))[1]


def select_mask(scores, pos, start, spec: SparseSpec):
    """`picked_mask(select_blocks(...))` without a sort: (b, s, kvh, M) bool,
    the same `topk` blocks a row. The k-th largest score is found by
    bisection over the scores' bit patterns (32 counts over M values a row,
    exact: a float32's bits order as an unsigned integer once the sign is
    flipped and the negative ones inverted); a row then takes every block above it and, of those
    equal to it, the lowest indices up to `topk`, which is `top_k`'s order.
    On the TPU `top_k` of 64 among 536 is a whole sort a row, a third of a
    prompt chunk's selection (PERF.md section 6)."""
    k = min(spec.topk, scores.shape[-1])
    bits = lax.bitcast_convert_type(
        _forced(scores, pos, start, spec).astype(jnp.float32), jnp.int32)
    keys = lax.bitcast_convert_type(       # unsigned, ordered as the floats
        jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31)), jnp.uint32)

    def take_bit(i, kth):   # the largest t with k keys >= t, a bit a step
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = lax.fori_loop(0, 32, take_bit,
                        jnp.zeros(keys.shape[:-1], jnp.uint32))
    above = keys > kth[..., None]
    level = keys == kth[..., None]
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (level & (jnp.cumsum(level, axis=-1) <= room))


def picked_mask(picks, blocks: int):
    """picks (..., k) -> (..., blocks) bool."""
    return jnp.any(picks[..., None] == jnp.arange(blocks), axis=-2)


def sparse_attention_reference(q, k, v, index, pos, start, spec: SparseSpec):
    """The whole layer's attention in plain jax.numpy: q (b, s, h, d) at
    positions pos (b, s); k, v (b, L, kvh, d) and index (b, J, kvh, d) the
    sequence's span from position 0 (J * stride <= L); start (b,) the first
    real position. Returns (out (b, s, h, d) in q's type, picks (b, s, kvh,
    topk)); a dense row's picks are not used."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    blocks = index.shape[1] // spec.rows
    qg = q.reshape(b, s, kvh, h // kvh, d)
    n = pos + 1 - start[:, None]
    picks = select_blocks(
        block_scores(qg, index, pos + 1, start, spec), pos, start, spec)
    span = jnp.arange(k.shape[1], dtype=jnp.int32)
    seen = (span <= pos[..., None]) & (span >= start[:, None, None])
    of_block = jnp.minimum(span // spec.block, blocks - 1)
    picked = jnp.take_along_axis(
        picked_mask(picks, blocks),
        jnp.broadcast_to(of_block, (b, s, kvh, k.shape[1])), axis=-1)
    dense = (n <= spec.dense_len)[:, :, None, None]
    mask = seen[:, :, None, :] & (dense | picked)            # (b, s, kvh, L)
    scores = jnp.einsum("bsngd,blnd->bsngl", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    probs = jax.nn.softmax(
        jnp.where(mask[:, :, :, None, :], scores, _NEG_INF), axis=-1)
    out = jnp.einsum("bsngl,blnd->bsngd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype), picks


# ------------------------------------------------------------------ decode
def _gather_index(index_pool, page_table):
    """(blocks, rows, lanes) pool through (b, mb) tables -> (b, mb * rows,
    lanes): each slot's compressed rows in order."""
    b = page_table.shape[0]
    rows = jnp.take(index_pool, page_table, axis=0)
    return rows.reshape(b, -1, index_pool.shape[-1])


@functools.partial(jax.jit, static_argnames=("spec", "kv_heads"))
def sparse_select(q, index_pool, page_table, lengths, attn_start, *,
                  spec: SparseSpec, kv_heads: int):
    """One decode step's lists. q (b, h, d) at positions `lengths` (b,);
    index_pool (blocks, rows, kvh*d); page_table (b, mb). Returns (pages
    (b, kvh, W) int32 physical pages ascending by block, GARBAGE past a
    list's end; tokens (b, kvh) int32 the list's length as the walk reads
    it: (entries - 1) * block + the query's offset in its page; held (b,)
    int32 pages a dense walk would have read)."""
    b, h, d = q.shape
    mb, width = page_table.shape[1], spec.list_pages
    index = _gather_index(index_pool, page_table).reshape(
        b, mb * spec.rows, kv_heads, d)
    pos = lengths[:, None]
    picks = select_blocks(
        block_scores(q.reshape(b, 1, kv_heads, h // kv_heads, d), index,
                     pos + 1, attn_start, spec),
        pos, attn_start, spec)[:, 0]                         # (b, kvh, topk)
    picks = jnp.sort(picks, axis=-1)
    first = attn_start // spec.block
    held = jnp.minimum(lengths // spec.block, mb - 1) - first + 1
    dense = (lengths + 1 - attn_start <= spec.dense_len)[:, None, None]
    cols = jnp.arange(width, dtype=jnp.int32)
    picks = jnp.pad(picks, ((0, 0), (0, 0), (0, width - picks.shape[-1])),
                    constant_values=mb)
    blocks = jnp.where(dense, first[:, None, None] + cols, picks)
    entries = jnp.broadcast_to(jnp.where(
        dense[:, :, 0], held[:, None], min(spec.topk, mb)), (b, kv_heads)
    ).astype(jnp.int32)
    live = cols < entries[..., None]
    pages = jnp.take_along_axis(
        jnp.broadcast_to(page_table[:, None], (b, kv_heads, mb)),
        jnp.minimum(blocks, mb - 1), axis=-1)
    tokens = (entries - 1) * spec.block + lengths[:, None] % spec.block
    # a dense list starts at the page of `attn_start`: the walk's own
    # `start` (handed over by the caller) masks that page's head
    return (jnp.where(live, pages, 0).astype(jnp.int32), tokens,
            held.astype(jnp.int32))


def sparse_walk_reference(q, k_pages, v_pages, pages, tokens, start):
    """q (b, h, d); pools (blocks, block, kvh*d); pages (b, kvh, W);
    tokens, start (b, kvh): positions [start, tokens] of each list are
    attended. Returns (b, h, d)."""
    b, h, d = q.shape
    kvh, width = pages.shape[1:]
    bs = k_pages.shape[1]

    def span(pool):
        got = jnp.take(pool, pages, axis=0)      # (b, kvh, W, bs, kvh*d)
        got = got.reshape(b, kvh, width * bs, kvh, d)
        own = jnp.arange(kvh)
        return got[:, own, :, own]               # (kvh, b, W*bs, d)

    k, v = span(k_pages), span(v_pages)
    at = jnp.arange(width * bs, dtype=jnp.int32)
    seen = (at <= tokens[..., None]) & (at >= start[..., None])
    qg = q.reshape(b, kvh, h // kvh, d)
    scores = jnp.einsum("bngd,nbld->bngl", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    probs = jax.nn.softmax(
        jnp.where(seen[:, :, None, :], scores, _NEG_INF), axis=-1)
    out = jnp.einsum("bngl,nbld->bngd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, d).astype(q.dtype)


# tokens a chunk of the list walk aims at: a page of 64 is one 16 KB copy a
# pool, so a chunk of 8 spreads the loop's fixed cost over 256 KB
_WALK_CHUNK_TOKENS = 512


def sparse_walk(q, k_pages, v_pages, pages, tokens, start, *,
                impl: str = "auto"):
    """One decode step over the lists of `sparse_select` (arguments as
    `sparse_walk_reference`): ONE device op named `sparse_walk`, grid
    (slots * kv heads,), the walk kernel of `paged_decode_attention` with a
    list for a table row and one KV head's lanes of a page a copy."""
    b, h, d = q.shape
    kvh, width = pages.shape[1:]
    if tokens.shape != (b, kvh) or start.shape != (b, kvh):
        raise ValueError(
            f"a length and a start a list: {tokens.shape}, {start.shape} "
            f"against {(b, kvh)}")   # the kernel reads them by grid cell
    bs, group = k_pages.shape[1], h // kvh
    packable = d % _LANES == 0 and bs % 8 == 0
    if impl == "reference" or (impl == "auto" and (
            not packable or not backend.on_tpu())):
        with jax.named_scope("sparse_walk"):
            return sparse_walk_reference(q, k_pages, v_pages, pages, tokens,
                                         start)
    if not packable:
        raise ValueError(
            f"impl='kernel' needs heads of whole lane tiles ({d}) and a "
            f"page of a multiple of 8 ({bs})")
    rows = -(-group // _GROUP_ROWS) * _GROUP_ROWS
    qg = q.reshape(b * kvh, group, d)
    if rows != group:
        qg = jnp.pad(qg, ((0, 0), (0, rows - group), (0, 0)))
    per = max(1, _WALK_CHUNK_TOKENS // bs)
    kernel = functools.partial(
        _paged_walk_kernel, sm_scale=d ** -0.5, block_size=bs, pages=per,
        d=d, rows=rows, kv_heads=1, lane_heads=kvh)
    q_spec = pl.BlockSpec((None, rows, d), lambda c, *_: (c, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    chunk_buf = pltpu.VMEM((2, per * bs, d), k_pages.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * kvh,),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=[
                chunk_buf, chunk_buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kvh, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=not backend.on_tpu(),
        name="sparse_walk",
    )(tokens.reshape(-1).astype(jnp.int32),
      start.reshape(-1).astype(jnp.int32),
      pages.reshape(b * kvh, width).astype(jnp.int32), qg, k_pages, v_pages)
    return out[:, :group].reshape(b, h, d)


# ----------------------------------------------------------------- prefill
def prefill_selection(q, index, pos, start, spec: SparseSpec):
    """Which blocks each row of a chunk attends: q (s, kvh, g, d) one
    sequence's chunk at positions pos (s,); index (J, kvh, d). Returns
    (s, kvh, M) bool: a dense row's visible blocks, a sparse row's picks.
    A tile of `PREFILL_TILE` rows at a time, so the scores against the
    compressed rows never stand for the whole chunk."""
    s, kvh = q.shape[:2]
    blocks = index.shape[0] // spec.rows
    tile = min(PREFILL_TILE, s)

    def one(args):
        qt, pt = args
        m = jnp.arange(blocks, dtype=jnp.int32)
        seen = (m <= pt[:, None] // spec.block) \
            & (m >= start // spec.block)
        dense = (pt + 1 - start <= spec.dense_len)[:, None]

        def picked():
            return select_mask(
                block_scores(qt[None], index[None], pt[None] + 1,
                             start[None], spec), pt[None], start[None],
                spec)[0]

        # a tile whose every row is dense (a prompt's first dense_len
        # tokens) scores nothing
        sparse = lax.cond(jnp.all(dense), lambda: jnp.zeros(
            (tile, kvh, blocks), bool), picked)
        return seen[:, None, :] & (dense[:, None, :] | sparse)

    out = lax.map(one, (q.reshape(s // tile, tile, *q.shape[1:]),
                        pos.reshape(s // tile, tile)))
    return out.reshape(s, kvh, blocks)


def _prefill_kernel(logi_ref, cnt_ref, pt_ref, pos_ref,       # SMEM
                    q_ref, bias_ref, *refs, block_size, tile, group, tiles,
                    width, fold):
    """Grid (kv heads, tiles, list entries / `fold`, as far as the chunk's
    last row sees: a run-time bound): cell (n, t, u) folds the `fold` blocks
    `logi[n, t, fold * u + j]` of the sequence (pages `pt[logi]`, fetched
    one a `BlockSpec` and joined in VMEM to ONE key tile and ONE value tile
    of `fold * block_size` keys) into the online softmax of tile t's
    `group * tile` query rows (q comes scaled), under the causal mask of
    each entry's own block and the rows' own picks (`bias`, a column a
    BLOCK in lane tiles of 128: 0 where the row picked it, -1e30 where not;
    an entry's column is spread over its keys by a one-hot matmul, no
    reduction over the list). An entry at or past the tile's count (a live
    step's tail) is masked whole; a step whose first entry is past it is not
    run, and neither fetches (the index maps repeat the last live page).
    The state is this kernel's own: running max and denominator replicated
    over the lanes, an accumulator that is NOT normalised until `_done`."""
    k_refs, v_refs = refs[:fold], refs[fold:2 * fold]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * fold:]
    n, t, u = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    cell = n * tiles + t
    count = cnt_ref[cell]

    @pl.when(u == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(fold * u < count)
    def _fold():
        bs, keys = block_size, fold * block_size
        col = lax.broadcasted_iota(jnp.int32, (tile, keys), 1)
        q_pos = pos_ref[0] + t * tile + lax.broadcasted_iota(
            jnp.int32, (tile, keys), 0)
        hot_row = lax.broadcasted_iota(jnp.int32, (_LANES, keys), 0)
        hot_col = lax.broadcasted_iota(jnp.int32, (_LANES, keys), 1)
        k_pos = jnp.full((tile, keys), _DEAD_POS, jnp.int32)
        own = jnp.zeros((tile, keys), jnp.float32)
        for j in range(fold):
            entry = fold * u + j
            block = logi_ref[cell * width + entry]
            mine = lambda at: (at >= j * bs) & (at < (j + 1) * bs)
            # a dead entry's keys stand past every query: the causal mask
            first = jnp.where(entry < count, block * bs, _DEAD_POS)
            k_pos = jnp.where(mine(col), first + col - j * bs, k_pos)
            hot = (hot_row == lax.rem(block, _LANES)) & mine(hot_col)
            own = own + jnp.dot(
                bias_ref[lax.div(block, _LANES)],
                jnp.where(hot, 1.0, 0.0).astype(bias_ref.dtype),
                preferred_element_type=jnp.float32)
        pen = jnp.where(k_pos <= q_pos, 0.0, _NEG_INF) + own   # (tile, keys)
        join = lambda rs: jnp.concatenate([r[...] for r in rs], axis=0)
        s = _dot_tb(q_ref[...], join(k_refs))         # (group*tile, keys)
        s = (s.reshape(group, tile, keys) + pen[None]).reshape(
            group * tile, keys)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _widen(m_next, keys))
        alpha = jnp.exp(m_prev - m_next)
        v = join(v_refs)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * _widen(alpha, v.shape[-1]) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_next

    @pl.when(u == pl.num_programs(2) - 1)
    def _done():
        l = l_scr[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[...] = (acc_scr[...] * _widen(l_inv, acc_scr.shape[-1])
                      ).astype(o_ref.dtype)


def sparse_prefill(q, k_pages, v_pages, selected, pt_row, pos0, *,
                   block: int, impl: str = "auto"):
    """A chunk's attention for ONE sequence. q (s, kvh, g, d) at positions
    pos0 + [0, s); pools (blocks, block, kvh*d) with the chunk's own keys
    written; selected (s, kvh, M) bool from `prefill_selection`; pt_row (mb,)
    the sequence's page table. Returns (s, kvh, g, d)."""
    s, kvh, group, d = q.shape
    mb = pt_row.shape[0]
    tile = min(PREFILL_TILE, s)
    fold = PREFILL_FOLD
    packable = d % _LANES == 0 and block % 8 == 0 and s % tile == 0 \
        and tile % 8 == 0
    if impl == "reference" or (impl == "auto" and (
            not packable or not backend.on_tpu())):
        with jax.named_scope("sparse_prefill"):
            return _sparse_prefill_reference(
                q, k_pages, v_pages, selected, pt_row, pos0, block)
    if not packable:
        raise ValueError("impl='kernel' needs heads of whole lane tiles, "
                         "a page of a multiple of 8 and whole tiles")
    tiles = s // tile
    full = -(-selected.shape[-1] // _LANES) * _LANES
    sel = selected.reshape(tiles, tile, kvh, -1)
    union = jnp.any(sel, axis=1)                              # (T, kvh, M)
    order = jnp.argsort(~union, axis=-1, stable=True)   # picked ones first
    count = jnp.sum(union, axis=-1).astype(jnp.int32)         # (T, kvh)
    pad = full - order.shape[-1]
    order = jnp.pad(order, ((0, 0), (0, 0), (0, pad))).astype(jnp.int32)
    at = jnp.arange(full, dtype=jnp.int32)
    # entries past the count repeat the last live one: no new copy
    logical = jnp.minimum(jnp.take_along_axis(
        order, jnp.minimum(at, jnp.maximum(count[..., None] - 1, 0)), -1),
        mb - 1)
    bias = jnp.where(jnp.pad(sel, ((0, 0),) * 3 + ((0, pad),)), 0.0,
                     _NEG_INF).astype(jnp.bfloat16)      # (T, tile, kvh, M)
    # a lane tile of 128 blocks a leading index: the kernel takes the tile
    # that holds an entry's block and never the whole width
    bias = jnp.transpose(
        bias.reshape(tiles, tile, kvh, full // _LANES, _LANES),
        (2, 0, 3, 1, 4))                     # (kvh, T, M / 128, tile, 128)
    to_cells = lambda a: jnp.moveaxis(a, 1, 0)      # (T, kvh, ..) -> kvh first
    logical, count = to_cells(logical), to_cells(count).reshape(-1)
    # scaled here, once a chunk, not in every grid step
    qk = (q * d ** -0.5).astype(q.dtype)
    qk = jnp.moveaxis(qk.reshape(tiles, tile, kvh, group, d), (2, 3), (0, 2))
    qk = qk.reshape(kvh, tiles, group * tile, d)
    kernel = functools.partial(
        _prefill_kernel, block_size=block, tile=tile, group=group,
        tiles=tiles, width=full, fold=fold)

    def page_spec(j):
        def page_map(n, t, u, logi, cnt, pt, pos):
            return pt[logi[(n * tiles + t) * full + fold * u + j]], 0, n
        return pl.BlockSpec((None, block, d), page_map)

    cell = lambda n, t, u, *_: (n, t, 0, 0)
    pages = [page_spec(j) for j in range(fold)]
    pos0 = jnp.asarray(pos0, jnp.int32)
    # ONE kernel whatever the context: the list axis of the grid ends where
    # the chunk's last row can see (a row picks among the blocks at or
    # before its own), a bound the call reads at run time
    steps = jnp.minimum((pos0 + s - 1) // block // fold + 1, full // fold)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(kvh, tiles, steps),
            in_specs=[
                pl.BlockSpec((None, None, group * tile, d), cell),
                pl.BlockSpec((None, None, full // _LANES, tile, _LANES),
                             lambda n, t, u, *_: (n, t, 0, 0, 0)),
                *pages, *pages,
            ],
            out_specs=pl.BlockSpec((None, None, group * tile, d), cell),
            scratch_shapes=[
                pltpu.VMEM((group * tile, _LANES), jnp.float32),
                pltpu.VMEM((group * tile, _LANES), jnp.float32),
                pltpu.VMEM((group * tile, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((kvh, tiles, group * tile, d),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not backend.on_tpu(),
        name="sparse_prefill",
    )(logical.reshape(-1), count, pt_row.astype(jnp.int32), pos0.reshape(1),
      qk, bias, *[k_pages] * fold, *[v_pages] * fold)
    out = out.reshape(kvh, tiles, group, tile, d)
    return jnp.moveaxis(out, (0, 2), (2, 3)).reshape(s, kvh, group, d)


def _sparse_prefill_reference(q, k_pages, v_pages, selected, pt_row, pos0,
                              block):
    s, kvh, group, d = q.shape
    span = lambda pool: jnp.take(pool, pt_row, axis=0).reshape(-1, kvh, d)
    k, v = span(k_pages), span(v_pages)
    at = jnp.arange(k.shape[0], dtype=jnp.int32)
    pos = pos0 + jnp.arange(s, dtype=jnp.int32)
    mask = (at <= pos[:, None])[:, None, :] & jnp.take_along_axis(
        selected, jnp.broadcast_to(
            jnp.minimum(at // block, selected.shape[-1] - 1),
            (s, kvh, k.shape[0])), axis=-1)
    scores = jnp.einsum("sngd,lnd->sngl", q, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    probs = jax.nn.softmax(
        jnp.where(mask[:, :, None, :], scores, _NEG_INF), axis=-1)
    return jnp.einsum("sngl,lnd->sngd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
