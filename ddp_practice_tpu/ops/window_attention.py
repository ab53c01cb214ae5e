"""Paged flash attention for a prompt's chunk: grouped KV heads, a causal
mask and a run-time FIRST KEY a query row.

    o_t = softmax_s(q_t . k_s / sqrt(d)) v_s     over the keys s with
          max(start, t - window + 1) <= s <= t

One sequence's chunk of `s` tokens stands at slot-local positions
`pos0 + [0, s)`; its keys and values, the chunk's own included, live in pools
of pages `(blocks, block, kv_heads * d)` behind the sequence's page-table
row. `window` and `start` are run-time scalars, so ONE kernel,
`window_prefill`, serves a layer that attends everything (`NO_WINDOW`) and a
layer with a sliding window, and one lowering serves every such layer of a
program.

A grid cell is a TILE of `WINDOW_TILE` query positions for ALL KV heads: a
KV head's `group` query heads x the tile's positions are the rows of one
matmul against whole pages fetched through the table. A tile walks the table
columns `lo_t .. hi_t` and no others: `hi_t` holds its last row's own
position, `lo_t` its FIRST row's first key, so pages wholly behind a tile's
window are neither fetched nor computed (a tile of 128 rows under a window
of 4,096 walks 66 or 67 pages of 64 whatever the context), and a tile wholly
past the chunk's real tokens walks none.

A tile's pages are CONSECUTIVE table columns, so the cell walks them itself,
`pages_per_step` at a time (from the shapes at trace time: 8 pages = 512 keys
at the window cell's 896 rows): the pools stay in HBM and a step's pages
come WHOLE, every KV head's lanes in one contiguous row, by one async copy
each into a VMEM buffer two deep, the next step's (or the next tile's first)
in flight while this one is computed (`ops/decode_attention.py
_paged_walk_kernel`'s scheme). Each KV head then folds the step's keys under
ONE update of its softmax state, so the state's load, rescale and store and
the cross-lane maximum are paid once a step and a page costs one copy's
issue for all heads, where a pipelined `BlockSpec` a page and head cost ~55
scalar bundles each in every grid step (PERF.md section 6, PR 46). A step
is one of three bodies by two scalar tests on the walk: a whole step none
of whose keys any row's mask can cut (at or before the tile's first row,
inside the last row's window, at or after `start`) adds no mask; a whole
step with an edge adds it; the walk's last step, where fewer pages are left
than a step folds, fetches and computes them a quarter of a step at a time
and no further. The denominator is kept as lane-folded partial sums and
reduced across lanes once a tile.

Off the TPU `window_prefill` is plain `jax.numpy` (`_prefill_reference`:
the sequence's whole span gathered, a mask), the kernel's oracle in the
tests.

A decode step of a window layer needs no kernel of its own:
`ops/decode_attention.py paged_decode_attention` already walks the columns
`attn_start // block .. len // block`, and the layer hands it
`window_start(...)` under the name `window_walk`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.flash_attention import (
    _LANES,
    _NEG_INF,
    _dot_tb,
    _widen,
)
from ddp_practice_tpu.utils import backend

# query positions a grid cell holds (times the group's heads: 7 x 128 = 896
# matmul rows at 28 heads on 4)
WINDOW_TILE = 128
# the window of a layer that has none: every position's first key is `start`
NO_WINDOW = 2 ** 30
# what a step's key tile may take: a head's float32 scores (896 rows x 512
# keys at the window cell: past that the tile's spills cost a key more than
# the state's update saves, PERF.md section 6, PR 46), and the pages'
# buffers (K and V, two deep, every KV head's lanes)
_SCORE_TILE_BYTES = 2 * 2 ** 20
_PAGE_BUFFER_BYTES = 4 * 2 ** 20


def pages_per_step(block: int, lanes: int, rows: int, columns: int,
                   itemsize: int = 2) -> int:
    """Pages a step folds into one key tile, from what a program's shapes
    say at trace time: a power of two, as many as keep a head's float32
    score tile (`rows` x keys) and the pages' buffers (`lanes` = KV heads x
    head width a row) inside their budgets, never more than the table has
    columns."""
    most = min(_SCORE_TILE_BYTES // (4 * rows * block),
               _PAGE_BUFFER_BYTES // (4 * block * lanes * itemsize), columns)
    return 2 ** max(int(most).bit_length() - 1, 0)


def _tail_pages(pages: int, block: int) -> int:
    """Pages the walk's last step computes at a time where it is not whole:
    a quarter of a step, of at least a lane tile of keys."""
    part = max(pages // 4, 1)
    while part < pages and part * block < _LANES:
        part *= 2
    return part


def window_start(lengths, attn_start, window: int):
    """First key a decode step attends: the query stands at `lengths`, so a
    window of `window` tokens (the query's own among them) begins at
    `lengths + 1 - window`, never before the sequence's own `attn_start`."""
    first = jnp.asarray(lengths, jnp.int32) + 1 - window
    if attn_start is None:
        return jnp.maximum(first, 0)
    return jnp.maximum(first, jnp.asarray(attn_start, jnp.int32))


def _prefill_reference(q, k_pages, v_pages, pt_row, pos0, start, window):
    s, kvh, group, d = q.shape
    span = lambda pool: jnp.take(pool, pt_row, axis=0).reshape(-1, kvh, d)
    k, v = span(k_pages), span(v_pages)
    at = jnp.arange(k.shape[0], dtype=jnp.int32)
    pos = (pos0 + jnp.arange(s, dtype=jnp.int32))[:, None]
    mask = (at <= pos) & (at >= start) & (at > pos - window)
    scores = jnp.einsum("sngd,lnd->sngl", q, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    probs = jax.nn.softmax(
        jnp.where(mask[:, None, None, :], scores, _NEG_INF), axis=-1)
    return jnp.einsum("sngl,lnd->sngd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _step_kind(lo, cnt, pos0, start, window, t, u, *, tile, block, pages):
    """(left, clear) of step u of tile t: the pages of the tile's walk that
    are left at the step's first column (it runs where any is, and is whole
    from `pages` up), and whether no row's mask can cut a key of a whole
    step: all at or before the tile's first row, inside its last row's
    window, at or after `start`. Scalars in the kernel, arrays on the
    host."""
    left = cnt - pages * u
    row0 = pos0 + tile * t
    key0 = (lo + pages * u) * block
    clear = (key0 + pages * block - 1 <= row0) & (key0 >= start) \
        & (key0 > row0 + tile - 1 - window)
    return left, clear


def _prefill_kernel(lo_ref, cnt_ref, pt_ref, at_ref,          # SMEM
                    q_ref, k_hbm, v_hbm, o_ref,   # q/out blocks; the pools
                    k_buf, v_buf, sem, m_scr, l_scr, acc_scr, carry,
                    *, block_size, tile, group, pages, part, mask_all):
    """Grid (tiles,): cell t walks tile t's table columns `lo[t] + [0,
    cnt[t])`, `pages` a step, for ALL KV heads: a step's pages come whole
    (every head's lanes, one contiguous row of the pool) by one async copy
    each into a (pages * block, kv_heads * d) buffer, K and V, two buffers
    deep: step u + 1 is in flight while step u is computed, and a tile's
    last step starts the next tile's first (`carry` hands the buffer's
    parity and whether that copy is in flight from cell to cell, which is
    why the grid is "arbitrary"). Each head's `group * tile` query rows
    (q comes scaled) then fold the step's keys, its own d lanes of the
    buffer, into its online softmax under ONE update of its state.
    `at_ref` = (pos0, start, window). The walk's last step, where fewer
    pages are left than a step folds, fetches and computes `part` pages at
    a time as far as pages are left; a column past the tile's `cnt[t]`
    inside such a part repeats the last live page under keys past every
    row, which the causal mask cuts. The state, a KV head: the running max
    replicated over the lanes, the denominator as partial sums a lane (one
    cross-lane sum in the end), an accumulator normalised once."""
    t, tiles = pl.program_id(0), pl.num_programs(0)
    kvh, rows, d = q_ref.shape
    mb = pt_ref.shape[0]
    pos0, start, window = at_ref[0], at_ref[1], at_ref[2]
    lo, cnt = lo_ref[t], cnt_ref[t]
    steps = (cnt + pages - 1) // pages
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def copies(tt, u, buf, go):
        """Start (`go`) or wait for the pages of step u of tile tt in
        buffer `buf`: a whole step's, or the parts its last step runs."""
        first, left = lo_ref[tt] + pages * u, cnt_ref[tt] - pages * u
        for g in range(0, pages, part):
            @pl.when(left > g)
            def _(g=g):
                for j in range(g, g + part):
                    page = 0
                    if go:
                        col = first + jnp.minimum(j, left - 1)
                        page = pt_ref[jnp.minimum(col, mb - 1)]
                    for which, (hbm, dst) in enumerate(pools):
                        dma = pltpu.make_async_copy(
                            hbm.at[page],
                            dst.at[buf, pl.ds(j * block_size, block_size)],
                            sem.at[which, buf])
                        if go:
                            dma.start()
                        else:
                            dma.wait()

    @pl.when(t == 0)
    def _open():
        carry[0] = 0           # the buffer the next step that runs reads
        carry[1] = 0           # whether its copies are in flight

    buf0 = carry[0]

    @pl.when((steps > 0) & (carry[1] == 0))
    def _cold():
        copies(t, 0, buf0, True)

    nxt = jnp.minimum(t + 1, tiles - 1)
    more = (t + 1 < tiles) & (cnt_ref[nxt] > 0)

    m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def fold(n, u, buf, first, count, masked):
        """Head n folds the step's pages `first + [0, count)` under one
        update of its state, their scores in lane blocks of `w` keys."""
        keys = count * block_size
        w = math.gcd(keys, _LANES)
        span = (buf, pl.ds(first * block_size, keys),
                pl.ds(pl.multiple_of(n * d, d), d))
        s = _dot_tb(q_ref[n], k_buf[span])            # (group*tile, keys)
        cols = [s[:, c:c + w] for c in range(0, keys, w)]
        if masked:
            # key <= row, key >= start, key > row - window, by lane block
            # against what a row minus a lane reads
            lane = lax.broadcasted_iota(jnp.int32, (tile, w), 1)
            rel = pos0 + t * tile - lane + lax.broadcasted_iota(
                jnp.int32, (tile, w), 0)
            key0 = (lo + pages * u + first) * block_size
            for i, c in enumerate(range(0, keys, w)):
                seen = (rel >= key0 + c) & (lane >= start - key0 - c) \
                    & (rel < key0 + c + window)
                pen = jnp.where(seen, 0.0, _NEG_INF)          # (tile, w)
                cols[i] = (cols[i].reshape(group, tile, w)
                           + pen[None]).reshape(group * tile, w)
        m_prev = m_scr[n]
        top = jnp.max(functools.reduce(jnp.maximum, cols), axis=1)
        m_next = jnp.maximum(m_prev, top[:, None])
        alpha = jnp.exp(m_prev - m_next)
        m_cols = m_next[:, :w]
        ps = [jnp.exp(c - m_cols) for c in cols]
        if w == _LANES:
            l_scr[n] = alpha * l_scr[n] + functools.reduce(jnp.add, ps)
        else:
            l_scr[n] = alpha * l_scr[n]
            l_scr[n, :, :w] += functools.reduce(jnp.add, ps)
        v = v_buf[span]
        acc_scr[n] = acc_scr[n] * _widen(alpha, d) + jnp.dot(
            jnp.concatenate([p.astype(v.dtype) for p in ps], axis=1), v,
            preferred_element_type=jnp.float32)
        m_scr[n] = m_next

    def step(u, _):
        buf = (buf0 + u) % 2
        ends = u + 1 == steps

        @pl.when(jnp.logical_not(ends) | more)
        def _prefetch():
            copies(jnp.where(ends, nxt, t), jnp.where(ends, 0, u + 1),
                   1 - buf, True)

        copies(t, u, buf, False)
        left, clear = _step_kind(lo, cnt, pos0, start, window, t, u,
                                 tile=tile, block=block_size, pages=pages)
        whole = left >= pages
        edge = whole if mask_all else whole & jnp.logical_not(clear)
        # (when, first page, pages, masked): the bodies a head may run
        bodies = [] if mask_all else [(whole & clear, 0, pages, False)]
        if part == pages:
            bodies.append((edge | (left < pages), 0, pages, True))
        else:
            bodies.append((edge, 0, pages, True))
            bodies += [((left > g) & (left < pages), g, part, True)
                       for g in range(0, pages, part)]

        def head(n, _):
            for when, first, count, masked in bodies:
                pl.when(when)(functools.partial(
                    fold, n, u, buf, first, count, masked))
            return 0

        lax.fori_loop(0, kvh, head, 0)
        return 0

    lax.fori_loop(0, steps, step, 0)

    @pl.when(steps > 0)
    def _close():
        carry[0] = (buf0 + steps) % 2
        carry[1] = more.astype(jnp.int32)

    l = jnp.sum(l_scr[...], axis=-1, keepdims=True)
    l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
    o_ref[...] = (acc_scr[...] * l_inv).astype(o_ref.dtype)


def tile_walks(pos0, start, window, real, *, tiles: int, tile: int,
               block: int, columns: int, xp=jnp):
    """(lo, cnt) (tiles,) int32: the first table column a tile of `tile`
    query rows walks and how many, rows at `pos0 + [0, tiles * tile)` of
    which the first `real` are tokens. `xp=numpy` counts on the host."""
    first = pos0 + tile * xp.arange(tiles, dtype=xp.int32)
    lo = xp.maximum(xp.maximum(first - window + 1, start), 0) // block
    hi = xp.minimum((first + tile - 1) // block, columns - 1)
    live = first < pos0 + real
    return (xp.where(live, lo, 0).astype(xp.int32),
            xp.where(live, xp.maximum(hi - lo + 1, 0), 0).astype(xp.int32))


def walk_counts(pos0: int, start: int, window: int, real: int, *, s: int,
                block: int, columns: int, pages: int) -> dict:
    """What ONE KV head's grid of a `window_prefill` call does, counted on
    the host by the kernel's own rule: `steps` that run, of them `clear`
    without a mask, the `walked` pages of the tiles' walks and the
    `executed` pages the steps compute (whole steps, and the last step's
    parts)."""
    tile = min(WINDOW_TILE, s)
    tiles, part = s // tile, _tail_pages(pages, block)
    lo, cnt = tile_walks(pos0, start, window, real, tiles=tiles, tile=tile,
                         block=block, columns=columns, xp=np)
    u = np.arange(max(-(-int(cnt.max()) // pages), 1))
    left, clear = _step_kind(
        lo[:, None], cnt[:, None], pos0, start, window,
        np.arange(tiles)[:, None], u[None], tile=tile, block=block,
        pages=pages)
    whole, tail = left >= pages, (left > 0) & (left < pages)
    return {"steps": int((whole | tail).sum()),
            "clear": int((whole & clear).sum()),
            "walked": int(cnt.sum()),
            "executed": int(pages * whole.sum()
                            + (-(-left // part) * part)[tail].sum())}


@functools.partial(jax.jit, static_argnames=("block", "pages", "mask_all"))
def _prefill_call(q, k_pages, v_pages, pt_row, pos0, start, window, real, *,
                  block: int, pages: int, mask_all: bool = False):
    """The device op `window_prefill`, jitted on its own: a program whose
    layers share their shapes lowers it once, not once a layer. `pages` a
    step folds; `mask_all` adds the mask in every step (the tests'
    control of the steps that run without)."""
    s, kvh, group, d = q.shape
    tile = min(WINDOW_TILE, s)
    tiles, mb = s // tile, pt_row.shape[0]
    rows, keys = group * tile, pages * block
    lo, cnt = tile_walks(pos0, start, window, real, tiles=tiles, tile=tile,
                         block=block, columns=mb)
    # scaled here, once a chunk, not in every grid step
    qk = (q * d ** -0.5).astype(q.dtype)
    qk = jnp.moveaxis(qk.reshape(tiles, tile, kvh, group, d), (2, 3), (0, 2))
    qk = qk.reshape(kvh, tiles, rows, d)

    cell = pl.BlockSpec((kvh, None, rows, d), lambda t, *_: (0, t, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_size=block, tile=tile,
                          group=group, pages=pages,
                          part=_tail_pages(pages, block), mask_all=mask_all),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[cell, pool, pool],
            out_specs=cell,
            scratch_shapes=[
                pltpu.VMEM((2, keys, kvh * d), k_pages.dtype),
                pltpu.VMEM((2, keys, kvh * d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, rows, _LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, _LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, d), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((kvh, tiles, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # every head's q, output and state, the pages' buffers, and a
            # head's score tile with the probabilities beside it, in
            # float32 and as the second matmul's operand
            vmem_limit_bytes=int(
                4 * kvh * rows * d * q.dtype.itemsize
                + 4 * keys * kvh * d * k_pages.dtype.itemsize
                + 4 * kvh * rows * (2 * _LANES + d)
                + 4 * 4 * rows * keys + 8 * 2 ** 20)),
        interpret=not backend.on_tpu(),
        name="window_prefill",
    )(lo, cnt, pt_row.astype(jnp.int32),
      jnp.stack([pos0, start, window]).astype(jnp.int32),
      qk, k_pages, v_pages)
    out = out.reshape(kvh, tiles, group, tile, d)
    return jnp.moveaxis(out, (0, 2), (2, 3)).reshape(s, kvh, group, d)


def window_prefill(q, k_pages, v_pages, pt_row, pos0, *, start=0,
                   window: int = NO_WINDOW, real=None, impl: str = "auto"):
    """A chunk's attention for ONE sequence. q (s, kvh, g, d) at positions
    pos0 + [0, s), of which the first `real` are tokens (all, if None: the
    rest is padding whose rows come back as zeros or as garbage, finite);
    pools (blocks, block, kvh*d) with the chunk's own keys written; pt_row
    (mb,) the sequence's page table; `start` the sequence's first position,
    `window` the keys a row attends, its own among them. Returns
    (s, kvh, g, d)."""
    s, _, group, d = q.shape
    block = k_pages.shape[1]
    tile = min(WINDOW_TILE, s)
    as_i32 = lambda x: jnp.asarray(x, jnp.int32)
    pos0, start, window = as_i32(pos0), as_i32(start), as_i32(window)
    packable = d % _LANES == 0 and block % 8 == 0 and s % tile == 0 \
        and tile % 8 == 0
    if impl == "reference" or (impl == "auto" and (
            not packable or not backend.on_tpu())):
        with jax.named_scope("window_prefill"):
            return _prefill_reference(q, k_pages, v_pages, pt_row, pos0,
                                      start, window)
    if not packable:
        raise ValueError("impl='kernel' needs heads of whole lane tiles, "
                         "a page of a multiple of 8 and whole tiles")
    return _prefill_call(
        q, k_pages, v_pages, pt_row, pos0, start, window,
        as_i32(s if real is None else real), block=block,
        pages=pages_per_step(block, k_pages.shape[2], group * tile,
                             pt_row.shape[0], k_pages.dtype.itemsize))
