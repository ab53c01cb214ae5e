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

The tile is `ops/sparse_attention.py sparse_prefill`'s: a grid cell holds a
KV head's `group` query heads x `WINDOW_TILE` positions as the rows of one
matmul against `WINDOW_FOLD` whole pages fetched through the table and joined
in VMEM. A tile walks the table columns `lo_t .. hi_t` and no others: `hi_t`
holds its last row's own position, `lo_t` its FIRST row's first key, so pages
wholly behind a tile's window are neither fetched nor computed (a tile of 128
rows under a window of 4,096 walks 67 pages of 64 whatever the context), and
a tile wholly past the chunk's real tokens walks none. The list axis of the
grid is a run-time bound, the longest tile's walk.

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

import jax
import jax.numpy as jnp
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
# matmul rows at 28 heads on 4), and pages folded into one step's key tile
WINDOW_TILE = 128
WINDOW_FOLD = 4
# the window of a layer that has none: every position's first key is `start`
NO_WINDOW = 2 ** 30


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


def _prefill_kernel(lo_ref, cnt_ref, pt_ref, at_ref,          # SMEM
                    q_ref, *refs, block_size, tile, group, fold):
    """Grid (kv heads, tiles, steps): cell (n, t, u) folds the pages at table
    columns `lo[t] + fold * u + [0, fold)` into the online softmax of tile
    t's `group * tile` query rows (q comes scaled). `at_ref` = (pos0, start,
    window). A column past the tile's `cnt[t]` holds keys past every row of
    the tile, which the causal mask cuts (its index map repeats the last
    live page: no new copy); a step wholly past it is not run. The state is
    `sparse_prefill`'s: running max and denominator replicated over the
    lanes, an accumulator normalised once in `_done`."""
    k_refs, v_refs = refs[:fold], refs[fold:2 * fold]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * fold:]
    t, u = pl.program_id(1), pl.program_id(2)

    @pl.when(u == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(fold * u < cnt_ref[t])
    def _fold():
        keys = fold * block_size
        q_pos = at_ref[0] + t * tile + lax.broadcasted_iota(
            jnp.int32, (tile, keys), 0)
        k_pos = (lo_ref[t] + fold * u) * block_size + lax.broadcasted_iota(
            jnp.int32, (tile, keys), 1)
        seen = (k_pos <= q_pos) & (k_pos >= at_ref[1]) \
            & (k_pos > q_pos - at_ref[2])
        pen = jnp.where(seen, 0.0, _NEG_INF)                  # (tile, keys)
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


def tile_walks(pos0, start, window, real, *, tiles: int, tile: int,
               block: int, columns: int):
    """(lo, cnt) (tiles,) int32: the first table column a tile of `tile`
    query rows walks and how many, rows at `pos0 + [0, tiles * tile)` of
    which the first `real` are tokens."""
    first = pos0 + tile * jnp.arange(tiles, dtype=jnp.int32)
    lo = jnp.maximum(jnp.maximum(first - window + 1, start), 0) // block
    hi = jnp.minimum((first + tile - 1) // block, columns - 1)
    live = first < pos0 + real
    return (jnp.where(live, lo, 0).astype(jnp.int32),
            jnp.where(live, jnp.maximum(hi - lo + 1, 0), 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block",))
def _prefill_call(q, k_pages, v_pages, pt_row, pos0, start, window, real, *,
                  block: int):
    """The device op `window_prefill`, jitted on its own: a program whose
    layers share their shapes lowers it once, not once a layer."""
    s, kvh, group, d = q.shape
    tile, fold = min(WINDOW_TILE, s), WINDOW_FOLD
    tiles, mb = s // tile, pt_row.shape[0]
    lo, cnt = tile_walks(pos0, start, window, real, tiles=tiles, tile=tile,
                         block=block, columns=mb)
    # scaled here, once a chunk, not in every grid step
    qk = (q * d ** -0.5).astype(q.dtype)
    qk = jnp.moveaxis(qk.reshape(tiles, tile, kvh, group, d), (2, 3), (0, 2))
    qk = qk.reshape(kvh, tiles, group * tile, d)

    def page_spec(j):
        def page_map(n, t, u, lo, cnt, pt, at):
            col = lo[t] + jnp.minimum(fold * u + j,
                                      jnp.maximum(cnt[t] - 1, 0))
            return pt[jnp.minimum(col, mb - 1)], 0, n
        return pl.BlockSpec((None, block, d), page_map)

    cell = lambda n, t, u, *_: (n, t, 0, 0)
    pages = [page_spec(j) for j in range(fold)]
    steps = jnp.maximum(-(-jnp.max(cnt) // fold), 1)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_size=block, tile=tile,
                          group=group, fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(kvh, tiles, steps),
            in_specs=[pl.BlockSpec((None, None, group * tile, d), cell),
                      *pages, *pages],
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
        name="window_prefill",
    )(lo, cnt, pt_row.astype(jnp.int32),
      jnp.stack([pos0, start, window]).astype(jnp.int32),
      qk, *[k_pages] * fold, *[v_pages] * fold)
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
    s, d = q.shape[0], q.shape[-1]
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
    return _prefill_call(q, k_pages, v_pages, pt_row, pos0, start, window,
                         as_i32(s if real is None else real), block=block)
