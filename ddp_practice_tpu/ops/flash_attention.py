"""Flash attention as Pallas TPU kernels — forward AND tiled backward.

The reference consumes fused CUDA kernels through torch (cuDNN/cuBLAS —
SURVEY §2.2 "CUDA/cuDNN kernels"); the TPU-native analogue for the one op
XLA doesn't already fuse optimally at long sequence length is a hand-tiled
attention kernel. The kernels are STREAMING: a 3D grid
(batch*heads, outer-block, inner-block) whose innermost dimension sweeps
the contracted sequence axis while per-block state lives in VMEM scratch
— so only one q tile and one k/v tile are VMEM-resident at any moment and
sequence length is bounded by HBM, not VMEM. Forward, per (q-block,
k-block) grid step:

    @when(kj == 0):   m, l, acc := -inf, 0, 0  # scratch init
    s   = (q*scale) @ k^T                      # MXU, fp32 accumulate
    m'  = max(m, rowmax(s))                    # online softmax rescale
    acc = acc*(l*corr/l') + exp(s-m') @ v / l' # MXU; acc stays normalized
    @when(kj == last): out = acc, lse = m + log l

so the (seq x seq) score matrix never materializes in HBM — O(seq) memory,
one pass over K/V. Causal masking is taken twice: whole k-blocks above the
diagonal are skipped at the grid (@when(visible) gates the FLOPs), and
inside a visible cell only the 256-wide score sub-tiles that hold an
unmasked element are computed (_live_subtiles, _cell_strips): at seq 2048
with blocks (512, 1024) a head runs 9.0 squares of 512x512 where the whole
cells ran 12 and 8.0 hold work (`causal_tile_counts`).

Performance structure (the round-4 restructure; measured on TPU v5e —
see BENCHMARKS.md kernel table):
  * softmax state (m, l) is kept LANE-REPLICATED at (block_q, 128) and
    widened to block_k by lane-tiling — never a width-1 cross-lane
    broadcast over the (block_q, block_k) tile, which dominated VPU time
    in the round-3 kernel;
  * the accumulator is renormalized every step, so the epilogue is a bare
    cast (no wide divide), and all broadcasts against acc slice the
    replicated 128-lane state down to head_dim;
  * all contractions are `lax.dot_general` with explicit dimension
    numbers — k^T / p^T / ds^T are never materialized;
  * sm_scale is folded into the q tile at load ((block_q, d) mul — for
    d=64 the scale 1/8 is exact in bf16) so no (block_q, block_k) scale
    pass runs;
  * p / ds are cast to bf16 before their MXU consumers (FlashAttention-2
    staging); softmax statistics stay fp32;
  * a cell's work is ONE straight-line body of static shapes (a branch
    for each place the diagonal can cross a cell), never a loop over
    sub-tiles: Mosaic overlaps MXU and VPU work inside a basic block only.
With head_dim 64 the MXU contraction/output width caps useful utilization
at 50% of peak. Where the kernels stand at lm_base shapes (b 8, h 12,
s 2048, causal; PERF.md section 5, PR 27): forward 1.03 ms, dq 1.22,
dk/dv 1.50 a layer = 25% / 32% / 35% of bf16 peak on the dots that hold
work, 28% / 35% / 39% on the sub-tiles executed. The backward is within a
quarter of the cap; the forward is held by the VPU's softmax chain
(rowmax, exp, rowsum, rescale), not by the MXU.

Backward is tiled the same way (FlashAttention-2 scheme), recomputing
p = exp(s - lse) blockwise from the saved logsumexp:

    delta = rowsum(do * o)                    # XLA, cheap
    dKdV kernel (grid bh x k-blocks x q-blocks, q innermost):
        p = exp(qs@k^T - lse);  dv += p^T @ do          # scratch accum
        ds = p * (do @ v^T - delta); dk += ds^T @ qs
    dQ kernel (grid bh x q-blocks x k-blocks, k innermost):
        dq += (ds @ k) * scale                          # scratch accum

Both kernels recompute p and ds of every tile: seven dots and two exps a
score. On the packed layout (below) the dKdV kernel takes dq along where a
float32 accumulator for the WHOLE query sequence of a (batch row, head
pack) fits VMEM (_ONE_KERNEL_BWD_VMEM: up to seq 6144 at two heads of 64
in bf16), and the backward is ONE kernel of five dots and one exp; longer
sequences, and the folded layout, run the two. Either way training memory
is O(seq) end to end. `flash_attention_with_lse`
additionally exposes lse as a differentiable output — the lse cotangent
folds into delta (d lse/d s = p, so ds gains p*g_lse, i.e. delta -= g_lse)
— which is what lets ring attention use this kernel as its per-block local
attention and merge normalized partials across ring steps
(parallel/ring.py).

Runs compiled on TPU; `interpret=True` under the CPU backend so the same
tests cover it everywhere (tests/conftest.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.rope import (
    flat_rope_tables,
    rope_flat_bwd,
    rope_flat_qk,
)
from ddp_practice_tpu.utils import backend

_NEG_INF = -1e30
_LANES = 128

# dot_general dimension numbers: contract the LAST dim of both operands
# (x @ y^T without materializing the transpose) and the FIRST dim of both
# (x^T @ y likewise).
_TRANS_B = (((1,), (1,)), ((), ()))
_TRANS_A = (((0,), (0,)), ((), ()))


def _dot_tb(x, y):
    return lax.dot_general(x, y, _TRANS_B, preferred_element_type=jnp.float32)


def _dot_ta(x, y):
    return lax.dot_general(x, y, _TRANS_A, preferred_element_type=jnp.float32)


def _widen(x128, w):
    """Widen lane-replicated (rows, 128) state to (rows, w) without a
    width-1 cross-lane broadcast: slice when w <= 128, lane-tile when w is
    a multiple of 128, fall back to a plain broadcast otherwise (rare,
    non-tiled shapes)."""
    if w <= _LANES:
        return x128[:, :w]
    if w % _LANES == 0:
        return jnp.tile(x128, (1, w // _LANES))
    return jnp.broadcast_to(x128[:, :1], (x128.shape[0], w))


def _softmax_accumulate(s, v_tile, m_prev, l_prev, acc_prev, *,
                        vs_row=None):
    """One online-softmax accumulation step, shared by every forward
    kernel (folded, packed, decode): fold the fp32 score tile `s`
    (rows, block_k) and its value tile into lane-replicated (rows, 128)
    running max/denominator state and a NORMALIZED accumulator
    (rows, d). Returns (m_next, l_next, acc_next).

    `vs_row` (rows, block_k) handles an INT8 value tile with
    per-position dequant scales: p @ diag(vs) @ V == (p * vs_row) @ V,
    so the scale folds into the probability row BEFORE the dot and the
    MXU still consumes the raw tile. The softmax DENOMINATOR stays
    unscaled — vs dequantizes values, it is not probability mass."""
    block_k = s.shape[-1]
    d = acc_prev.shape[-1]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    p = jnp.exp(s - _widen(m_next, block_k))
    alpha = jnp.exp(m_prev - m_next)
    l_corr = alpha * l_prev
    l_next = l_corr + jnp.sum(p, axis=1)[:, None]
    l_inv = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
    pv = lax.dot_general(
        (p if vs_row is None else p * vs_row).astype(v_tile.dtype),
        v_tile, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_next = acc_prev * _widen(l_corr * l_inv, d) + pv * _widen(l_inv, d)
    return m_next, l_next, acc_next


def _tile_penalty(shift, rows, cols):
    """Additive mask for a (rows, cols) score tile: 0 where query a may
    attend key b (b <= a + shift; shift = first query's position + offset
    - first key's position, offset = seq_k - seq_q), -inf-like otherwise.
    Added to s (cheaper than select on Mosaic)."""
    rel = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
           - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    return jnp.where(rel + shift >= 0, 0.0, _NEG_INF)


def _mask_tail(s, pen):
    """The score tile with the causal penalty `pen` on its last columns
    (none, some, or all of them: only the sub-tiles the diagonal crosses
    pay for the mask)."""
    if pen is None:
        return s
    w = s.shape[1] - pen.shape[1]
    if w == 0:
        return s + pen
    return jnp.concatenate([s[:, :w], s[:, w:] + pen], axis=1)


def _cell_strips(body, qi, kj, *, block_q, block_k, causal, seq_q, seq_k):
    """Run `body` over what a grid cell has to compute (nothing, where a
    causal cell lies above the diagonal: no branch below fits it).

    body(strips) takes a list of (rows, n_keys, pen): the static slice
    `rows` of the q block against the K/V block's first n_keys keys, with
    `pen` (None, or a (rows, n_masked) penalty) for the last of them.
    Non-causal: one strip, the cell whole. Causal: the strips of sub_q
    query rows, each against the key sub-tiles that _live_subtiles calls
    live. Which those are depends on the program ids, but a shape allows
    only a few answers (_causal_plan lists them; at seq 2048 with blocks
    (512, 1024): the diagonal in the block's first half, in its second,
    or below the block), and each is compiled as its own branch: ONE
    straight-line body of static shapes for the whole cell. Mosaic
    overlaps MXU and VPU work inside a basic block, not across the steps
    of a loop: looping over single sub-tiles ran the kernels 2-4x slower
    than the whole cell, branching a strip at a time left them where
    they were (PERF.md section 6, PR 27)."""
    if not causal:
        return body([(slice(None), block_k, None)])
    (sub_q, sub_k), cases, _ = _causal_plan(seq_q, seq_k, block_q, block_k)
    shift = qi * block_q + (seq_k - seq_q) - kj * block_k
    starts = range(0, block_q, sub_q)
    live = [_live_subtiles(shift + r, sub_q, sub_k, block_k) for r in starts]
    for case in cases:
        hit = [(n_full == a) & (n_live == b)
               for (n_full, n_live), (a, b) in zip(live, case)]

        @pl.when(functools.reduce(jnp.logical_and, hit))
        def _case(case=case):
            strips = []
            for r, (a, b) in zip(starts, case):
                rows, n_keys = slice(r, r + sub_q), b * sub_k
                if a < b:  # the diagonal crosses key sub-tiles [a, b)
                    strips.append((rows, n_keys, _tile_penalty(
                        shift + r - a * sub_k, sub_q, (b - a) * sub_k)))
                elif b and strips and strips[-1][1:] == (n_keys, None):
                    # bare strips of one width run as one (a cell below
                    # the diagonal: whole, as before)
                    strips[-1] = (slice(strips[-1][0].start, rows.stop),
                                  n_keys, None)
                elif b:
                    strips.append((rows, n_keys, None))
            body(strips)


def _fwd_cell(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, heads, sm_scale,
              strips):
    """The forward over one grid cell, as _cell_strips' body: a strip's
    live scores are ONE tile, folded into the strip's running state by one
    _softmax_accumulate (one rescale a strip and cell, as the whole cell
    paid before). `heads`: (lanes, index) of each head in the refs."""
    for rows, n_keys, pen in strips:
        for lanes, hh in heads:
            q = (q_ref[rows, lanes] * sm_scale).astype(q_ref.dtype)
            s = _mask_tail(_dot_tb(q, k_ref[:n_keys, lanes]), pen)
            (m_ref[hh, rows], l_ref[hh, rows],
             acc_ref[rows, lanes]) = _softmax_accumulate(
                s, v_ref[:n_keys, lanes], m_ref[hh, rows], l_ref[hh, rows],
                acc_ref[rows, lanes])


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, block_q, block_k, causal, seq_q, seq_k,
):
    """Streaming grid cell (bh, q-block, k-block): k innermost, so only one
    (block_q, d) + one (block_k, d) tile live in VMEM at a time — sequence
    length is unbounded by VMEM. Online-softmax state (m, l) persists
    lane-replicated at (block_q, 128) in scratch across the k sweep; acc
    is kept normalized every step so the final write is a cast."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    _cell_strips(
        functools.partial(_fwd_cell, q_ref, k_ref, v_ref, m_scr, l_scr,
                          acc_scr, [(slice(None), 0)], sm_scale),
        qi, kj, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=seq_q, seq_k=seq_k)

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)
        l_col = l_scr[0][:, :1]
        l_safe = jnp.maximum(l_col, 1e-30)
        lse_ref[:] = m_scr[0][:, :1] + jnp.log(l_safe)


def _fit_block(seq, block):
    """Largest block <= the requested size that divides seq (blocks are
    upper bounds, not contracts: seq 1536 with default block_k 1024 fits
    down to 512 instead of erroring; seq <= block clamps to seq)."""
    block = min(block, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


def _check_blocks(seq_q, seq_k, block_q, block_k, causal):
    block_q = _fit_block(seq_q, block_q)
    block_k = _fit_block(seq_k, block_k)
    if causal and seq_q > seq_k:
        raise ValueError(
            f"causal flash attention needs seq_q <= seq_k (bottom-right "
            f"alignment); got seq_q={seq_q}, seq_k={seq_k} — early query "
            f"rows would attend to nothing"
        )
    return block_q, block_k


def _block_visible(block_q, block_k, offset):
    """Predicate: does causal q-block i see any of k-block j?"""
    return lambda i, j: (i * block_q + block_q - 1 + offset) >= (j * block_k)


# Edge of the score sub-tiles a causal grid cell is worked in: inside a
# visible (block_q, block_k) cell only the sub-tiles holding an unmasked
# element are computed (_live_subtiles). Chosen on the chip, not an
# argument: at the LM cells' shape 256 ran the three kernels in 3.75 ms a
# layer, 128 in 4.25, 512 in 3.94, the whole cell in 4.72 (PERF.md §6,
# PR 27).
_SUB = 256


def _sub_tiles(block_q, block_k):
    """(sub_q, sub_k): the sub-tile of a causal grid cell. A block that
    _SUB does not divide into lane-aligned pieces (192, 320: no multiple
    of 128) stays whole."""

    def sub(block):
        fit = _fit_block(block, _SUB)
        return block if fit % _LANES else fit

    return sub(block_q), sub(block_k)


def _live_subtiles(shift, sub_q, sub_k, block_k, xp=jnp):
    """THE causal schedule inside a grid cell: of the block_k // sub_k key
    sub-tiles of a K/V block, which does a strip of sub_q query rows
    compute, and on which does it add the mask's penalty? `shift` = (the
    strip's first query row) + offset - (the block's first key), offset =
    seq_k - seq_q: query a of the strip sees key b of the block iff
    b <= a + shift. Returns (n_full, n_live): sub-tiles [0, n_full) hold
    no masked element, [n_full, n_live) are crossed by the diagonal,
    [n_live, ...) are all mask and are not computed. Every causal kernel,
    folded and packed, reads this one function (through _cell_strips),
    on traced scalars; `causal_tile_counts` and the tests read it on
    numpy arrays."""
    n_full = xp.clip(shift + 1, 0, block_k) // sub_k
    n_live = (xp.clip(shift + sub_q, 0, block_k) + sub_k - 1) // sub_k
    return n_full, n_live


def _causal_plan(seq_q, seq_k, block_q, block_k):
    """_live_subtiles over a whole causal head, in numpy: the sub-tile
    shape; the answers the shape can produce, as the sorted set of a
    visible cell's ((n_full, n_live) of each of its strips) — _cell_strips
    compiles one branch an answer; and the number of sub-tiles computed."""
    sub_q, sub_k = _sub_tiles(block_q, block_k)
    at = lambda n, step: np.arange(0, n, step)
    shift = (at(seq_q, sub_q)[:, None] + (seq_k - seq_q)
             - at(seq_k, block_k)[None, :])
    n_full, n_live = _live_subtiles(shift, sub_q, sub_k, block_k, xp=np)
    # (q block, strip of the block, k block, which count)
    cells = np.stack([n_full, n_live], axis=-1).reshape(
        seq_q // block_q, block_q // sub_q, seq_k // block_k, 2)
    cases = sorted({
        tuple((int(a), int(b)) for a, b in cells[i, :, j])
        for i in range(cells.shape[0]) for j in range(cells.shape[2])
        if cells[i, :, j, 1].any()
    })
    return (sub_q, sub_k), cases, int(n_live.sum())


def causal_tile_counts(seq_q, seq_k, block_q=512, block_k=1024):
    """(executed, useful) score sub-tiles of one causal head: how many
    (sub_q, sub_k) sub-tiles the kernels compute under _live_subtiles,
    and how many the unmasked scores alone would fill. The schedule is
    static, so this is a number of the shape, not of a run (PERF.md §3)."""
    block_q, block_k = _check_blocks(seq_q, seq_k, block_q, block_k, True)
    (sub_q, sub_k), _, executed = _causal_plan(
        seq_q, seq_k, block_q, block_k)
    unmasked = int(np.clip(np.arange(seq_q) + seq_k - seq_q + 1, 0,
                           seq_k).sum())
    return executed, unmasked / (sub_q * sub_k)


def _redirect(causal, vis, i, j, idx):
    """Prefetch-redirect for swept block indices: a block belonging to a
    cell the kernel will skip (fully above the diagonal) redirects its
    DMA to block 0 instead of fetching data that `@pl.when(visible)`
    discards (the bundled jax TPU kernel's prefetch trick). All six
    sweep index maps below (folded + packed, kv- and q-swept) are built
    from this one predicate+select so the visibility condition lives in
    exactly one place."""
    return lax.select(vis(i, j), idx, 0) if causal else idx


def _kv_index_map(causal, block_q, block_k, offset):
    """kv-block index map for k-innermost folded sweeps."""
    vis = _block_visible(block_q, block_k, offset)
    return lambda b, i, j: (b, _redirect(causal, vis, i, j, j), 0)


# The pallas_call wrappers below are jitted (their keywords static): a
# model calls them once a layer with the same shapes, and as ONE jitted
# function the kernels are traced and lowered once a program instead of
# once a layer. The causal kernels hold a body for each place the diagonal
# can cross a cell; lowered twelve times over they added 26 s to the LM
# training cells' `setup_s` (PERF.md section 6, PR 27).
_FOLDED_STATICS = ("causal", "block_q", "block_k", "interpret")
_PACKED_STATICS = _FOLDED_STATICS + ("n_heads", "fused_qkv")


@functools.partial(jax.jit, static_argnames=_FOLDED_STATICS)
def _flash_fwd(q, k, v, *, causal, block_q, block_k, interpret):
    """q/k/v: (bh, seq, d). Returns (out, lse)."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q, block_k = _check_blocks(seq_q, seq_k, block_q, block_k, causal)
    sm_scale = 1.0 / (d ** 0.5)
    grid = (bh, seq_q // block_q, seq_k // block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k,
    )
    kv_map = _kv_index_map(causal, block_q, block_k,
                           seq_k - seq_q if causal else 0)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q, _LANES), jnp.float32),
            pltpu.VMEM((1, block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out, lse[..., 0]


def _stat(x, width):
    """A per-row statistic (lse, delta) held as a (rows, 1) column:
    broadcast once to the 128-lane replicated form and lane-widened from
    there (never a width-1 broadcast at score-tile width)."""
    return _widen(jnp.broadcast_to(x, (x.shape[0], _LANES)), width)


def _bwd_tiles(q_ref, do_ref, lse_ref, delta, k_ref, v_ref, heads, sm_scale,
               strips):
    """What both backward kernels compute of a grid cell, as a generator
    over (strip, head): (rows, keys, lanes), the scaled q tile, do, and
    the probability and ds tiles over the strip's live keys, recomputed
    from the saved logsumexp: p = exp(qs@k^T - lse), ds = p * (do@v^T -
    delta). `delta(rows, lanes, hh)` gives the head's (rows, 1) column."""
    for rows, n_keys, pen in strips:
        keys = slice(0, n_keys)
        for lanes, hh in heads:
            qs = (q_ref[rows, lanes] * sm_scale).astype(q_ref.dtype)
            do = do_ref[rows, lanes]
            k = k_ref[keys, lanes]
            s = _mask_tail(_dot_tb(qs, k), pen)
            p = jnp.exp(s - _stat(lse_ref[rows, hh:hh + 1], n_keys))
            dp = _dot_tb(do, v_ref[keys, lanes])            # do @ v^T
            ds = p * (dp - _stat(delta(rows, lanes, hh), n_keys))
            yield (rows, keys, lanes), qs, do, k, p, ds.astype(qs.dtype)


def _bwd_cell(dq_scr, dk_scr, dv_scr, *args):
    """The backward of one grid cell, as _cell_strips' body: a (strip,
    head)'s p and ds, recomputed ONCE by _bwd_tiles, feed every
    accumulator the kernel holds (None: another kernel's). dk_scr / dv_scr
    cover the cell's K/V block, dq_scr its q block."""
    for (rows, keys, lanes), qs, do, k, p, ds in _bwd_tiles(*args):
        if dv_scr is not None:
            dv_scr[keys, lanes] = dv_scr[keys, lanes] + _dot_ta(
                p.astype(do.dtype), do)                     # p^T @ do
            dk_scr[keys, lanes] = dk_scr[keys, lanes] + _dot_ta(ds, qs)  # ds^T @ qs
        if dq_scr is not None:
            dq_scr[rows, lanes] = dq_scr[rows, lanes] + lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),            # ds @ k
                preferred_element_type=jnp.float32,
            )


def _dkdv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, sm_scale, block_q, block_k, causal, seq_q, seq_k,
):
    """Streaming grid cell (bh, k-block, q-block): q innermost; dk/dv
    accumulate in scratch across the q sweep (FlashAttention-2), writing
    the output block on the last q step. Only one q tile + one k/v tile
    are VMEM-resident — seq is unbounded by VMEM."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    _cell_strips(
        functools.partial(_bwd_cell, None, dk_scr, dv_scr, q_ref, do_ref,
                          lse_ref, lambda rows, lanes, hh: delta_ref[rows],
                          k_ref, v_ref, [(slice(None), 0)], sm_scale),
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=seq_q, seq_k=seq_k)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref, dq_scr,
    *, sm_scale, block_q, block_k, causal, seq_q, seq_k,
):
    """Streaming grid cell (bh, q-block, k-block): k innermost; dq
    accumulates in scratch across the k sweep."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    _cell_strips(
        functools.partial(_bwd_cell, dq_scr, None, None, q_ref, do_ref,
                          lse_ref, lambda rows, lanes, hh: delta_ref[rows],
                          k_ref, v_ref, [(slice(None), 0)], sm_scale),
        qi, kj, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=seq_q, seq_k=seq_k)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[:] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=_FOLDED_STATICS)
def _flash_bwd(q, k, v, do, lse, delta, *, causal, block_q, block_k,
               interpret):
    """Tiled dq/dk/dv. delta = rowsum(do*o) - g_lse, fp32 (bh, seq_q)."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q, block_k = _check_blocks(seq_q, seq_k, block_q, block_k, causal)
    sm_scale = 1.0 / (d ** 0.5)
    lse3 = lse[..., None].astype(jnp.float32)
    delta3 = delta[..., None].astype(jnp.float32)

    offset = seq_k - seq_q if causal else 0
    vis = _block_visible(block_q, block_k, offset)

    def qo_map(b, j, i):
        return (b, _redirect(causal, vis, i, j, i), 0)

    dkdv = functools.partial(
        _dkdv_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k,
    )
    dk, dv = pl.pallas_call(
        dkdv,
        name="flash_bwd_dkv",
        grid=(bh, seq_k // block_k, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), qo_map),
            pl.BlockSpec((None, block_q, d), qo_map),
            pl.BlockSpec((None, block_q, 1), qo_map),
            pl.BlockSpec((None, block_q, 1), qo_map),
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, do, lse3, delta3, k, v)

    dqk = functools.partial(
        _dq_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, seq_q=seq_q, seq_k=seq_k,
    )
    kv_map = _kv_index_map(causal, block_q, block_k, offset)
    dq = pl.pallas_call(
        dqk,
        name="flash_bwd_dq",
        grid=(bh, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, do, lse3, delta3, k, v)
    return dq, dk, dv


def _interpret() -> bool:
    return not backend.on_tpu()


# --------------------------------------------------------------------- #
# Packed-layout kernels: attention directly on the flat (b, s, h*d)
# activations the QKV projection produces.
#
# The folded path above transposes (b, s, h, d) -> (b*h, s, d) around
# every kernel call; at lm_base shapes those transposes are ~5% of the
# whole train step ("data formatting" in the xprof composition —
# BENCHMARKS.md). Mosaic cannot squeeze a size-h dim out of a 4D block,
# but it CAN take a 128-wide column block out of the flat h*d dim — so
# for d <= 128 we pack 128//d heads per grid cell: the q/k/v tiles are
# (block, 128) contiguous slices of the UNTRANSPOSED activations, and the
# kernel walks the packed heads with 64-aligned column slices (python-
# unrolled). Head count h must divide into whole packs; anything else
# falls back to the folded path. Zero layout ops at the model boundary.
# --------------------------------------------------------------------- #


def _heads_per_pack(h: int, d: int):
    """Packing arity for head_dim d: how many heads share one 128-lane
    tile. None = shapes don't pack (fall back to the folded path).
    d < 64 is excluded even when it divides 128: the in-kernel head walk
    slices columns at h*d offsets, and Mosaic only supports 64-aligned
    column slices (tpu-env-gotchas)."""
    if d >= _LANES:
        return 1 if d % _LANES == 0 else None
    if d < 64 or _LANES % d:
        return None
    hpc = _LANES // d
    return hpc if h % hpc == 0 else None


def _packed_heads(hpc, d):
    """(lanes, index) of the hpc heads of a 128-wide column pack."""
    return [(slice(hh * d, (hh + 1) * d), hh) for hh in range(hpc)]


def _packed_dims(qf, kf, vf, n_heads, fused_qkv):
    """(hd, d, hpc, n_packs, koff, voff) of a packed call. An operand
    3*h*d wide IS the raw QKV-projection output, columns [q heads | k
    heads | v heads], and is windowed at its own column blocks (k at
    n_packs, v at 2*n_packs): all three under `fused_qkv`; v alone where
    q and k were rotated out of it (the flat rope path), whose q and k
    operands are (b, s, h*d) arrays of their own."""
    hd = qf.shape[-1] // 3 if fused_qkv else qf.shape[-1]
    d = hd // n_heads
    hpc = _heads_per_pack(n_heads, d)
    n_packs = n_heads // hpc
    for name, x in (("k", kf), ("v", vf)):
        if x.shape[-1] not in (hd, 3 * hd):
            raise ValueError(
                f"{name} is {x.shape[-1]} wide: neither h*d = {hd} nor the "
                f"projection's {3 * hd}")
    koff = n_packs if kf.shape[-1] == 3 * hd else 0
    voff = 2 * n_packs if vf.shape[-1] == 3 * hd else 0
    return hd, d, hpc, n_packs, koff, voff


def _fwd_kernel_packed(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, block_q, block_k, causal, seq_q, seq_k, hpc, d,
):
    """Packed grid cell (b, head-pack, q-block, k-block): identical math
    to _fwd_kernel, repeated over the hpc heads living in this 128-wide
    column pack. Per-head state is (hpc, block_q, 128) scratch; the
    accumulator shares one (block_q, hpc*d) buffer whose column blocks
    belong to the packed heads."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    _cell_strips(
        functools.partial(_fwd_cell, q_ref, k_ref, v_ref, m_scr, l_scr,
                          acc_scr, _packed_heads(hpc, d), sm_scale),
        qi, kj, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=seq_q, seq_k=seq_k)

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)
        for hh in range(hpc):
            l_safe = jnp.maximum(l_scr[hh][:, :1], 1e-30)
            lse_ref[:, hh:hh + 1] = m_scr[hh][:, :1] + jnp.log(l_safe)


@functools.partial(jax.jit, static_argnames=_PACKED_STATICS)
def _flash_fwd_packed(qf, kf, vf, *, n_heads, causal, block_q, block_k,
                      interpret, fused_qkv=False):
    """qf/kf/vf: flat (b, s, h*d). Returns (out_flat, lse_packed) where
    lse_packed is (b, n_packs, seq_q, hpc) fp32.

    fused_qkv=True: qf/kf/vf are all the SAME (b, s, 3*h*d) array — the
    raw QKV-projection output, columns [q heads | k heads | v heads].
    The three in_specs window it at column-block offsets (0, n_packs,
    2*n_packs), so no slice/relayout ever materializes q, k, v (the
    sliced path cost ~4 ms/step of pure data formatting at lm_base
    shapes — round-4 profile). Without it kf or vf may still be that
    array and is windowed at its own offset (_packed_dims): the flat
    rope path hands in rotated q and k and reads v where the projection
    wrote it."""
    b, seq_q, _ = qf.shape
    seq_k = kf.shape[1]
    hd, d, hpc, n_packs, koff, voff = _packed_dims(
        qf, kf, vf, n_heads, fused_qkv)
    w = hpc * d
    block_q, block_k = _check_blocks(seq_q, seq_k, block_q, block_k, causal)
    sm_scale = 1.0 / (d ** 0.5)
    offset = seq_k - seq_q if causal else 0
    vis = _block_visible(block_q, block_k, offset)

    def k_map(b_, g, i, j):
        return (b_, _redirect(causal, vis, i, j, j), g + koff)

    def v_map(b_, g, i, j):
        return (b_, _redirect(causal, vis, i, j, j), g + voff)

    kernel = functools.partial(
        _fwd_kernel_packed, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, causal=causal, seq_q=seq_q, seq_k=seq_k,
        hpc=hpc, d=d,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_packed",
        grid=(b, n_packs, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, w), lambda b_, g, i, j: (b_, i, g)),
            pl.BlockSpec((None, block_k, w), k_map),
            pl.BlockSpec((None, block_k, w), v_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, w), lambda b_, g, i, j: (b_, i, g)),
            pl.BlockSpec((None, None, block_q, hpc),
                         lambda b_, g, i, j: (b_, g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, seq_q, hd), qf.dtype),
            jax.ShapeDtypeStruct((b, n_packs, seq_q, hpc), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hpc, block_q, _LANES), jnp.float32),
            pltpu.VMEM((hpc, block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out, lse


def _bwd_kernel_packed(
    q_ref, do_ref, out_ref, lse_ref, k_ref, v_ref, *refs,
    sm_scale, block_q, block_k, causal, seq_q, seq_k, hpc, d,
):
    """Packed grid cell (b, head-pack, k-block, q-block), q innermost:
    dk/dv accumulate in (block_k, w) scratch across the q sweep and leave
    at its last step. refs = (dk_ref, dv_ref, dk_scr, dv_scr) is the dk/dv
    half of the two-kernel backward. refs = (dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr) is the WHOLE backward in one kernel: the same
    p and ds feed dq too, which no single grid step owns (every k-block
    adds to every q block), so its float32 accumulator holds the whole
    sequence of the (b, pack), (q-blocks, block_q, w), zeroed at the
    pack's first cell and written at its last into an output block that
    stays put over both inner grid axes. Five dots a (strip, head) where
    the two kernels run 4 + 3, one exp of every score where they run two
    (PERF.md section 6, PR 31)."""
    if len(refs) == 6:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs
    else:
        (dk_ref, dv_ref, dk_scr, dv_scr), dq_ref, dq_scr = refs, None, None
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    first_q, last_q = qi == 0, qi == pl.num_programs(3) - 1

    @pl.when(first_q)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    if dq_ref is not None:
        @pl.when(first_q & (ki == 0))
        def _init_dq():
            dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    def delta(rows, lanes, hh):
        # delta = rowsum(do * o) for this head, recomputed in-register
        # (a VPU mult+rowsum, noise next to the dots) — a separate
        # XLA/Pallas delta pass costs more in relayouts/grid overhead
        # than it saves (measured round 4)
        return jnp.sum(
            do_ref[rows, lanes].astype(jnp.float32)
            * out_ref[rows, lanes].astype(jnp.float32),
            axis=-1, keepdims=True,
        )

    _cell_strips(
        functools.partial(_bwd_cell,
                          None if dq_scr is None else dq_scr.at[qi],
                          dk_scr, dv_scr, q_ref, do_ref, lse_ref, delta,
                          k_ref, v_ref, _packed_heads(hpc, d), sm_scale),
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=seq_q, seq_k=seq_k)

    @pl.when(last_q)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(last_q & (ki == pl.num_programs(2) - 1))
        def _finalize_dq():
            for i in range(seq_q // block_q):
                dq_ref[i * block_q:(i + 1) * block_q] = (
                    dq_scr[i] * sm_scale).astype(dq_ref.dtype)


def _dq_kernel_packed(
    q_ref, do_ref, out_ref, lse_ref, k_ref, v_ref, dq_ref, dq_scr,
    delta_scr,
    *, sm_scale, block_q, block_k, causal, seq_q, seq_k, hpc, d,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)
        # per-head delta = rowsum(do * o), computed once per q block (the
        # do/out blocks are constant across the kj sweep, so their DMAs
        # amortize) instead of in a separate pass whose narrow output
        # needed a strided relayout per layer
        prod = do_ref[:].astype(jnp.float32) * out_ref[:].astype(
            jnp.float32)
        for hh in range(hpc):
            delta_scr[:, hh:hh + 1] = jnp.sum(
                prod[:, hh * d:(hh + 1) * d], axis=-1, keepdims=True
            )

    _cell_strips(
        functools.partial(
            _bwd_cell, dq_scr, None, None, q_ref, do_ref, lse_ref,
            lambda rows, lanes, hh: delta_scr[rows, hh:hh + 1],
            k_ref, v_ref, _packed_heads(hpc, d), sm_scale),
        qi, kj, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=seq_q, seq_k=seq_k)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[:] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


# VMEM the packed backward's blocks (two deep) and scratch may take when
# dq, dk and dv come from ONE kernel, whose dq accumulator and dq output
# block hold the whole query sequence of a (batch row, head pack). Mosaic
# adds a strip's score tiles to that (3.5 MiB at bf16, 4.4 at float32),
# and the sum has to fit the 16 MiB a kernel is scoped. Where it does, one
# kernel runs; past it the dk/dv kernel and the dq kernel with block-sized
# state, as before PR 31. The shape decides, nothing else: at two heads of
# 64 in bf16 under blocks (512, 1024) the line falls after seq_q 6144
# (10.25 MiB held, 13.7 in all; the LM cells' 2048 hold 6.25 and are given
# 9.7: tests/test_tpu_compile.py compiles both, and float32 at 2048, 14.4).
_ONE_KERNEL_BWD_VMEM = 10.5 * 2**20


def _one_kernel_bwd_vmem(seq_q, block_q, block_k, w, dtype):
    """Bytes of _bwd_kernel_packed's blocks and scratch with dq aboard."""
    two_deep = 2 * jnp.dtype(dtype).itemsize * w * (
        3 * block_q          # q, do, out in
        + 4 * block_k        # k, v in; dk, dv out
        + seq_q)             # dq out
    lse = 2 * 4 * block_q * _LANES
    return two_deep + lse + 4 * w * (2 * block_k + seq_q)    # float32 scratch


def _packed_bwd_calls(qf, kf, vf, do, out, lse_pk, *, one_kernel, n_heads,
                      causal, block_q, block_k, interpret, fused_qkv):
    """The packed backward as ONE pallas_call (`flash_bwd_packed`) or as
    two (`flash_bwd_dkv_packed`, `flash_bwd_dq_packed`): the same tiles in
    the same order either way. _flash_bwd_packed chooses; the tests hold
    the two against each other (one_kernel None: from the shape)."""
    b, seq_q, _ = qf.shape
    seq_k = kf.shape[1]
    hd, d, hpc, n_packs, koff, voff = _packed_dims(
        qf, kf, vf, n_heads, fused_qkv)
    w = hpc * d
    block_q, block_k = _check_blocks(seq_q, seq_k, block_q, block_k, causal)
    n_q, n_k = seq_q // block_q, seq_k // block_k
    if one_kernel is None:
        one_kernel = _one_kernel_bwd_vmem(
            seq_q, block_q, block_k, w, qf.dtype) <= _ONE_KERNEL_BWD_VMEM
    offset = seq_k - seq_q if causal else 0
    vis = _block_visible(block_q, block_k, offset)
    statics = dict(sm_scale=1.0 / (d ** 0.5), block_q=block_q,
                   block_k=block_k, causal=causal, seq_q=seq_q, seq_k=seq_k,
                   hpc=hpc, d=d)
    dq_shape = jax.ShapeDtypeStruct((b, seq_q, hd), qf.dtype)

    def qo_map(b_, g, j, i):
        return (b_, _redirect(causal, vis, i, j, i), g)

    def stat_map_dkdv(b_, g, j, i):
        return (b_, g, _redirect(causal, vis, i, j, i), 0)

    kv_out = pl.BlockSpec((None, block_k, w), lambda b_, g, j, i: (b_, j, g))
    # the whole dq of a (b, pack): the block index ignores both inner axes,
    # so it is written back once, after the pack's last cell
    dq_out = [pl.BlockSpec((None, seq_q, w), lambda b_, g, j, i: (b_, 0, g))]
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel_packed, **statics),
        name="flash_bwd_packed" if one_kernel else "flash_bwd_dkv_packed",
        grid=(b, n_packs, n_k, n_q),
        in_specs=[
            pl.BlockSpec((None, block_q, w), qo_map),
            pl.BlockSpec((None, block_q, w), qo_map),
            pl.BlockSpec((None, block_q, w), qo_map),
            pl.BlockSpec((None, None, block_q, hpc), stat_map_dkdv),
            pl.BlockSpec((None, block_k, w),
                         lambda b_, g, j, i: (b_, j, g + koff)),
            pl.BlockSpec((None, block_k, w),
                         lambda b_, g, j, i: (b_, j, g + voff)),
        ],
        out_specs=dq_out * one_kernel + [kv_out, kv_out],
        out_shape=[dq_shape] * one_kernel + [
            jax.ShapeDtypeStruct((b, seq_k, hd), kf.dtype),
            jax.ShapeDtypeStruct((b, seq_k, hd), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_q, block_q, w), jnp.float32)] * one_kernel + [
            pltpu.VMEM((block_k, w), jnp.float32),
            pltpu.VMEM((block_k, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # dq is carried over the k-blocks too when it rides along
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if one_kernel else "parallel",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(qf, do, out, lse_pk, kf, vf)
    if one_kernel:
        return tuple(grads)
    dk, dv = grads

    def k_map(b_, g, i, j):
        return (b_, _redirect(causal, vis, i, j, j), g + koff)

    def v_map(b_, g, i, j):
        return (b_, _redirect(causal, vis, i, j, j), g + voff)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel_packed, **statics),
        name="flash_bwd_dq_packed",
        grid=(b, n_packs, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, w), lambda b_, g, i, j: (b_, i, g)),
            pl.BlockSpec((None, block_q, w), lambda b_, g, i, j: (b_, i, g)),
            pl.BlockSpec((None, block_q, w), lambda b_, g, i, j: (b_, i, g)),
            pl.BlockSpec((None, None, block_q, hpc),
                         lambda b_, g, i, j: (b_, g, i, 0)),
            pl.BlockSpec((None, block_k, w), k_map),
            pl.BlockSpec((None, block_k, w), v_map),
        ],
        out_specs=pl.BlockSpec((None, block_q, w),
                               lambda b_, g, i, j: (b_, i, g)),
        out_shape=dq_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, w), jnp.float32),
            pltpu.VMEM((block_q, hpc), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(qf, do, out, lse_pk, kf, vf)
    return dq, dk, dv


@functools.partial(jax.jit, static_argnames=_PACKED_STATICS)
def _flash_bwd_packed(qf, kf, vf, do, out, lse_pk, *, n_heads, causal,
                      block_q, block_k, interpret, fused_qkv=False):
    """Packed grads. lse_pk: (b, n_packs, seq_q, hpc) fp32; out is the
    saved forward output — delta (rowsum(do*o) per head) is computed
    inside the kernels from do/out tiles whose DMAs ride the existing
    block schedule. fused_qkv: as in _flash_fwd_packed (dq/dk/dv still
    come back as three (b, s, h*d) arrays; the caller concatenates once
    for the projection backward). One kernel where its whole-sequence dq
    fits VMEM (_ONE_KERNEL_BWD_VMEM), two past that."""
    return _packed_bwd_calls(
        qf, kf, vf, do, out, lse_pk, one_kernel=None, n_heads=n_heads,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        fused_qkv=fused_qkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_packed(qf, kf, vf, n_heads, causal, block_q, block_k):
    out, _ = _flash_fwd_packed(
        qf, kf, vf, n_heads=n_heads, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(),
    )
    return out


def _flash_packed_vjp_fwd(qf, kf, vf, n_heads, causal, block_q, block_k):
    out, lse_pk = _flash_fwd_packed(
        qf, kf, vf, n_heads=n_heads, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(),
    )
    return out, (qf, kf, vf, out, lse_pk)


def _flash_packed_vjp_bwd(n_heads, causal, block_q, block_k, res, g_out):
    qf, kf, vf, out, lse_pk = res
    g_out = g_out.astype(qf.dtype)
    dq, dk, dv = _flash_bwd_packed(
        qf, kf, vf, g_out, out, lse_pk, n_heads=n_heads, causal=causal,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )
    return dq, dk, dv


_flash_packed.defvjp(_flash_packed_vjp_fwd, _flash_packed_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_packed_qkv(qkvf, n_heads, causal, block_q, block_k):
    out, _ = _flash_fwd_packed(
        qkvf, qkvf, qkvf, n_heads=n_heads, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(), fused_qkv=True,
    )
    return out


def _flash_packed_qkv_vjp_fwd(qkvf, n_heads, causal, block_q, block_k):
    out, lse_pk = _flash_fwd_packed(
        qkvf, qkvf, qkvf, n_heads=n_heads, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(), fused_qkv=True,
    )
    return out, (qkvf, out, lse_pk)


def _flash_packed_qkv_vjp_bwd(n_heads, causal, block_q, block_k, res, g_out):
    qkvf, out, lse_pk = res
    g_out = g_out.astype(qkvf.dtype)
    dq, dk, dv = _flash_bwd_packed(
        qkvf, qkvf, qkvf, g_out, out, lse_pk, n_heads=n_heads,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(), fused_qkv=True,
    )
    # one concatenate back to the projection layout — the only
    # materialized boundary op on the fused path (vs 3 slice fusions +
    # 6 relayout copies per layer on the sliced path)
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


_flash_packed_qkv.defvjp(_flash_packed_qkv_vjp_fwd, _flash_packed_qkv_vjp_bwd)


def flash_attention_qkv(
    qkv: jnp.ndarray,  # (batch, seq, 3 * heads * head_dim)
    n_heads: int,
    *,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Fused self-attention straight off the QKV projection output.

    `qkv` is the flat (b, s, 3*h*d) activation the projection produces
    (column order [q heads | k heads | v heads] — exactly the row-major
    flatten of DenseGeneral's (3, h, d) features). The packed kernels
    window it at column offsets, so q/k/v are never sliced out: at
    lm_base shapes the sliced path paid ~4 ms/step in slice fusions and
    layout copies around the kernel boundary (round-4 profile), all of
    which this entry removes. Returns (b, s, h, d) like flash_attention.

    Requires packable head shapes (_heads_per_pack) and seq_q == seq_k
    (it IS self-attention); callers fall back to flash_attention with
    explicit slices otherwise."""
    b, s, three_hd = qkv.shape
    if three_hd % 3:
        raise ValueError(f"qkv last dim {three_hd} is not 3*h*d")
    hd = three_hd // 3
    d = hd // n_heads
    if _heads_per_pack(n_heads, d) is None:
        q, k, v = (
            qkv[..., :hd], qkv[..., hd:2 * hd], qkv[..., 2 * hd:]
        )
        rs = lambda x: x.reshape(b, s, n_heads, d)
        return flash_attention(
            rs(q), rs(k), rs(v), causal=causal, block_q=block_q,
            block_k=block_k,
        )
    return flash_attention_flat(
        qkv, n_heads, causal=causal, block_q=block_q, block_k=block_k
    ).reshape(b, s, n_heads, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_packed_rope_qkv(qkvf, cos, sin, n_heads, causal, block_q, block_k):
    return _flash_packed_rope_qkv_vjp_fwd(
        qkvf, cos, sin, n_heads, causal, block_q, block_k)[0]


def _flash_packed_rope_qkv_vjp_fwd(qkvf, cos, sin, n_heads, causal, block_q,
                                   block_k):
    # q' and k' leave the rotary pass row-major; v stays where the
    # projection wrote it, the third column window of qkvf
    qf, kf = rope_flat_qk(qkvf, cos, sin, n_heads=n_heads)
    out, lse_pk = _flash_fwd_packed(
        qf, kf, qkvf, n_heads=n_heads, causal=causal, block_q=block_q,
        block_k=block_k, interpret=_interpret(),
    )
    return out, (qf, kf, qkvf, cos, sin, out, lse_pk)


def _flash_packed_rope_qkv_vjp_bwd(n_heads, causal, block_q, block_k, res,
                                   g_out):
    qf, kf, qkvf, cos, sin, out, lse_pk = res
    dq, dk, dv = _flash_bwd_packed(
        qf, kf, qkvf, g_out.astype(qkvf.dtype), out, lse_pk,
        n_heads=n_heads, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )
    # the rotation's transpose and the concatenate in one pass: the
    # projection's cotangent is one row-major bf16 array
    return (rope_flat_bwd(dq, dk, dv, cos, sin, n_heads=n_heads),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


_flash_packed_rope_qkv.defvjp(_flash_packed_rope_qkv_vjp_fwd,
                              _flash_packed_rope_qkv_vjp_bwd)


def flash_attention_flat(
    qkv: jnp.ndarray,  # (batch, seq, 3 * heads * head_dim)
    n_heads: int,
    *,
    causal: bool = False,
    rope: bool = False,
    block_q: int = 512,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Self-attention that never leaves the flat row-major layout: the
    (b, s, 3*h*d) output of ONE qkv matmul in, (b, s, h*d) out for ONE
    out-projection matmul, and one (b, s, 3*h*d) cotangent back. No
    (b, s, h, d) array exists in between, so XLA has no 64-wide minor
    dimension to lay out sequence-minor and relayout around the kernels
    (eight copies a layer, two of them through float32, at lm_base's
    shape: PERF.md section 6, PR 33).

    `rope` rotates q and k at positions arange(s), as `apply_rope` does,
    in an element-wise kernel over the flat rows (ops/rope.py
    rope_flat_qk); the packed kernels then read q' and k' from its
    outputs and v from the projection's third column window. Without it
    this is `flash_attention_qkv` less its reshape. Heads must pack
    (_heads_per_pack): the caller keeps the 4-D path otherwise."""
    b, s, three_hd = qkv.shape
    hd = three_hd // 3
    if three_hd % 3 or _heads_per_pack(n_heads, hd // n_heads) is None:
        raise ValueError(
            f"{n_heads} heads over a {three_hd}-wide qkv do not pack into "
            f"{_LANES}-lane tiles")
    if not rope:
        return _flash_packed_qkv(qkv, n_heads, causal, block_q, block_k)
    cos, sin = flat_rope_tables(jnp.arange(s), hd, n_heads)
    return _flash_packed_rope_qkv(
        qkv, cos, sin, n_heads, causal, block_q, block_k)


# --------------------------------------------------------------------- #
# Whole-sequence kernels for short sequences (ViT: 196 patches).
#
# The streaming kernels above exist so that a sequence need not fit VMEM.
# Where it does (a few hundred tokens), a grid cell can hold ALL the keys
# of a head, and attention is plain again: one score tile, one row max,
# one exp, one row sum, one normalisation of the (s, d) output; no running
# m / l, no rescale, no scratch carried between grid steps. The backward
# is ONE kernel of five dots (s, dp, dv, dk, dq): nothing accumulates
# across cells, so s and dp are not recomputed for a second kernel.
#
# A grid cell is G images x one 128-lane head pack, the whole sequence of
# each, windowed out of the flat (b, s, 3*h*d) projection like the packed
# kernels' fused_qkv input. The block's sequence dim IS the array's, so
# nothing is padded in HBM; the tiles are the logical (s, s) and (s, d),
# and what lies between 196 and the lane tile's 256 exists in vregs
# only, where Mosaic masks it (padding the keys to 256 by hand, with a
# penalty on the tail, read 3% slower on the chip: PERF.md section 6,
# PR 29). The body is unrolled over the cell's images and the pack's
# heads: one straight-line block (PERF.md section 6, PR 27: Mosaic
# overlaps MXU and VPU work inside a basic block only). The backward
# writes dq, dk, dv into ONE flat (b, s, 3*h*d) cotangent: a third grid
# axis of 3 steps visits the q, k and v column blocks of the pack; step 0
# computes all three and keeps dk, dv in VMEM, steps 1 and 2 copy them
# out (no concatenate in HBM).
# --------------------------------------------------------------------- #

# The sequence lengths for which these kernels are chosen where nobody
# names a kernel (models/vit.py SelfAttention, attn_impl="auto"). Upper
# end: the longest sequence one cell holds; at 1280 the backward's score
# tiles (four (s, s) float32 live at once) ask for 17.86 MB of the 16 MB
# of scoped VMEM (tests/test_tpu_compile.py compiles the end). Lower end:
# the shortest length measured; XLA's own attention lost at every length
# from there up (PERF.md section 6, PR 29).
SHORT_SEQ_MIN = 64
SHORT_SEQ_MAX = 1024

# Images a grid cell: enough that a cell's dots outweigh a grid step's
# fixed cost (~0.35 us), few enough that the unrolled body and the cell's
# double-buffered blocks stay small. On the chip 4 and 8 images read the
# same at ViT-B/16's shape, 2 and 16 slower, and 6 (a ragged last cell of
# 128 images) slower than either (PERF.md section 6, PR 29).
_SHORT_CELL_FLOPS = 5e8
_SHORT_CELL_BYTES = 10 * 2**20
_SHORT_MAX_IMAGES = 16


def short_seq_supported(seq: int, n_heads: int, head_dim: int) -> bool:
    """Can the whole-sequence kernels run this self-attention shape?
    (Heads that pack into 128 lanes, a sequence one cell holds.)"""
    return (_heads_per_pack(n_heads, head_dim) is not None
            and 1 <= seq <= SHORT_SEQ_MAX)


def _images_per_cell(b: int, seq: int, w: int) -> int:
    """G: images a grid cell of the short kernels holds (see above): the
    fewest that make a cell's work, capped by VMEM, and the largest
    divisor of the batch at or under that where it is at least half of it
    (no ragged last cell)."""
    rows = -(-seq // 16) * 16
    # the backward's blocks: q, k, v, do in and one cotangent out, each
    # two deep, and the two kept cotangents
    cell_bytes = 12 * rows * w * 2
    # forward + backward: seven dots of 2 * s * s * w
    cell_flops = 7 * 2.0 * seq * seq * w
    g = max(int(min(_SHORT_CELL_BYTES // cell_bytes,
                    -(-_SHORT_CELL_FLOPS // cell_flops),
                    _SHORT_MAX_IMAGES, b)), 1)
    whole = max(n for n in range(1, g + 1) if b % n == 0)
    return whole if 2 * whole >= g else g


def _short_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, g,
                      hpc, d, causal):
    """Grid cell (image group, head pack): plain softmax attention of g
    images x hpc heads, each over its whole sequence. lse_ref: (seq,
    g * hpc), one column an (image, head)."""
    seq = q_ref.shape[1]
    pen = _tile_penalty(0, seq, seq) if causal else None
    for i in range(g):
        for lanes, hh in _packed_heads(hpc, d):
            q = (q_ref[i, :, lanes] * sm_scale).astype(q_ref.dtype)
            s = _mask_tail(_dot_tb(q, k_ref[i, :, lanes]), pen)
            m = jnp.max(s, axis=1, keepdims=True)
            e = jnp.exp(s - m)
            l = jnp.sum(e, axis=1, keepdims=True)
            v = v_ref[i, :, lanes]
            o = lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[i, :, lanes] = (o * (1.0 / l)).astype(o_ref.dtype)
            col = i * hpc + hh
            lse_ref[:, col:col + 1] = m + jnp.log(l)


def _short_bwd_tiles(q_ref, k_ref, v_ref, do_ref, lse_ref, *, sm_scale, g,
                     hpc, d, causal):
    """The backward of one cell, as a generator over (image, head):
    (i, lanes, dq, dk, dv) in float32. Five dots an (image, head).

    delta is rowsum(p * dp) over the score tile that is here anyway, not
    the streaming kernels' rowsum(do * out): with the float32 p on both
    sides a row of ds sums to zero as it does under autodiff of
    _attention, where out's bf16 rounding leaves every row a residue that
    lands, times q, in the K bias's gradient (zero in exact arithmetic;
    AdamW steps it at full size whatever its norm: PERF.md section 6,
    PR 29). It also spares the backward the out block."""
    seq = q_ref.shape[1]
    pen = _tile_penalty(0, seq, seq) if causal else None
    for i in range(g):
        for lanes, hh in _packed_heads(hpc, d):
            qs = (q_ref[i, :, lanes] * sm_scale).astype(q_ref.dtype)
            k = k_ref[i, :, lanes]
            do = do_ref[i, :, lanes]
            s = _mask_tail(_dot_tb(qs, k), pen)
            col = i * hpc + hh
            p = jnp.exp(s - lse_ref[:, col:col + 1])
            pdp = p * _dot_tb(do, v_ref[i, :, lanes])       # p * (do @ v^T)
            delta = jnp.sum(pdp, axis=1, keepdims=True)
            ds = (pdp - p * delta).astype(qs.dtype)
            dv = _dot_ta(p.astype(do.dtype), do)            # p^T @ do
            dk = _dot_ta(ds, qs)                            # ds^T @ qs
            dq = lax.dot_general(                           # ds @ k
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            yield i, lanes, dq, dk, dv


def _short_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dq_ref, dk_ref,
                      dv_ref, **kw):
    """Sliced inputs: grid cell (image group, head pack), three outputs."""
    for i, lanes, dq, dk, dv in _short_bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref, **kw):
        dq_ref[i, :, lanes] = dq.astype(dq_ref.dtype)
        dk_ref[i, :, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[i, :, lanes] = dv.astype(dv_ref.dtype)


def _short_bwd_kernel_qkv(q_ref, k_ref, v_ref, do_ref, lse_ref, dqkv_ref,
                          dk_scr, dv_scr, **kw):
    """Fused input: grid cell (image group, head pack, t); dqkv_ref is the
    pack's q column block of the flat cotangent at t == 0, its k block at
    1, its v block at 2. The inputs' block indices do not depend on t, so
    they are fetched once a cell."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _compute():
        for i, lanes, dq, dk, dv in _short_bwd_tiles(
                q_ref, k_ref, v_ref, do_ref, lse_ref, **kw):
            dqkv_ref[i, :, lanes] = dq.astype(dqkv_ref.dtype)
            dk_scr[i, :, lanes] = dk.astype(dk_scr.dtype)
            dv_scr[i, :, lanes] = dv.astype(dv_scr.dtype)

    @pl.when(t == 1)
    def _dk():
        dqkv_ref[:] = dk_scr[:]

    @pl.when(t == 2)
    def _dv():
        dqkv_ref[:] = dv_scr[:]


def _short_dims(qf, n_heads, fused_qkv):
    """(b, seq, hd, d, hpc, w, n_packs, koff, voff) of a short-kernel call."""
    b, seq, hd = qf.shape
    if fused_qkv:
        hd //= 3
    d = hd // n_heads
    hpc = _heads_per_pack(n_heads, d)
    w = hpc * d
    n_packs = n_heads // hpc
    off = n_packs if fused_qkv else 0
    return b, seq, hd, d, hpc, w, n_packs, off, 2 * off


_SHORT_STATICS = ("n_heads", "causal", "g", "interpret", "fused_qkv")


@functools.partial(jax.jit, static_argnames=_SHORT_STATICS)
def _flash_short_fwd(qf, kf, vf, *, n_heads, causal, g, interpret,
                     fused_qkv=False):
    """qf/kf/vf: flat (b, s, h*d), or with fused_qkv all the SAME
    (b, s, 3*h*d) projection output, windowed at column-block offsets as
    in _flash_fwd_packed. Returns (out (b, s, h*d), lse (n_cells, n_packs,
    s, g * hpc) float32: one column an (image of the cell, head of the
    pack)). A batch that g does not divide leaves the last cell ragged:
    its missing images are never fetched or written."""
    b, seq, hd, d, hpc, w, n_packs, koff, voff = _short_dims(
        qf, n_heads, fused_qkv)
    n_cells = pl.cdiv(b, g)
    kernel = functools.partial(
        _short_fwd_kernel, sm_scale=1.0 / (d ** 0.5), g=g, hpc=hpc, d=d,
        causal=causal)
    block = (g, seq, w)
    return pl.pallas_call(
        kernel,
        name="flash_short_fwd",
        grid=(n_cells, n_packs),
        in_specs=[
            pl.BlockSpec(block, lambda c, p: (c, 0, p)),
            pl.BlockSpec(block, lambda c, p: (c, 0, p + koff)),
            pl.BlockSpec(block, lambda c, p: (c, 0, p + voff)),
        ],
        out_specs=[
            pl.BlockSpec(block, lambda c, p: (c, 0, p)),
            pl.BlockSpec((None, None, seq, g * hpc),
                         lambda c, p: (c, p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, seq, hd), qf.dtype),
            jax.ShapeDtypeStruct((n_cells, n_packs, seq, g * hpc),
                                 jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qf, kf, vf)


@functools.partial(jax.jit, static_argnames=_SHORT_STATICS)
def _flash_short_bwd(qf, kf, vf, do, lse, *, n_heads, causal, g, interpret,
                     fused_qkv=False):
    """Gradients of _flash_short_fwd's out: (dq, dk, dv), each (b, s,
    h*d), or with fused_qkv ONE (b, s, 3*h*d) cotangent in the
    projection's own column order."""
    b, seq, hd, d, hpc, w, n_packs, koff, voff = _short_dims(
        qf, n_heads, fused_qkv)
    n_cells = pl.cdiv(b, g)
    kw = dict(sm_scale=1.0 / (d ** 0.5), g=g, hpc=hpc, d=d, causal=causal)
    block = (g, seq, w)

    def at(off):
        return pl.BlockSpec(block, lambda c, p, *t: (c, 0, p + off))

    in_specs = [
        at(0), at(koff), at(voff), at(0),
        pl.BlockSpec((None, None, seq, g * hpc),
                     lambda c, p, *t: (c, p, 0, 0)),
    ]
    if not fused_qkv:
        return pl.pallas_call(
            functools.partial(_short_bwd_kernel, **kw),
            name="flash_short_bwd",
            grid=(n_cells, n_packs),
            in_specs=in_specs,
            out_specs=[at(0)] * 3,
            out_shape=[jax.ShapeDtypeStruct((b, seq, hd), qf.dtype)] * 3,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(qf, kf, vf, do, lse)
    return pl.pallas_call(
        functools.partial(_short_bwd_kernel_qkv, **kw),
        name="flash_short_bwd",
        grid=(n_cells, n_packs, 3),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            block, lambda c, p, t: (c, 0, p + t * n_packs)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM(block, qf.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_short(qf, kf, vf, n_heads, causal, g):
    return _flash_short_fwd(qf, kf, vf, n_heads=n_heads, causal=causal, g=g,
                            interpret=_interpret())[0]


def _flash_short_vjp_fwd(qf, kf, vf, n_heads, causal, g):
    out, lse = _flash_short_fwd(qf, kf, vf, n_heads=n_heads, causal=causal,
                                g=g, interpret=_interpret())
    return out, (qf, kf, vf, lse)


def _flash_short_vjp_bwd(n_heads, causal, g, res, g_out):
    qf, kf, vf, lse = res
    return tuple(_flash_short_bwd(
        qf, kf, vf, g_out.astype(qf.dtype), lse, n_heads=n_heads,
        causal=causal, g=g, interpret=_interpret()))


_flash_short.defvjp(_flash_short_vjp_fwd, _flash_short_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_short_qkv(qkvf, n_heads, causal, g):
    return _flash_short_fwd(qkvf, qkvf, qkvf, n_heads=n_heads, causal=causal,
                            g=g, interpret=_interpret(), fused_qkv=True)[0]


def _flash_short_qkv_vjp_fwd(qkvf, n_heads, causal, g):
    out, lse = _flash_short_fwd(
        qkvf, qkvf, qkvf, n_heads=n_heads, causal=causal, g=g,
        interpret=_interpret(), fused_qkv=True)
    return out, (qkvf, lse)


def _flash_short_qkv_vjp_bwd(n_heads, causal, g, res, g_out):
    qkvf, lse = res
    return (_flash_short_bwd(
        qkvf, qkvf, qkvf, g_out.astype(qkvf.dtype), lse,
        n_heads=n_heads, causal=causal, g=g, interpret=_interpret(),
        fused_qkv=True),)


_flash_short_qkv.defvjp(_flash_short_qkv_vjp_fwd, _flash_short_qkv_vjp_bwd)


def _check_short(seq, n_heads, d):
    if not short_seq_supported(seq, n_heads, d):
        raise ValueError(
            f"the whole-sequence kernels take heads that pack into 128 "
            f"lanes and a sequence of at most {SHORT_SEQ_MAX}; got seq "
            f"{seq}, {n_heads} heads of {d}")


def flash_short_qkv(qkv, n_heads: int, *, causal: bool = False,
                    images_per_cell=None) -> jnp.ndarray:
    """Whole-sequence self-attention straight off the flat (b, s, 3*h*d)
    QKV projection (column order [q heads | k heads | v heads], as
    flash_attention_qkv takes it). Returns (b, s, h, d). Shapes must pass
    short_seq_supported; `images_per_cell` overrides G (tests, timing)."""
    b, s, three_hd = qkv.shape
    d = three_hd // 3 // n_heads
    _check_short(s, n_heads, d)
    g = images_per_cell or _images_per_cell(
        b, s, _heads_per_pack(n_heads, d) * d)
    return _flash_short_qkv(qkv, n_heads, causal, g).reshape(
        b, s, n_heads, d)


def flash_short(q, k, v, *, causal: bool = False,
                images_per_cell=None) -> jnp.ndarray:
    """The same over sliced (b, s, h, d) q, k, v of one length."""
    b, s, h, d = q.shape
    _check_short(s, h, d)
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"flash_short is self-attention over one length: q {q.shape}, "
            f"k {k.shape}, v {v.shape}")
    g = images_per_cell or _images_per_cell(b, s, _heads_per_pack(h, d) * d)
    flat = lambda x: x.reshape(b, s, h * d)
    return _flash_short(flat(q), flat(k), flat(v), h, causal, g).reshape(
        b, s, h, d)


# --------------------------------------------------------------------- #
# Differentiable entry points.
# _flash_lse returns (out, lse), both differentiable; the lse cotangent
# folds into delta (see module docstring). flash_attention drops lse.
# --------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse(q, k, v, causal, block_q, block_k):
    out, lse = _flash_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )
    return out, lse


def _flash_lse_vjp_fwd(q, k, v, causal, block_q, block_k):
    out, lse = _flash_fwd(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    g_out = g_out.astype(q.dtype)
    delta = jnp.sum(
        g_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    if g_lse is not None and not isinstance(
        g_lse, jax.custom_derivatives.SymbolicZero
    ):
        delta = delta - g_lse.astype(jnp.float32)
    dq, dk, dv = _flash_bwd(
        q, k, v, g_out, lse, delta,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )
    return dq, dk, dv


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention_with_lse(
    q: jnp.ndarray,  # (batch_heads, seq, head_dim)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 1024,
):
    """Fused attention over folded (b*h, s, d) layout, returning (out, lse).

    lse is a differentiable output — the building block ring attention uses
    to merge per-ring-step partials (parallel/ring.py)."""
    return _flash_lse(q, k, v, causal, block_q, block_k)


def flash_attention(
    q: jnp.ndarray,  # (batch, seq, heads, head_dim)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Fused multi-head attention; layout-matches ops.attention._attention.

    Default blocks (512, 1024): on TPU v5e at lm_base shapes (head_dim
    64) every smaller grid cell lost more to per-cell overhead than its
    mask savings gave (BENCHMARKS.md, round 4); the causal saving inside
    a cell is taken by 256-wide sub-tiles instead (_SUB; PERF.md section
    6, PR 27). Blocks clamp to the sequence length, so short-seq callers
    (ViT at s=64) are unaffected.

    When head_dim packs into 128 lanes (d <= 128 dividing 128, head count
    a multiple of the pack; or d a multiple of 128) the packed-layout
    kernels run directly on the flat (b, s, h*d) activations — no
    transposes at the model boundary (see the packed section above).
    Other shapes take the folded (b*h, s, d) path."""
    b, sq, h, d = q.shape
    sk = k.shape[1]

    if _heads_per_pack(h, d) is not None:
        out = _flash_packed(
            q.reshape(b, sq, h * d), k.reshape(b, sk, h * d),
            v.reshape(b, sk, h * d), h, causal, block_q, block_k,
        )
        return out.reshape(b, sq, h, d)

    def fold(x, s):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, x.shape[-1])

    out, _ = _flash_lse(
        fold(q, sq), fold(k, sk), fold(v, sk), causal, block_q, block_k
    )
    return jnp.transpose(out.reshape(b, h, sq, d), (0, 2, 1, 3))
