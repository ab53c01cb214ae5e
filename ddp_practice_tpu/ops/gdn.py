"""The gated delta rule (Gated DeltaNet): a matrix state a head, a prompt's
chunked scan and the one-token recurrence of decode.

A value head h (its key head is h // (value heads / key heads)) keeps a
state S (key_dim, value_dim) in float32:

    S  <- exp(g_t) S                         g_t <= 0, one scalar a head
    d  =  beta_t (v_t - S^T k_t)             the write reads the decayed state
    S  <- S + k_t d^T
    o_t = S^T q_t

`ops/ssm.py ssm_step` (Mamba-2) has the decay, the outer product and the
contraction; the READ of the decayed state before the write is what it
lacks, and what makes the chunked form need a triangular solve a chunk.

`gdn_scan`, over a chunk of C positions with G the running sum of g inside
it and S0 the state entering it:

    A[t,s] = beta_t exp(G_t - G_s) (k_t . k_s)   s < t, else 0
    T      = (I + A)^-1
    U      = T (beta V) - T (beta exp(G) K) S0   the rows d_t
    O      = (exp(G) Q) S0 + tril(Q K^T exp(G_t - G_s)) U
    S_C    = exp(G_C) S0 + (exp(G_C - G) K)^T U

Everything that does not hold S0 is a chunk's TERMS (`_chunk_terms`; A is
strictly lower of C = 64 rows and inverted by blocks, `_unit_lower_inverse`:
ten 64^3 matmuls and no solve). On the TPU they are one Pallas kernel,
`gdn_terms` (PR 42): a grid cell reads a chunk's rows of q, k, v, g and beta
once, in the layout the mixer has them, holds every (C, C) array in VMEM
and writes the six terms; before, they were some thirty XLA ops a layer
whose float32 operands and results crossed HBM. Off the TPU the terms are
plain matmuls in XLA for all chunks at once, which is also the kernel's
oracle. The three lines that hold S0 are the sequential part: the Pallas
kernel `gdn_scan`, the state in VMEM from chunk to chunk, off the TPU a
`lax.scan` over the chunks. A position with beta = 0, g = 0 and zero q, k,
v moves nothing: left padding and the tail of a partial chunk.

A decay a KEY CHANNEL (Kimi Delta Attention, ops/kda.py, which imports this
file's inverse, masks, head blocks and carry): `exp(g_t)` is then a vector
over the key lanes, `exp(G_t - G_s)` stays INSIDE the dot product `k_t . k_s`
and `q_t . k_s` and can no longer be factored out as the one scalar a pair
that `decay` is here, so the two (C, C) sums are matmuls of rows scaled
about a reference row, `(k_t exp(G_t - G_r)) . (k_s exp(G_r - G_s))`. Over a
whole chunk of 64 positions G runs to 64 |g|max and `exp(G_r - G_s)` leaves
float32 (|g| up to 5: exp(320)); about the start of a SUB-chunk of 16 it is
at most exp(80) < exp(88), which is what that model's bounded gate buys.
The step's decay is a third column beside k and q (one transpose a cell) and
the carry's end-of-chunk decay a row over the key lanes (`_scan_kernel`
`key_decay`); T, U, the terms' names and the three carry lines are as here.

Device op names (PERF.md section 3): the kernels are `gdn_step`,
`gdn_terms` and `gdn_scan`, one `gdn_terms` beside every `gdn_scan`, both
under the scope `gdn_scan`; `flood_gdn_dev_pct` sums the three by their
prefix, the two rooflines read `gdn_step` and `gdn_scan` alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.ssm import _heads_of_groups
from ddp_practice_tpu.utils import backend

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
# positions a chunk of the scan holds
CHUNK = 64
_LANES = 128
# value heads a grid cell of either kernel takes (64 KB of state each)
_HEADS = 16
# the scan's cell holds a chunk's six terms and its output for 16 heads two
# deep (5.6 MB at 128 x 128 heads) beside 4 MB of state blocks
_SCAN_VMEM = 40 << 20


def _dot(x, y, dims=(((1,), (0,)), ((), ()))):
    """float32 matmul at full precision (inside a kernel Mosaic's default
    is its own; the state's error would feed back through every read)."""
    return lax.dot_general(x, y, dims, precision=HIGHEST,
                           preferred_element_type=F32)


_TA = (((0,), (0,)), ((), ()))   # x^T y


def _column_selector(n: int, width: int):
    """(8, n width) float32 0 / 1: block r of `width` lanes has ones in row
    r. What `_lane_columns` multiplies by; the same for every head of a
    kernel's cell, so made once a cell."""
    at = lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (8, n * width), 1)
    return ((lane >= at * width) & (lane < (at + 1) * width)).astype(F32)


def _lane_columns(rows, pick):
    """Up to eight (1, d) rows -> as many (d, width) tiles, tile r holding
    row r's values as COLUMNS broadcast along the lanes (`col[a, b] =
    x[a]`): ONE depth-8 matmul of the rows, one a sublane, against `pick`
    (`_column_selector`). A column vector is not a layout the lanes hold
    (`_step_kernel`)."""
    d, width = rows[0].shape[-1], pick.shape[1] // len(rows)
    at = lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    x = jnp.zeros((8, d), F32)
    for r, row in enumerate(rows):
        x = jnp.where(at == r, jnp.broadcast_to(row, (8, d)), x)
    cols = _dot(x, pick, _TA)                     # (d, rows x width)
    return [cols[:, r * width:(r + 1) * width] for r in range(len(rows))]


def gdn_step_reference(q, k, v, g, beta, state):
    """One token, plain jax.numpy. q, k (b, hk, dk), normalised and scaled
    by the caller; v (b, hv, dv); g, beta (b, hv); state (b, hv, dk, dv)
    float32. Returns (o (b, hv, dv) float32, new state)."""
    hv = v.shape[1]
    q, k = (_heads_of_groups(x.astype(F32), hv) for x in (q, k))
    s = state * jnp.exp(g.astype(F32))[..., None, None]
    read = jnp.einsum("bhkv,bhk->bhv", s, k, precision=HIGHEST)
    d = beta.astype(F32)[..., None] * (v.astype(F32) - read)
    s = s + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=HIGHEST), s


def gdn_scan_reference(q, k, v, g, beta, h0):
    """The recurrence one position at a time (`lax.scan`): what the chunked
    form must equal. q, k (b, l, hk, dk); v (b, l, hv, dv); g, beta
    (b, l, hv); h0 (b, hv, dk, dv). Returns (o (b, l, hv, dv) float32,
    final state)."""
    def one(state, inp):
        o_t, state = gdn_step_reference(*inp, state)
        return state, o_t

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    final, os_ = lax.scan(one, h0.astype(F32), xs)
    return jnp.moveaxis(os_, 0, 1), final


# ------------------------------------------------------------- decode step
def _step_kernel(q_ref, k_ref, v_ref, da_ref, beta_ref, h_ref, o_ref, ho_ref,
                 *, heads, per_key):
    """One grid cell: one sequence, `heads` value heads. A head's (dk, dv)
    tile is decayed, read, written and read again on the VPU, whole
    registers at a time: k and q stand as COLUMNS broadcast along the lanes
    (`col[a, b] = x[a]`, made once a key head as a depth-8 matmul of the
    vector against ones, row 0 real: a column vector is not a layout the
    lanes hold), so `S^T k` is a product of two tiles summed down the
    sublanes and `k (x) d` a product with d's row. (Through the MXU, the
    vector as eight equal rows against the tile, each head paid two float32
    passes of a (128, 128) operand and the kernel read a third of its
    roofline: PERF.md section 6, PR 38.)"""
    dk, dv = h_ref.shape[-2:]
    row0 = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0
    ones = jnp.ones((8, dv), F32)
    col = lambda ref, j: _dot(
        jnp.where(row0, jnp.broadcast_to(ref[j:j + 1, :], (8, dk)), 0.0),
        ones, _TA)                                             # (dk, dv)
    for j in range(heads // per_key):
        kcol, qcol = col(k_ref, j), col(q_ref, j)
        for i in range(j * per_key, (j + 1) * per_key):
            s = h_ref[i] * da_ref[i:i + 1, :]
            read = jnp.sum(s * kcol, axis=0, keepdims=True)    # (1, dv)
            d = beta_ref[i:i + 1, :] * (v_ref[i:i + 1, :] - read)
            new = s + kcol * d
            ho_ref[i] = new
            o_ref[i:i + 1, :] = jnp.sum(new * qcol, axis=0, keepdims=True)


def gdn_step(q, k, v, g, beta, state):
    """One token of the recurrence for every sequence; arguments and results
    as `gdn_step_reference`: the Pallas kernel on the TPU, plain jax.numpy
    elsewhere."""
    with jax.named_scope("gdn_step"):
        if not backend.on_tpu():
            return gdn_step_reference(q, k, v, g, beta, state)
        return gdn_step_kernel(q, k, v, g, beta, state)


def _head_block(hv: int, hk: int) -> tuple:
    """(value heads, key heads) a grid cell takes: `_HEADS` value heads
    where that is whole key heads in whole sublane tiles, else all."""
    per_key = hv // hk
    if hv % _HEADS == 0 and _HEADS % per_key == 0 \
            and (_HEADS // per_key) % 8 == 0:
        return _HEADS, _HEADS // per_key
    return hv, hk


def gdn_step_kernel(q, k, v, g, beta, state):
    """`gdn_step` as ONE device op of that name (interpret mode off the
    TPU, where only the tests call it)."""
    bsz, hv, dv = v.shape
    hk, dk = k.shape[1:]
    bh, bk = _head_block(hv, hk)
    # a head's decay and beta, laid along the lanes so the kernel broadcasts
    # them down the sublanes (1 KB a head beside its 128 KB of state)
    lanes = lambda x: jnp.broadcast_to(x.astype(F32)[..., None],
                                       (bsz, hv, dv))
    key = pl.BlockSpec((None, bk, dk), lambda i, j: (i, j, 0))
    val = pl.BlockSpec((None, bh, dv), lambda i, j: (i, j, 0))
    st = pl.BlockSpec((None, bh, dk, dv), lambda i, j: (i, j, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=bh, per_key=hv // hk),
        grid=(bsz, hv // bh),
        in_specs=[key, key, val, val, val, st],
        out_specs=[val, st],
        out_shape=[jax.ShapeDtypeStruct((bsz, hv, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={5: 1},     # the state is rewritten in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=not backend.on_tpu(),
        name="gdn_step",
    )(q.astype(F32), k.astype(F32), v.astype(F32),
      lanes(jnp.exp(g.astype(F32))), lanes(beta), state)
    return o, new


# ------------------------------------------------------------ prefill scan
def _chunk_terms(q, k, v, g, beta, chunk: int) -> dict:
    """What the chunked form needs of every chunk that does not hold the
    state, for all chunks at once; each (b, hv, nc, C, .) float32:
    `w` = T (beta exp(G) K), `u0` = T (beta V), `qg` = exp(G) Q, `p` =
    tril(Q K^T exp(G_t - G_s)) (C rows, its columns padded to 128 lanes),
    `kend` = exp(G_C - G) K, `dend` = exp(G_C) along value_dim lanes
    (b, hv, nc, 1, dv)."""
    bsz, l, hv, dv = v.shape
    nc, c = l // chunk, chunk
    heads = lambda x: jnp.moveaxis(        # (b, l, h, d) -> (b, h, nc, C, d)
        x.astype(F32).reshape(bsz, nc, c, *x.shape[2:]), 3, 1)
    qh, kh = (heads(_heads_of_groups(x, hv)) for x in (q, k))
    vh = heads(v)
    gh, bh = (heads(x[..., None])[..., 0] for x in (g, beta))  # (b,h,nc,C)
    cum = jnp.cumsum(gh, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]                # G_t - G_s
    low = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.where(low, jnp.exp(jnp.where(low, seg, 0.0)), 0.0)
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    kk = mm("bhctd,bhcsd->bhcts", kh, kh)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    t = _unit_lower_inverse(
        jnp.where(strict, bh[..., :, None] * decay * kk, 0.0))
    eg = jnp.exp(cum)[..., None]
    return {
        "w": mm("bhcts,bhcsd->bhctd", t, bh[..., None] * eg * kh),
        "u0": mm("bhcts,bhcsd->bhctd", t, bh[..., None] * vh),
        "qg": eg * qh,
        # its C columns zero-filled to whole 128-lane tiles: the kernel's
        # matmul against it contracts over lanes that hold something
        "p": jnp.pad(decay * mm("bhctd,bhcsd->bhcts", qh, kh),
                     ((0, 0),) * 4 + ((0, -c % _LANES),)),
        "kend": jnp.exp(cum[..., -1:] - cum)[..., None] * kh,
        "dend": jnp.broadcast_to(jnp.exp(cum[..., -1])[..., None, None],
                                 (bsz, hv, nc, 1, dv)),
    }


def _unit_lower_inverse(a, same=None, c=None):
    """(I + a)^-1 of a strictly lower (..., c, c), as matmuls of whole
    (c, c) matrices and no solve, by blocks: the 8-wide diagonal blocks d
    first, (I + d)^-1 = (I - d)(I + d^2)(I + d^4) (d^8 = 0; four matmuls),
    then block pairs merged three times, 8 -> 16 -> 32 -> 64: with T the
    inverse of the block diagonal and L what the next size adds below it,
    (D + L)^-1 = T - T L T exactly (L T L = 0; two matmuls a level). Ten
    matmuls at c = 64, as many as the closed product
    prod_j (I + (-a)^(2^j)) takes, whose powers reach 1e14 where the keys of
    a chunk are alike and the decay slow (a prompt of one repeated token)
    and cancel to nothing in float32: PERF.md section 6, PR 38.

    `same(size)` is the mask of the pairs in one diagonal block of `size`
    (from `arange` where none is given; the kernel hands in its own, of
    2-D iotas, for several matrices of `c` rows down one diagonal)."""
    c = c or a.shape[-1]
    if same is None:
        at = jnp.arange(c)
        same = lambda size: (at[:, None] // size) == (at[None, :] // size)
    mm = lambda x, y: jnp.einsum("...ts,...sr->...tr", x, y,
                                 precision=HIGHEST)
    d = jnp.where(same(8), a, 0.0)
    d2 = mm(d, d)
    t = jnp.where(same(1), 1.0, 0.0).astype(F32) - d
    t = t + mm(t, d2)
    t = t + mm(t, mm(d2, d2))
    size = 8
    while size < c:
        below = jnp.where(same(2 * size) & ~same(size), a, 0.0)
        t = t - mm(t, mm(below, t))
        size *= 2
    return t


_TERMS = ("w", "u0", "qg", "p", "kend", "dend")
_FAR = 1 << 30    # `_pair_levels` of two positions in two matrices


def _terms_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, tri_ref, w_ref,
                  u0_ref, qg_ref, p_ref, kend_ref, dend_ref):
    """One grid cell: one sequence, one chunk of c positions, a block of
    value heads; `_chunk_terms` of that chunk with every (c, c) array in
    VMEM. The refs hold the chunk's rows as the mixer has them: q, k
    (c, key heads, dk), v (c, heads, dv), g and beta (c, ALL value heads);
    `tri_ref` is `_pair_levels`.

    The value heads of ONE key head are worked as one matrix: their A's
    down the diagonal of an (n, n) tile, n = per_key c (two of 64 rows fill
    the MXU's 128 x 128 and every lane of a register; the inverse of a
    block diagonal is the blocks' inverses, and the merges stop at c), their
    rows of K, Q, V one below the other. The cell's key heads are the BATCH
    of every product: the inverse is a chain of ten matmuls each waiting
    for the last, and the other heads' chains are what fills the wait (a
    key head at a time read 3.33 ms a 4,096-token layer, all eight side by
    side 2.15: PERF.md section 6, PR 42). Whole arrays, no loop over heads:
    the body is traced and lowered on the host once a prompt bucket, and
    an op a head made that 4 s a bucket on the chip's host."""
    heads, c, dv = u0_ref.shape
    keys, dk = k_ref.shape[1:]
    per_key = heads // keys
    n = per_key * c
    wide = max(n, dk, dv)
    tri = tri_ref[...]
    near = jnp.abs(tri)
    same = lambda size: near <= size
    low, strict = (tri >= 0) & (near < _FAR), (tri > 0) & (near < _FAR)
    g, beta = g_ref[...], beta_ref[...]
    if g.shape[1] > heads:    # this block's columns, by a 0 / 1 matmul
        at = lambda axis: lax.broadcasted_iota(
            jnp.int32, (g.shape[1], heads), axis)
        pick = (at(0) == pl.program_id(1) * heads + at(1)).astype(F32)
        g, beta = _dot(g, pick), _dot(beta, pick)
    # G, the running sum of g down the chunk: (c, heads)
    cum = _dot(low[:c, :c].astype(F32), g)
    mm = functools.partial(jnp.einsum, precision=HIGHEST,
                           preferred_element_type=F32)
    heads_first = lambda ref: jnp.swapaxes(ref[...], 0, 1)     # (h, c, d)
    tile = lambda x: jnp.concatenate([x] * per_key, 1)
    # a key head's value heads one below the other, and back
    by_key = lambda x: x.reshape(keys, n, x.shape[-1])
    by_head = lambda x: x.reshape(heads, c, x.shape[-1])
    k1, q1 = heads_first(k_ref), heads_first(q_ref)            # (keys, c, dk)
    k, q, v = tile(k1), tile(q1), by_key(heads_first(v_ref))
    # a head's column of scalars, along the lanes: (keys, n, wide)
    gc, b = (by_key(jnp.broadcast_to(x.T[:, :, None], (heads, c, wide)))
             for x in (cum, beta))
    seg = gc[..., :n] - jnp.swapaxes(gc[..., :n], 1, 2)        # G_t - G_s
    decay = jnp.where(low, jnp.exp(jnp.where(low, seg, 0.0)), 0.0)
    # q k^T over k k^T, their columns once a value head: ONE product
    both = mm("jtd,jsd->jts", jnp.concatenate([q1, k1], 1), k)
    qk, kk = tile(both[:, :c]), tile(both[:, c:])              # (keys, n, n)
    t = _unit_lower_inverse(
        jnp.where(strict, b[..., :n] * decay * kk, 0.0), same, c)
    eg = jnp.exp(gc)
    w_ref[...] = by_head(mm("jts,jsd->jtd", t, (b * eg)[..., :dk] * k))
    u0_ref[...] = by_head(mm("jts,jsd->jtd", t, b[..., :dv] * v))
    qg_ref[...] = by_head(eg[..., :dk] * q)
    dend_ref[...] = jnp.exp(jnp.broadcast_to(                  # exp(G_C)
        cum.T[:, c - 1:c][:, :, None], (heads, 1, dv)))
    # G_t - G_C is a head's last column of seg
    kend_ref[...] = by_head(jnp.exp(-jnp.broadcast_to(jnp.concatenate(
        [seg[:, r * c:(r + 1) * c, (r + 1) * c - 1:(r + 1) * c]
         for r in range(per_key)], 1), (keys, n, dk))) * k)
    # a head's c columns of p first, zeros to the last lane
    p = decay * qk
    p = by_head(jnp.stack([
        p[:, r * c:(r + 1) * c] if not r
        else pltpu.roll(p[:, r * c:(r + 1) * c], n - r * c, 2)
        for r in range(per_key)], 1).reshape(keys, n, n))
    if n >= p_ref.shape[-1]:
        p_ref[...] = p[..., :p_ref.shape[-1]]
    else:
        p_ref[:, :, :n] = p
        p_ref[:, :, n:] = jnp.zeros((heads, c, p_ref.shape[-1] - n), F32)


def _pair_levels(c: int, per_key: int):
    """(n, n) int32, n = per_key c, for the pairs (t, s) of `per_key`
    matrices of c rows down one diagonal: 0 on the diagonal, else the size
    of the smallest block of `_unit_lower_inverse` (8, 16, 32, ... up to
    the first that holds c) that holds both, `_FAR` where t and s lie in
    two matrices; negative above the diagonal. What the kernel's masks are
    compared from (as iotas, a `//` and a `%` a mask were half of the
    kernel's lowering)."""
    at = np.arange(per_key * c)
    t, s = at[:, None], at[None, :]
    level = np.full((per_key * c,) * 2, _FAR)
    size = 8
    while size < 2 * c:
        size *= 2
    while size >= 8:
        level = np.where((t // c == s // c)
                         & (t % c // size == s % c // size), size, level)
        size //= 2
    return (np.sign(t - s) * np.where(t == s, 0, level)).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _terms_call(q, k, v, g, beta, *, chunk: int, interpret: bool) -> dict:
    """`_chunk_terms` as ONE device op, `gdn_terms`. Jitted on its own: a
    model's layers ask for the same shapes, and a kernel traced and lowered
    once a program, not once a layer, is host time off its set-up."""
    bsz, l, hv, dv = v.shape
    hk, dk = k.shape[2:]
    nc, c, n = l // chunk, chunk, hv // hk * chunk
    bh, bk = _head_block(hv, hk)
    rows = lambda h, width: pl.BlockSpec((None, c, h, width),
                                         lambda i, j, t: (i, t, j, 0))
    scalars = pl.BlockSpec((None, c, hv), lambda i, j, t: (i, t, 0))
    per = lambda r, width: pl.BlockSpec(
        (None, bh, None, r, width), lambda i, j, t: (i, j, t, 0, 0))
    widths = {"w": dk, "u0": dv, "qg": dk, "p": c + -c % _LANES,
              "kend": dk, "dend": dv}
    out = pl.pallas_call(
        _terms_kernel,
        grid=(bsz, hv // bh, nc),
        in_specs=[rows(bk, dk), rows(bk, dk), rows(bh, dv), scalars,
                  scalars, pl.BlockSpec((n, n), lambda i, j, t: (0, 0))],
        out_specs=[per(1 if name == "dend" else c, widths[name])
                   for name in _TERMS],
        out_shape=[jax.ShapeDtypeStruct(
            (bsz, hv, nc, 1 if name == "dend" else c, widths[name]), F32)
            for name in _TERMS],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_SCAN_VMEM),
        interpret=interpret,
        name="gdn_terms",
    )(*(x.astype(F32) for x in (q, k, v, g, beta)), _pair_levels(c, hv // hk))
    return dict(zip(_TERMS, out))


def gdn_terms_kernel(q, k, v, g, beta, chunk: int = CHUNK) -> dict:
    """`_chunk_terms` as ONE device op, `gdn_terms` (interpret mode off the
    TPU, where only the tests call it)."""
    return _terms_call(q, k, v, g, beta, chunk=chunk,
                       interpret=not backend.on_tpu())


def _carry_chunk(s, w, u0, qg, p, kend, dend):
    """The three lines of one chunk that hold the state. s (dk, dv)."""
    u = u0 - _dot(w, s)
    rows = jnp.concatenate(   # zero rows under p's zero columns
        [u, jnp.zeros((p.shape[-1] - u.shape[0], u.shape[1]), F32)]) \
        if p.shape[-1] > u.shape[0] else u
    o = _dot(qg, s) + _dot(p, rows)
    return o, dend * s + _dot(kend, u, _TA)


def _carry_reference(terms: dict, h0):
    """The state through the chunks, a `lax.scan`: (o (b, hv, nc, C, dv),
    final state)."""
    one = jax.vmap(jax.vmap(_carry_chunk))       # over batch and heads

    def step(s, chunk):
        o, s = one(s, *chunk)
        return s, o

    final, o = lax.scan(
        step, h0, tuple(jnp.moveaxis(terms[n], 2, 0) for n in _TERMS))
    return jnp.moveaxis(o, 0, 2), final


def _scan_kernel(w_ref, u0_ref, qg_ref, p_ref, kend_ref, dend_ref, h0_ref,
                 o_ref, ho_ref, *, heads, key_decay=False):
    """Grid (sequences, head blocks, chunks), chunks innermost and in order:
    the block's state stays in VMEM from chunk to chunk (`ho_ref`, whose
    block index ignores the chunk axis, so it goes to HBM once, after the
    last chunk), seeded from `h0_ref`. `key_decay` (ops/kda.py): a head's
    `dend` is a decay a KEY lane, (1, dk), and stands as a column over the
    state's rows (`_lane_columns`)."""
    @pl.when(pl.program_id(2) == 0)
    def _seed():
        ho_ref[...] = h0_ref[...]

    pick = _column_selector(1, ho_ref.shape[-1]) if key_decay else None
    for i in range(heads):
        args = (ho_ref[i], w_ref[i], u0_ref[i], qg_ref[i], p_ref[i],
                kend_ref[i], dend_ref[i])
        if key_decay:
            args = args[:-1] + tuple(_lane_columns(args[-1:], pick))
        o, new = _carry_chunk(*args)
        o_ref[i] = o
        ho_ref[i] = new


def _carry_kernel(terms: dict, h0):
    """`_carry_reference` as ONE device op, `gdn_scan`."""
    return _carry_call(terms, h0, interpret=not backend.on_tpu())


# jitted on its own, as `_terms_call` is (six lowerings a prefill program
# before: tests/test_tpu_compile.py counts them)
@functools.partial(jax.jit, static_argnames=("interpret", "key_decay"))
def _carry_call(terms: dict, h0, *, interpret: bool, key_decay: bool = False):
    """`key_decay` (ops/kda.py): the same carry with `dend` a decay a key
    lane, as the device op `kda_scan`."""
    bsz, hv, nc, c, dv = terms["u0"].shape
    dk = terms["w"].shape[-1]
    bh = _HEADS if hv % _HEADS == 0 else hv
    per = lambda rows, width: pl.BlockSpec(
        (None, bh, None, rows, width), lambda i, j, t: (i, j, t, 0, 0))
    st = pl.BlockSpec((None, bh, dk, dv), lambda i, j, t: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, heads=bh, key_decay=key_decay),
        grid=(bsz, hv // bh, nc),
        in_specs=[per(c, dk), per(c, dv), per(c, dk),
                  per(c, terms["p"].shape[-1]), per(c, dk),
                  per(1, terms["dend"].shape[-1]), st],
        out_specs=[per(c, dv), st],
        out_shape=[jax.ShapeDtypeStruct((bsz, hv, nc, c, dv), F32),
                   jax.ShapeDtypeStruct(h0.shape, F32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SCAN_VMEM),
        interpret=interpret,
        name="kda_scan" if key_decay else "gdn_scan",
    )(*(terms[n] for n in _TERMS), h0)


def gdn_scan(q, k, v, g, beta, h0, *, chunk: int = CHUNK,
             kernel: bool | None = None):
    """The recurrence over a whole call of several tokens, FROM `h0`;
    arguments and results as `gdn_scan_reference`, by the chunked form. The
    chunks' terms and the carry through the chunks are the two Pallas
    kernels on the TPU (`kernel` None), XLA matmuls and a `lax.scan`
    elsewhere; the tests name either pair."""
    with jax.named_scope("gdn_scan"):
        l = v.shape[1]
        c = min(chunk, l)
        pad = -l % c
        ins = (q, k, v, g, beta)
        if pad:  # beta = 0, g = 0, zero q, k, v: nothing moves
            ins = tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),)
                                * (x.ndim - 2)) for x in ins)
        use = backend.on_tpu() if kernel is None else kernel
        terms = (gdn_terms_kernel if use else _chunk_terms)(*ins, c)
        o, final = (_carry_kernel if use else _carry_reference)(
            terms, h0.astype(F32))
        bsz, hv, nc, _, dv = o.shape
        o = jnp.moveaxis(o, 1, 3).reshape(bsz, nc * c, hv, dv)
        return o[:, :l], final
