"""Kimi Delta Attention (arXiv:2510.26692): the gated delta rule of
ops/gdn.py with a decay a KEY CHANNEL. A head (one key head a value head)
keeps a state S (key_dim, value_dim) in float32:

    S  <- diag(exp(g_t)) S                   g_t <= 0, a VECTOR over key_dim
    d  =  beta_t (v_t - S^T k_t)
    S  <- S + k_t d^T
    o_t = S^T q_t

Over a chunk of C positions, G the running sum of g inside it (a vector a
position) and S0 the state entering it, `exp(G_t - G_s)` no longer leaves
the dot product over the key lanes:

    A[t,s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s < t
    T      = (I + A)^-1
    U      = T (beta V) - T (beta (exp(G) * K)) S0
    O      = (exp(G) * Q) S0 + tril(sum_c Q[t,c] K[s,c] exp(.)) U
    S_C    = diag(exp(G_C)) S0 + (exp(G_C - G) * K)^T U

The two (C, C) sums are matmuls of rows scaled about a REFERENCE: with r the
start of t's SUB-chunk of `SUB` = 16 positions (G_r the sum before its first
row), `exp(G_t - G_s) = exp(G_t - G_r) exp(G_r - G_s)`: the first factor is
at most 1, the second at most 1 for s before r and at most exp(16 |g|max)
for s inside t's sub-chunk. THE KERNEL RELIES ON |g| <= 5 (the model's
`kda_safe_gate`: g = kda_lower_bound sigmoid(.), lower bound -5): 16 x 5 =
80 < 88, float32's largest exponent; a gate without that bound needs a
shorter sub-chunk. One matmul a sub-chunk (its rows against every key row
scaled about its r), four a chunk; everything after is ops/gdn.py's: the
inverse by blocks (`_unit_lower_inverse`), the terms' names, the carry
through the chunks (`_carry_chunk`, `_carry_call`), whose end-of-chunk decay
`dend` is here a row over the key lanes (a column over the state's rows by
ops/gdn.py `_lane_columns`, once a head and chunk).

The decode step is ops/gdn.py `_step_kernel`'s arithmetic on the VPU with
THREE column tiles a head (k, q and exp(g) as `col[a, b] = x[a]`, one
(key_dim, value_dim) tile each). They come from the vectors' layout: a grid
cell's 3 x 16 vectors are transposed once, key lanes along the sublanes as
the state has them, and a tile is one lane of that, broadcast. The MXU is
not in the kernel, whose time is the state's way through HBM.

Device op names (PERF.md section 3): `kda_step` (decode), `kda_terms` and
`kda_scan` (a prompt's chunks), each under the scope of its name; off the
TPU the plain forms below run, which are also the kernels' oracles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.gdn import (
    _FAR, _LANES, _SCAN_VMEM, _TERMS, CHUNK, F32, HIGHEST, _carry_call,
    _carry_reference, _head_block, _pair_levels, _unit_lower_inverse)
from ddp_practice_tpu.utils import backend

# positions about one reference row (module docstring)
SUB = 16


def kda_step_reference(q, k, v, g, beta, state):
    """One token, plain jax.numpy. q, k (b, h, dk), normalised and scaled by
    the caller; v (b, h, dv); g (b, h, dk) the log decay; beta (b, h); state
    (b, h, dk, dv) float32. Returns (o (b, h, dv) float32, new state)."""
    q, k, v = (x.astype(F32) for x in (q, k, v))
    s = state * jnp.exp(g.astype(F32))[..., None]
    read = jnp.einsum("bhkv,bhk->bhv", s, k, precision=HIGHEST)
    d = beta.astype(F32)[..., None] * (v - read)
    s = s + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=HIGHEST), s


def kda_scan_reference(q, k, v, g, beta, h0):
    """The recurrence one position at a time (`lax.scan`): what the chunked
    form must equal. q, k, g (b, l, h, dk); v (b, l, h, dv); beta (b, l, h);
    h0 (b, h, dk, dv). Returns (o (b, l, h, dv) float32, final state)."""
    def one(state, inp):
        o_t, state = kda_step_reference(*inp, state)
        return state, o_t

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    final, os_ = lax.scan(one, h0.astype(F32), xs)
    return jnp.moveaxis(os_, 0, 1), final


# ------------------------------------------------------------- decode step
def _step_kernel(q_ref, k_ref, v_ref, da_ref, beta_ref, h_ref, o_ref, ho_ref,
                 *, heads):
    """One grid cell: one sequence, `heads` heads; ops/gdn.py `_step_kernel`'s
    arithmetic on the VPU with the decay a third column beside k and q. A
    column tile holds no arithmetic (`col[a, b] = x[a]`), so none is made by
    one: the cell's 3 x heads vectors are transposed ONCE (the XLU), key
    lanes along the sublanes as the state has them, and a head's tile is one
    lane of that broadcast along the lanes, made where it is consumed (a
    head's state and one tile are half the core's registers). Through the
    MXU (`_lane_columns`, a float32 "highest" matmul a head) the VPU waited
    for three 128-column tiles a head and the kernel read 39% of its
    roofline: PERF.md section 6, PR 48."""
    dk, dv = h_ref.shape[-2:]
    lanes = jnp.concatenate([da_ref[...], k_ref[...], q_ref[...]], 0).T
    col = lambda r, i: jnp.broadcast_to(
        lanes[:, r * heads + i:r * heads + i + 1], (dk, dv))
    for i in range(heads):
        s = h_ref[i] * col(0, i)
        kcol = col(1, i)
        read = jnp.sum(s * kcol, axis=0, keepdims=True)        # (1, dv)
        d = beta_ref[i:i + 1, :] * (v_ref[i:i + 1, :] - read)
        new = s + kcol * d
        ho_ref[i] = new
        o_ref[i:i + 1, :] = jnp.sum(new * col(2, i), axis=0, keepdims=True)


def kda_step(q, k, v, g, beta, state):
    """One token of the recurrence for every sequence; arguments and results
    as `kda_step_reference`: the Pallas kernel on the TPU, plain jax.numpy
    elsewhere."""
    with jax.named_scope("kda_step"):
        if not backend.on_tpu():
            return kda_step_reference(q, k, v, g, beta, state)
        return kda_step_kernel(q, k, v, g, beta, state)


def kda_step_kernel(q, k, v, g, beta, state):
    """`kda_step` as ONE device op of that name (interpret mode off the
    TPU, where only the tests call it)."""
    return _step_call(q, k, v, g, beta, state,
                      interpret=not backend.on_tpu())


# jitted on its own, as `_terms_call` is: a decode program's six layers ask
# for the same shapes, and the body's 357 ops are lowered once, not six times
@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, g, beta, state, *, interpret: bool):
    bsz, h, dv = v.shape
    dk = k.shape[-1]
    bh, _ = _head_block(h, h)
    key = pl.BlockSpec((None, bh, dk), lambda i, j: (i, j, 0))
    val = pl.BlockSpec((None, bh, dv), lambda i, j: (i, j, 0))
    st = pl.BlockSpec((None, bh, dk, dv), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=bh),
        grid=(bsz, h // bh),
        in_specs=[key, key, val, key, val, st],
        out_specs=[val, st],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={5: 1},     # the state is rewritten in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_step",
    )(q.astype(F32), k.astype(F32), v.astype(F32), jnp.exp(g.astype(F32)),
      jnp.broadcast_to(beta.astype(F32)[..., None], (bsz, h, dv)), state)


# ------------------------------------------------------------ prefill scan
def _chunk_terms(q, k, v, g, beta, chunk: int) -> dict:
    """ops/gdn.py `_chunk_terms` with the decay inside the two (C, C) sums,
    written as it reads (a (C, C, dk) exponent a head and chunk: the oracle,
    and the form off the TPU, where the sizes are a test's); each term
    (b, h, nc, C, .) float32 but `dend` = exp(G_C), (b, h, nc, 1, dk)."""
    bsz, l, h, dv = v.shape
    nc, c = l // chunk, chunk
    heads = lambda x: jnp.moveaxis(        # (b, l, h, d) -> (b, h, nc, C, d)
        x.astype(F32).reshape(bsz, nc, c, *x.shape[2:]), 3, 1)
    qh, kh, vh, gh = (heads(x) for x in (q, k, v, g))
    bh = heads(beta[..., None])                                # (b,h,nc,C,1)
    cum = jnp.cumsum(gh, axis=-2)                              # G, (.., C, dk)
    low = jnp.tril(jnp.ones((c, c), bool))[..., None]
    seg = cum[..., :, None, :] - cum[..., None, :, :]          # G_t - G_s
    decay = jnp.where(low, jnp.exp(jnp.where(low, seg, 0.0)), 0.0)
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    kk = mm("bhctd,bhcsd,bhctsd->bhcts", kh, kh, decay)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    t = _unit_lower_inverse(jnp.where(strict, bh * kk, 0.0))
    eg = jnp.exp(cum)
    return {
        "w": mm("bhcts,bhcsd->bhctd", t, bh * eg * kh),
        "u0": mm("bhcts,bhcsd->bhctd", t, bh * vh),
        "qg": eg * qh,
        "p": jnp.pad(mm("bhctd,bhcsd,bhctsd->bhcts", qh, kh, decay),
                     ((0, 0),) * 4 + ((0, -c % _LANES),)),
        "kend": jnp.exp(cum[..., -1:, :] - cum) * kh,
        "dend": jnp.exp(cum[..., -1:, :]),
    }


def _terms_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, tri_ref, sel_ref,
                  blk_ref, w_ref, u0_ref, qg_ref, p_ref, kend_ref, dend_ref,
                  *, pair):
    """One grid cell: one sequence, one chunk of c positions, a block of
    heads; `_chunk_terms` of that chunk with every (c, c) array in VMEM. The
    refs hold the chunk's rows as the mixer has them: q, k, g (c, heads,
    dk), v (c, heads, dv), beta (c, ALL heads); `tri_ref`, `sel_ref` and
    `blk_ref` are `_tile_constants(c, pair)`.

    `pair` heads are worked as one matrix, as ops/gdn.py `_terms_kernel`
    works a key head's value heads: their rows one below the other (n =
    pair c = 128 of them fill the MXU), their A's down the diagonal of an
    (n, n) tile, what two heads' rows give each other masked as `_FAR`; the
    tiles are the batch of every product. The decay (module docstring): a
    row's exponent about the start of its own sub-chunk is `loc`, its
    running sum INSIDE the sub-chunk; sub-chunk i's rows meet every key row
    s scaled by exp(G_r(i) - G_s), s up to the end of i, in one product a
    sub-chunk whose other rows are dropped. A row of another row's sums
    (the reference of a sub-chunk, a head's last) is a 0 / 1 matmul
    (`sel_ref`), a row's sub-chunk a constant (`blk_ref`): no index
    arithmetic and no array past three dimensions in the body."""
    heads, c, dv = u0_ref.shape
    dk = k_ref.shape[-1]
    n, tiles, nsub = pair * c, heads // pair, c // SUB
    tri = tri_ref[...]
    near = jnp.abs(tri)
    same = lambda size: near <= size
    low, strict = (tri >= 0) & (near < _FAR), (tri > 0) & (near < _FAR)
    beta = beta_ref[...]
    if beta.shape[1] > heads:    # this block's columns, by a 0 / 1 matmul
        at = lambda axis: lax.broadcasted_iota(
            jnp.int32, (beta.shape[1], heads), axis)
        pick = (at(0) == pl.program_id(1) * heads + at(1)).astype(F32)
        beta = lax.dot_general(beta, pick, (((1,), (0,)), ((), ())),
                               precision=HIGHEST, preferred_element_type=F32)
    mm = functools.partial(jnp.einsum, precision=HIGHEST,
                           preferred_element_type=F32)
    # a tile's heads one below the other, (tiles, n, d), and back
    by_tile = lambda ref: jnp.swapaxes(ref[...], 0, 1).reshape(
        tiles, n, ref.shape[-1])
    by_head = lambda x: x.reshape(heads, c, x.shape[-1])
    k, q, v, g = (by_tile(r) for r in (k_ref, q_ref, v_ref, g_ref))
    b = jnp.broadcast_to(beta.T[:, :, None], (heads, c, max(dk, dv, n))
                         ).reshape(tiles, n, -1)
    # rows' sums under a 0 / 1 (n, n) matrix, the same for every tile
    rows_of = lambda m, x: mm("jts,jsd->jtd", jnp.broadcast_to(
        m.astype(F32), (tiles, n, n)), x)
    # G = base + loc: the sum before a row's sub-chunk, and inside it
    loc, base = rows_of(low & same(SUB), g), rows_of(low & (near > SUB), g)
    cum = base + loc
    blk = blk_ref[...]
    inside = jnp.exp(loc)
    rows = jnp.concatenate([q * inside, k * inside], 1)    # (tiles, 2n, dk)
    blk_out = jnp.concatenate([blk, blk], 0)[:, :n]
    both = jnp.zeros((tiles, 2 * n, n), F32)
    for i in range(nsub):
        ref = rows_of(sel_ref[i], base)      # G before sub-chunk i, a head
        keys = k * jnp.exp(jnp.where(blk[:, :dk] <= i, ref - cum, 0.0))
        both = jnp.where(blk_out == i, mm("jtd,jsd->jts", rows, keys), both)
    qk, kk = both[:, :n], both[:, n:]
    t = _unit_lower_inverse(
        jnp.where(strict, b[..., :n] * kk, 0.0), same, c)
    eg = jnp.exp(cum)
    w_ref[...] = by_head(mm("jts,jsd->jtd", t, b[..., :dk] * eg * k))
    u0_ref[...] = by_head(mm("jts,jsd->jtd", t, b[..., :dv] * v))
    qg_ref[...] = by_head(eg * q)
    last = rows_of(sel_ref[nsub], cum)                     # G_C, a head
    dend_ref[...] = jnp.exp(by_head(last)[:, :1])
    kend_ref[...] = by_head(jnp.exp(last - cum) * k)
    # a head's c columns of p first, zeros to the last lane
    p = jnp.where(low, qk, 0.0)
    p = by_head(jnp.stack([
        p[:, r * c:(r + 1) * c] if not r
        else pltpu.roll(p[:, r * c:(r + 1) * c], n - r * c, 2)
        for r in range(pair)], 1).reshape(tiles, n, n))
    if n >= p_ref.shape[-1]:
        p_ref[...] = p[..., :p_ref.shape[-1]]
    else:
        p_ref[:, :, :n] = p
        p_ref[:, :, n:] = jnp.zeros((heads, c, p_ref.shape[-1] - n), F32)


def _tile_constants(c: int, pair: int, wide: int):
    """What the kernel's masks and row picks are read from, for `pair`
    matrices of c rows down one diagonal (n = pair c): ops/gdn.py
    `_pair_levels`; `sel` (c / SUB + 1, n, n) float32 0 / 1, `sel[i] @ x`
    row t = x's first row of sub-chunk i in t's matrix, `sel[-1] @ x` its
    last row there; `blk` (n, wide) int32, a row's sub-chunk along the
    lanes."""
    n, nsub = pair * c, c // SUB
    at = np.arange(n)
    sel = np.zeros((nsub + 1, n, n), np.float32)
    for i in range(nsub):
        sel[i, at, at // c * c + i * SUB] = 1.0
    sel[nsub, at, at // c * c + c - 1] = 1.0
    blk = np.broadcast_to((at % c // SUB)[:, None], (n, wide))
    return _pair_levels(c, pair), sel, np.ascontiguousarray(blk, np.int32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _terms_call(q, k, v, g, beta, *, chunk: int, interpret: bool) -> dict:
    """`_chunk_terms` as ONE device op, `kda_terms`; jitted on its own, as
    ops/gdn.py `_terms_call` is."""
    bsz, l, h, dv = v.shape
    dk = k.shape[-1]
    nc, c = l // chunk, chunk
    if c % SUB:
        raise ValueError(f"a chunk of {c} positions is not whole sub-chunks "
                         f"of {SUB}")
    bh, _ = _head_block(h, h)
    pair = 2 if bh % 2 == 0 else 1
    consts = _tile_constants(c, pair, max(pair * c, dk))
    rows = lambda width: pl.BlockSpec((None, c, bh, width),
                                      lambda i, j, t: (i, t, j, 0))
    per = lambda r, width: pl.BlockSpec(
        (None, bh, None, r, width), lambda i, j, t: (i, j, t, 0, 0))
    widths = {"w": dk, "u0": dv, "qg": dk, "p": c + -c % _LANES,
              "kend": dk, "dend": dk}
    out = pl.pallas_call(
        functools.partial(_terms_kernel, pair=pair),
        grid=(bsz, h // bh, nc),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                  pl.BlockSpec((None, c, h), lambda i, j, t: (i, t, 0)),
                  *(pl.BlockSpec(x.shape, lambda i, j, t, r=x.ndim: (0,) * r)
                    for x in consts)],
        out_specs=[per(1 if name == "dend" else c, widths[name])
                   for name in _TERMS],
        out_shape=[jax.ShapeDtypeStruct(
            (bsz, h, nc, 1 if name == "dend" else c, widths[name]), F32)
            for name in _TERMS],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_SCAN_VMEM),
        interpret=interpret,
        name="kda_terms",
    )(*(x.astype(F32) for x in (q, k, v, g, beta)), *consts)
    return dict(zip(_TERMS, out))


def kda_terms_kernel(q, k, v, g, beta, chunk: int = CHUNK) -> dict:
    """`_chunk_terms` as ONE device op, `kda_terms` (interpret mode off the
    TPU, where only the tests call it)."""
    return _terms_call(q, k, v, g, beta, chunk=chunk,
                       interpret=not backend.on_tpu())


def kda_scan(q, k, v, g, beta, h0, *, chunk: int = CHUNK,
             kernel: bool | None = None):
    """The recurrence over a whole call of several tokens, FROM `h0`;
    arguments and results as `kda_scan_reference`, by the chunked form. The
    chunks' terms and the carry through the chunks are the two Pallas
    kernels on the TPU (`kernel` None), XLA matmuls and a `lax.scan`
    elsewhere; the tests name either pair."""
    l = v.shape[1]
    c = min(chunk, -(-l // SUB) * SUB)     # whole sub-chunks
    pad = -l % c
    ins = (q, k, v, g, beta)
    if pad:  # beta = 0, g = 0, zero q, k, v: nothing moves
        ins = tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),)
                            * (x.ndim - 2)) for x in ins)
    use = backend.on_tpu() if kernel is None else kernel
    with jax.named_scope("kda_terms"):
        terms = (kda_terms_kernel if use else _chunk_terms)(*ins, c)
    with jax.named_scope("kda_scan"):
        if use:
            o, final = _carry_call(terms, h0.astype(F32), key_decay=True,
                                   interpret=not backend.on_tpu())
        else:   # `dend`'s row stands as a column over the state's rows
            o, final = _carry_reference(dict(terms, dend=jnp.swapaxes(
                terms["dend"], -1, -2)), h0.astype(F32))
    bsz, h, nc, _, dv = o.shape
    o = jnp.moveaxis(o, 1, 3).reshape(bsz, nc * c, h, dv)
    return o[:, :l], final
