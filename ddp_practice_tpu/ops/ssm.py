"""State-space mixer arithmetic: a prompt's scan and the one-token recurrence
of decode, for Mamba-2 (one decay a head) and Mamba-1 (one a channel and
state: the second half of this file).

Mamba-2 (state-space duality) first.

One recurrence, two forms. Per head (head size p, state size n, the head's
group supplying B and C):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h: (p, n), float32
    y_t = h_t C_t + D x_t

`ssm_scan` computes it over a whole sequence by the chunked (SSD) form:
inside a chunk the outputs are one masked (chunk x chunk) product a head,
between chunks only the (p, n) state is carried, so a prompt costs
matmuls and `len / chunk` sequential steps. `ssm_step` is the recurrence
itself for one token a sequence; on the TPU it is the Pallas kernel
`ssm_step`, one pass over the state (the state is what a decode step of
such a model mostly moves: 4 B x p x n a head, read and written).

A position with `dt == 0` moves nothing: the decay is exp(0) and the input
term vanishes. That is how left padding and the tail of a partial chunk
are made invisible (models/hybrid_lm.py masks `dt` and the conv input).

Device op names (PERF.md section 3): the kernel is `ssm_step`; the scan is
XLA ops under `jax.named_scope("ssm_scan")`; Mamba-1's two kernels are
`sel_step` and `sel_scan`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.ops.flash_attention import _dot_ta, _dot_tb
from ddp_practice_tpu.utils import backend


def causal_conv(xbc, tail, weight, bias, real_lengths=None):
    """Depthwise causal conv over time. xbc (b, l, c); `tail` (b, k-1, c)
    the k-1 inputs before position 0 (zeros for a fresh sequence); weight
    (k, c), bias (c,) or None. Returns (out (b, l, c), new tail
    (b, k-1, c)): the last k-1 inputs, or with `real_lengths` (b,) those
    before position real_lengths[b] (the rest of the row is right
    padding)."""
    k = weight.shape[0]
    seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    l = xbc.shape[1]
    out = sum(seq[:, i:i + l] * weight[i].astype(xbc.dtype)
              for i in range(k))
    if bias is not None:
        out = out + bias.astype(xbc.dtype)
    if real_lengths is None:
        return out, seq[:, l:]
    return out, jax.vmap(lambda row, at: lax.dynamic_slice_in_dim(
        row, at, k - 1, axis=0))(seq, real_lengths.astype(jnp.int32))


def _heads_of_groups(v, heads: int):
    """(b, ..., g, n) -> (b, ..., heads, n): a group serves heads/g heads."""
    g = v.shape[-2]
    return jnp.repeat(v, heads // g, axis=-2)


def ssm_scan(x, dt, a, b_mat, c_mat, d_skip, h0, *, chunk: int):
    """The chunked scan. x (b, l, h, p); dt (b, l, h) already positive
    (0 at masked positions); a (h,) negative; b_mat, c_mat (b, l, g, n);
    d_skip (h,); h0 (b, h, p, n) float32. Returns (y (b, l, h, p) float32,
    final state (b, h, p, n) float32)."""
    with jax.named_scope("ssm_scan"):
        return _ssm_scan(x, dt, a, b_mat, c_mat, d_skip, h0, chunk=chunk)


def _ssm_scan(x, dt, a, b_mat, c_mat, d_skip, h0, *, chunk):
    f32 = jnp.float32
    bsz, l, h, p = x.shape
    g = b_mat.shape[2]
    q = min(chunk, l)
    pad = -l % q
    x, dt, b_mat, c_mat = (v.astype(f32) for v in (x, dt, b_mat, c_mat))
    if pad:  # dt = 0 there: neither state nor any real output moves
        x, dt, b_mat, c_mat = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_mat, c_mat))
    nc = (l + pad) // q
    xd = (x * dt[..., None]).reshape(bsz, nc, q, h, p)
    da = (dt * a.astype(f32)).reshape(bsz, nc, q, h)
    bm = b_mat.reshape(bsz, nc, q, g, -1)
    cm = c_mat.reshape(bsz, nc, q, g, -1)
    cum = jnp.cumsum(da, axis=2)                        # (b, nc, q, h)
    # inside a chunk: y_l += sum_{s<=l} exp(cum_l - cum_s) (C_l . B_s) xd_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cm, bm)       # (b, nc, g, q, q)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (b, nc, l, s, h)
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    w = decay * jnp.moveaxis(jnp.repeat(cb, h // g, axis=2), 2, -1)
    y = jnp.einsum("bclsh,bcshp->bclhp", w, xd)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)           # (b, nc, q, h)
    bh = _heads_of_groups(bm, h)                        # (b, nc, q, h, n)
    add = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", bh, to_end, xd)
    whole = jnp.exp(cum[:, :, -1, :])                   # (b, nc, h)

    def carry(state, inp):
        add_c, whole_c = inp
        return whole_c[..., None, None] * state + add_c, state

    final, entering = lax.scan(
        carry, h0.astype(f32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # (b, nc, h, p, n)
    ch = _heads_of_groups(cm, h)
    y = y + jnp.einsum("bcqhn,bchpn,bcqh->bcqhp", ch, entering,
                       jnp.exp(cum))
    y = y.reshape(bsz, nc * q, h, p)[:, :l]
    return y + x[:, :l] * d_skip.astype(f32)[None, None, :, None], final


def ssm_scan_sequential(x, dt, a, b_mat, c_mat, d_skip, h0):
    """The recurrence one position at a time (`lax.scan`): what the
    chunked form must equal. Tests only."""
    f32 = jnp.float32

    def one(state, inp):
        x_t, dt_t, b_t, c_t = inp
        y_t, state = ssm_step_reference(
            x_t, dt_t, a, b_t, c_t, d_skip, state)
        return state, y_t

    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0)
               for v in (x, dt, b_mat, c_mat))
    final, ys = lax.scan(one, h0.astype(f32), xs)
    return jnp.moveaxis(ys, 0, 1), final


def ssm_step_reference(x, dt, a, b_mat, c_mat, d_skip, state):
    """One token, plain jax.numpy. x (b, h, p); dt (b, h); b_mat, c_mat
    (b, g, n); state (b, h, p, n) float32. Returns (y (b, h, p) float32,
    new state)."""
    f32 = jnp.float32
    h = x.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    bh = _heads_of_groups(b_mat.astype(f32), h)          # (b, h, n)
    ch = _heads_of_groups(c_mat.astype(f32), h)
    decay = jnp.exp(dt * a.astype(f32))[..., None, None]
    state = decay * state + (dt[..., None] * x)[..., None] * bh[:, :, None]
    y = jnp.einsum("bhpn,bhn->bhp", state, ch)
    return y + x * d_skip.astype(f32)[None, :, None], state


# bytes of state a grid cell of `ssm_step` moves each way at most: as many
# whole groups as fit and divide `g`, one where a group alone is larger. What
# `kda_step` and `gdn_step` move a cell. Mamba-2 at 128 slots x 8 groups of 16
# heads of (64, 128), 512 KB a group, alone on a v5e, ms a call and share of
# the state's bytes at 819 GB/s (PERF.md section 6, PR 51): 1 group a cell
# 1.887 (69.5%), 2 groups 1.666 (78.7%), 4 groups 1.637 (80.1%), all 8 (4 MiB)
# 1.640 (79.9%). Lightning attention (a group ONE 64 KB head, all fixed cost
# alone) fills the budget with 16 of its 32: 0.2085 ms, and 32 a cell are no
# faster (0.2087) while their 32 unrolled heads take the decode program 0.9 s
# longer to build at every start.
_STEP_BYTES = 2**20


def _step_kernel(da_ref, xd_ref, b_ref, c_ref, h_ref, y_ref, ho_ref, *,
                 groups, heads):
    """One grid cell: one sequence, `groups` groups of `heads` heads. The
    state tile of a head is (p, n): its update is `decay * h + xd (x) B` with
    the outer product as a depth-8 matmul (row 0 real, seven rows of zeros:
    a column vector is not a layout the lanes hold), its output `C . h`
    one matmul against the new tile."""
    n = h_ref.shape[-1]
    row0 = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0
    for j in range(groups):
        b8 = jnp.where(row0, jnp.broadcast_to(b_ref[j], (8, n)), 0.0)
        c8 = jnp.broadcast_to(c_ref[j], (8, n))
        for i in range(heads):
            x8 = jnp.where(row0, jnp.broadcast_to(
                xd_ref[j, i:i + 1, :], (8, xd_ref.shape[-1])), 0.0)
            new = h_ref[j, i] * da_ref[j, i:i + 1, :] + _dot_ta(x8, b8)
            ho_ref[j, i] = new                                  # (p, n)
            y_ref[j, i:i + 1, :] = _dot_tb(c8, new)[:1]


def ssm_step(x, dt, a, b_mat, c_mat, d_skip, state):
    """One token of the recurrence for every sequence; same arguments and
    results as `ssm_step_reference`: the Pallas kernel on the TPU, plain
    jax.numpy elsewhere."""
    if not backend.on_tpu():
        with jax.named_scope("ssm_step"):
            return ssm_step_reference(x, dt, a, b_mat, c_mat, d_skip, state)
    return ssm_step_kernel(x, dt, a, b_mat, c_mat, d_skip, state)


def ssm_step_kernel(x, dt, a, b_mat, c_mat, d_skip, state):
    """`ssm_step` as ONE device op of that name (interpret mode off the
    TPU, where only the tests call it)."""
    f32 = jnp.float32
    bsz, h, p = x.shape
    g, n = b_mat.shape[1:]
    hg = h // g
    x, dt = x.astype(f32), dt.astype(f32)
    # a head's decay, laid along the lanes so the kernel broadcasts it
    # down the sublanes (8 MB a call at 128 x 128 heads: under 1% of the
    # state's bytes)
    da = jnp.broadcast_to(
        jnp.exp(dt * a.astype(f32))[..., None], (bsz, h, n)
    ).reshape(bsz, g, hg, n)
    xd = (x * dt[..., None]).reshape(bsz, g, hg, p)
    group = hg * 4 * p * n              # bytes of one group's state
    gb = max(k for k in range(1, g + 1)
             if g % k == 0 and k * group <= max(_STEP_BYTES, group))
    grp = lambda last: pl.BlockSpec((None, gb, hg, last),
                                    lambda i, j: (i, j, 0, 0))
    vec = pl.BlockSpec((None, gb, 1, n), lambda i, j: (i, j, 0, 0))
    st = pl.BlockSpec((None, gb, hg, p, n), lambda i, j: (i, j, 0, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_step_kernel, groups=gb, heads=hg),
        grid=(bsz, g // gb),
        in_specs=[grp(n), grp(p), vec, vec, st],
        out_specs=[grp(p), st],
        out_shape=[jax.ShapeDtypeStruct((bsz, g, hg, p), f32),
                   jax.ShapeDtypeStruct((bsz, g, hg, p, n), f32)],
        input_output_aliases={4: 1},     # the state is rewritten in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=not backend.on_tpu(),
        name="ssm_step",
    )(da, xd, b_mat.astype(f32)[:, :, None, :],
      c_mat.astype(f32)[:, :, None, :],
      state.reshape(bsz, g, hg, p, n))
    y = y.reshape(bsz, h, p) + x * d_skip.astype(f32)[None, :, None]
    return y, new.reshape(bsz, h, p, n)


# ------------------------------------------------ Mamba-1: selective scan
# The decay differs for every (channel, state) pair:
#
#     h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
#     y_t[c]    = sum_n h_t[c, n] C_t[n] + D[c] u_t[c]
#
# so no matmul form carries it: both kernels are element-wise over the
# state, the exponential computed inside (a decay tensor in HBM would
# double the state's traffic). A sequence's state is laid out
# (n, channels / 128, 128): for one n, a block of 1024 channels is ONE
# dense (8, 128) register, B_t[n] and C_t[n] are scalars (SMEM), and the
# sum over n is a tree of multiply-adds between whole registers: no
# broadcast along lanes, no reduction across sublanes, no masked store.
# dt, u and y take the same (channels / 128, 128) layout a position.

_LANES = 128
_SUBLANES = 8
# time steps a grid cell of the scan carries the state through
SEL_CHUNK = 128
# steps of the time loop in one basic block: Mosaic overlaps the loads, the
# exponentials and the multiply-add chain inside a block, not across the
# steps of a loop (PERF.md section 6, PR 27)
_SEL_UNROLL = 4
# positions one call of the scan kernel takes
_SEL_CALL_TOKENS = 2048
# most slots a grid cell of the decode kernel takes (2 MB of state in, 2 MB
# out, two deep: half of the 16 MiB a kernel gets)
_SEL_SLOTS = 32


def sel_state_shape(batch: int, channels: int, state: int) -> tuple:
    """Shape of the float32 state `sel_step` / `sel_scan` carry."""
    if channels % _LANES:
        raise ValueError(
            f"the selective scan lays {channels} channels out in rows of "
            f"{_LANES}: want a multiple")
    return (batch, state, channels // _LANES, _LANES)


def sel_step_reference(u, dt, a, b_mat, c_mat, d_skip, state):
    """One token, plain jax.numpy. u, dt (b, c); a (c, n) negative; b_mat,
    c_mat (b, n); d_skip (c,); state as `sel_state_shape`. Returns
    (y (b, c) float32, new state)."""
    f32 = jnp.float32
    bsz, ch = u.shape
    u, dt = u.astype(f32), dt.astype(f32)
    h = state.reshape(bsz, -1, ch)                       # (b, n, c)
    decay = jnp.exp(dt[:, None, :] * a.astype(f32).T[None])
    h = decay * h + (dt * u)[:, None, :] * b_mat.astype(f32)[:, :, None]
    y = jnp.sum(h * c_mat.astype(f32)[:, :, None], axis=1)
    return y + u * d_skip.astype(f32), h.reshape(state.shape)


def sel_scan_reference(u, dt, a, b_mat, c_mat, d_skip, h0):
    """The recurrence one position at a time (`lax.scan`). u, dt (b, l, c);
    b_mat, c_mat (b, l, n); h0 as `sel_state_shape`. Returns (y (b, l, c)
    float32, final state)."""
    def one(state, inp):
        y_t, state = sel_step_reference(
            inp[0], inp[1], a, inp[2], inp[3], d_skip, state)
        return state, y_t

    xs = tuple(jnp.moveaxis(v.astype(jnp.float32), 1, 0)
               for v in (u, dt, b_mat, c_mat))
    final, ys = lax.scan(one, h0.astype(jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), final


def _sel_update(h, dt, dtu, a, b_of, c_of):
    """One position of one channel block. h, a: a tile a state index n;
    dt, dtu: the block's tile of dt and dt * u; b_of(n), c_of(n): scalars.
    Returns (new h tiles, y tile)."""
    new = [jnp.exp(dt * a[i]) * h[i] + dtu * b_of(i) for i in range(len(h))]
    terms = [new[i] * c_of(i) for i in range(len(h))]
    while len(terms) > 1:   # a tree, not a chain of len(h) dependent adds
        terms = [terms[i] + terms[i + 1] if i + 1 < len(terms) else terms[i]
                 for i in range(0, len(terms), 2)]
    return new, terms[0]


def _sel_step_kernel(b_ref, c_ref, dt_ref, u_ref, a_ref, h_ref, y_ref,
                     ho_ref, *, slots, n):
    """Grid (channel blocks, slot blocks): `slots` sequences' state of one
    channel block, each read once and written once, in place."""
    base = pl.program_id(1) * slots
    a = [a_ref[i] for i in range(n)]

    def one(s, carry):
        dt = dt_ref[s]
        row = (base + s) * n
        new, y = _sel_update(
            [h_ref[s, i] for i in range(n)], dt, dt * u_ref[s], a,
            lambda i: b_ref[row + i], lambda i: c_ref[row + i])
        for i in range(n):
            ho_ref[s, i] = new[i]
        y_ref[s] = y
        return carry

    lax.fori_loop(0, slots, one, 0)


def _sel_scan_kernel(b_ref, c_ref, dt_ref, u_ref, a_ref, h0_ref, y_ref,
                     ho_ref, *, chunk, n):
    """Grid (sequences, channel blocks, time chunks), time innermost and in
    order: the block's state stays in VMEM from chunk to chunk (`ho_ref`,
    whose block index ignores the time axis, so it goes to HBM once, after
    the last chunk) and in registers inside one, seeded from `h0_ref`."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _seed():
        ho_ref[...] = h0_ref[...]

    a = [a_ref[i] for i in range(n)]

    def some(g, h):
        for j in range(_SEL_UNROLL):
            t = g * _SEL_UNROLL + j
            dt = dt_ref[t]
            h, y = _sel_update(
                h, dt, dt * u_ref[t], a,
                lambda i: b_ref[k, t * n + i], lambda i: c_ref[k, t * n + i])
            y_ref[t] = y
        return tuple(h)

    h = lax.fori_loop(0, chunk // _SEL_UNROLL, some,
                      tuple(ho_ref[i] for i in range(n)))
    for i in range(n):
        ho_ref[i] = h[i]


def _sel_rows(channels: int) -> tuple:
    """(rows of 128 channels, rows a channel block)."""
    rows = channels // _LANES
    return rows, (_SUBLANES if rows % _SUBLANES == 0 else rows)


def _sel_a(a, rows: int):
    """a (c, n) -> (n, rows, 128), the state's layout."""
    return a.astype(jnp.float32).T.reshape(a.shape[1], rows, _LANES)


def sel_step(u, dt, a, b_mat, c_mat, d_skip, state):
    """One token of the recurrence for every sequence; arguments and results
    as `sel_step_reference`: the Pallas kernel on the TPU, plain jax.numpy
    elsewhere."""
    if not backend.on_tpu():
        with jax.named_scope("sel_step"):
            return sel_step_reference(u, dt, a, b_mat, c_mat, d_skip, state)
    return sel_step_kernel(u, dt, a, b_mat, c_mat, d_skip, state)


@jax.jit
def sel_step_kernel(u, dt, a, b_mat, c_mat, d_skip, state):
    """`sel_step` as ONE device op of that name (interpret mode off the
    TPU, where only the tests call it)."""
    f32 = jnp.float32
    bsz, ch = u.shape
    n = a.shape[1]
    rows, sub = _sel_rows(ch)
    slots = next(s for s in range(min(_SEL_SLOTS, bsz), 0, -1)
                 if bsz % s == 0)
    u, dt = u.astype(f32), dt.astype(f32)
    tile = lambda v: v.reshape(bsz, rows, _LANES)
    vec = pl.BlockSpec((slots, sub, _LANES), lambda j, i, *_: (i, j, 0))
    st = pl.BlockSpec((slots, n, sub, _LANES), lambda j, i, *_: (i, 0, j, 0))
    y, new = pl.pallas_call(
        functools.partial(_sel_step_kernel, slots=slots, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # B and C: 2 x b x n scalars
            grid=(rows // sub, bsz // slots),
            in_specs=[vec, vec,
                      pl.BlockSpec((n, sub, _LANES),
                                   lambda j, i, *_: (0, j, 0)),
                      st],
            out_specs=[vec, st],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, rows, _LANES), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={5: 1},     # the state is rewritten in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=not backend.on_tpu(),
        name="sel_step",
    )(b_mat.astype(f32).reshape(-1), c_mat.astype(f32).reshape(-1),
      tile(dt), tile(u), _sel_a(a, rows), state)
    return y.reshape(bsz, ch) + u * d_skip.astype(f32), new


def sel_scan(u, dt, a, b_mat, c_mat, d_skip, h0):
    """The recurrence over a whole call of several tokens, FROM `h0`;
    arguments and results as `sel_scan_reference`: the Pallas kernel on the
    TPU, the sequential `lax.scan` elsewhere. A position with dt == 0
    leaves the state as it was."""
    if not backend.on_tpu():
        with jax.named_scope("sel_scan"):
            return sel_scan_reference(u, dt, a, b_mat, c_mat, d_skip, h0)
    # a call's B and C stand whole in SMEM (128 B a position): a longer
    # sequence goes through the kernel a stretch at a time
    ys, state = [], h0
    for s in range(0, u.shape[1], _SEL_CALL_TOKENS):
        cut = slice(s, s + _SEL_CALL_TOKENS)
        y, state = sel_scan_kernel(u[:, cut], dt[:, cut], a, b_mat[:, cut],
                                   c_mat[:, cut], d_skip, state)
        ys.append(y)
    return (ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)), state


@functools.partial(jax.jit, static_argnames=("chunk",))
def sel_scan_kernel(u, dt, a, b_mat, c_mat, d_skip, h0, *,
                    chunk: int = SEL_CHUNK):
    """`sel_scan` as ONE device op of that name (interpret mode off the
    TPU, where only the tests call it)."""
    f32 = jnp.float32
    bsz, l, ch = u.shape
    n = a.shape[1]
    rows, sub = _sel_rows(ch)
    q = min(chunk, -(-l // _SEL_UNROLL) * _SEL_UNROLL)
    pad = -l % q
    u, dt, b_mat, c_mat = (v.astype(f32) for v in (u, dt, b_mat, c_mat))
    ins = (u, dt, b_mat, c_mat)
    if pad:  # dt = 0 there: the state does not move
        ins = tuple(jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in ins)
    nc = (l + pad) // q
    up, dtp, bp, cp = ins
    tile = lambda v: v.reshape(bsz, nc * q, rows, _LANES)
    scal = lambda v: v.reshape(bsz, nc, q * n)
    vec = pl.BlockSpec((None, q, sub, _LANES), lambda i, j, k: (i, k, j, 0))
    # a sequence's B and C whole (a block in SMEM obeys the (8, 128) rule
    # too, so a chunk's share cannot be cut out): 8 B x n a position
    smem = pl.BlockSpec((None, nc, q * n), lambda i, j, k: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    st = pl.BlockSpec((None, n, sub, _LANES), lambda i, j, k: (i, 0, j, 0))
    y, final = pl.pallas_call(
        functools.partial(_sel_scan_kernel, chunk=q, n=n),
        grid=(bsz, rows // sub, nc),
        in_specs=[smem, smem, vec, vec,
                  pl.BlockSpec((n, sub, _LANES), lambda i, j, k: (0, j, 0)),
                  st],
        out_specs=[vec, st],
        out_shape=[jax.ShapeDtypeStruct((bsz, nc * q, rows, _LANES), f32),
                   jax.ShapeDtypeStruct(h0.shape, f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not backend.on_tpu(),
        name="sel_scan",
    )(scal(bp), scal(cp), tile(dtp), tile(up), _sel_a(a, rows),
      h0.astype(f32))
    y = y.reshape(bsz, nc * q, ch)[:, :l]
    return y + u * d_skip.astype(f32), final
