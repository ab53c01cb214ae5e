"""Mamba-2 (state-space duality) mixer arithmetic: a prompt's chunked scan
and the one-token recurrence of decode.

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
XLA ops under `jax.named_scope("ssm_scan")`.
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


def causal_conv(xbc, tail, weight, bias):
    """Depthwise causal conv over time. xbc (b, l, c); `tail` (b, k-1, c)
    the k-1 inputs before position 0 (zeros for a fresh sequence); weight
    (k, c), bias (c,). Returns (out (b, l, c), new tail (b, k-1, c))."""
    k = weight.shape[0]
    seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    l = xbc.shape[1]
    out = sum(seq[:, i:i + l] * weight[i].astype(xbc.dtype)
              for i in range(k))
    return out + bias.astype(xbc.dtype), seq[:, l:]


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


def _step_kernel(da_ref, xd_ref, b_ref, c_ref, h_ref, y_ref, ho_ref, *,
                 heads):
    """One grid cell: one sequence, one group's `heads` heads. The state
    tile of a head is (p, n): its update is `decay * h + xd (x) B` with the
    outer product as a depth-8 matmul (row 0 real, seven rows of zeros:
    a column vector is not a layout the lanes hold), its output `C . h`
    one matmul against the new tile."""
    n = h_ref.shape[-1]
    row0 = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0
    b8 = jnp.where(row0, jnp.broadcast_to(b_ref[...], (8, n)), 0.0)
    c8 = jnp.broadcast_to(c_ref[...], (8, n))
    for i in range(heads):
        x8 = jnp.where(row0, jnp.broadcast_to(
            xd_ref[i:i + 1, :], (8, xd_ref.shape[-1])), 0.0)
        new = h_ref[i] * da_ref[i:i + 1, :] + _dot_ta(x8, b8)   # (p, n)
        ho_ref[i] = new
        y_ref[i:i + 1, :] = _dot_tb(c8, new)[:1]


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
    grp = lambda last: pl.BlockSpec((None, None, hg, last),
                                    lambda i, j: (i, j, 0, 0))
    vec = pl.BlockSpec((None, None, 1, n), lambda i, j: (i, j, 0, 0))
    st = pl.BlockSpec((None, None, hg, p, n), lambda i, j: (i, j, 0, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=hg),
        grid=(bsz, g),
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
