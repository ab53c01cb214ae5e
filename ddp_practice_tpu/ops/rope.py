"""Rotary position embeddings (RoPE, Su et al.) — relative positions for
the LM family's long-context work.

No counterpart in the reference (a CNN; no sequence axis anywhere,
origin_main.py:9-31). Learned absolute positions (models/lm.py pos_embed)
tie the model to max_len at train time; RoPE encodes position as a
rotation of each query/key pair so attention scores depend only on
relative offsets — the standard choice for long-context decoders and the
variant that composes with the framework's sequence-parallel schemes for
free: applied to Q/K *before* attention, the rotation is baked into the
tensors, so ring K/V blocks travel with their positions and Ulysses'
head scatter never sees positions at all.

TPU notes: angles are computed in fp32 (bf16 loses position resolution
past a few thousand tokens) and cast back; the rotate-half layout keeps
everything as two contiguous (…, d/2) slabs — no interleaved gathers.
`apply_rope` on (b, s, h, d) does NOT fuse into the projection's matmul:
a 64- or 32-wide minor dimension wastes the 128 lanes, so XLA lays every
such 4-D activation out sequence-minor, rotates it in fusions of its own
and pays a relayout copy wherever a row-major consumer (a Pallas kernel,
the cache) follows; the cotangents make the same detour in float32
(compiled for a v5e at lm_base's shape: PERF.md section 6, PR 33).
Decode, serving and every sharded-head path live with that, on small
tensors. The training block does not: `rope_flat_qk` / `rope_flat_bwd`
below rotate the FLAT (b, s, h*d) rows of the qkv projection in one
element-wise Pallas pass each way, 128-lane tiles at a time, in
`apply_rope`'s own arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.utils import backend

_LANES = 128


def _angles(positions, half: int, theta: float):
    """float32 (..., half) rotation angles of int positions (...)."""
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    theta: float = 10000.0,
    interleaved: bool = False,
) -> jnp.ndarray:
    """Rotate (b, s, h, d) by per-position angles; positions is (s,) int,
    or (b, s) int when sequences sit at different absolute offsets (the
    paged KV cache decodes every slot at its OWN write position —
    serve/kv_pages.py — so the batch no longer shares one cursor).

    GPT-NeoX rotate-half convention: channel pairs are (i, i + d/2);
    `interleaved` pairs (2i, 2i + 1) instead (a `rope_interleave` config:
    models/mla_lm.py). A model that rotates only part of a head passes
    that part (its own `d`, so the frequencies are the part's).
    Under GSPMD jit the model sees the GLOBAL sequence, so callers pass
    `arange(s)` (+ the KV-cache cursor when decoding); inside a hand-built
    shard_map over the sequence the caller must add its shard offset.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    half = d // 2
    angles = _angles(positions, half, theta)                   # (..., half)
    if angles.ndim == 2:        # (s, half): shared across the batch
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    elif angles.ndim == 3:      # (b, s, half): per-sequence offsets
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    else:
        raise ValueError(
            f"positions must be (s,) or (b, s), got ndim {positions.ndim}"
        )
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# Rotary on the flat rows of the qkv projection (training, heads packed).
#
# A 128-lane tile of a (rows, h*d) activation holds 128 // d whole heads
# (one head's d lanes where d >= 128), so rotate-half never leaves a
# chunk of w = max(d, 128) lanes: the partner of lane l is l + d/2 in a
# head's first half and l - d/2 in its second, a lane roll each way and
# a select (lanes that wrapped around the chunk are never the selected
# ones). With tables that repeat cos over both halves and carry the sign
# in sin ([-sin | +sin] a head) the rotation is x * cos + partner * sin:
# x1*cos - x2*sin and x2*cos + x1*sin, apply_rope's float32
# multiply-adds term for term, then its one cast. The transpose of a
# rotation is the rotation by the negated angle, so the backward is the
# same pass with the sine negated.
# --------------------------------------------------------------------- #

# rows a grid step: the forward holds 2 inputs + 2 outputs of (rows, h*d)
# two deep, the backward 3 inputs + one (rows, 3*h*d) output: 3 MiB and
# 4.5 MiB at h*d 768 in bf16, far inside a kernel's 16 MiB
_ROPE_ROWS = 256


def _rotate_into(x_ref, o_ref, col, cos, sin, *, hd, d, w):
    """o_ref[:, col:col + hd] = the rotated hd columns of x_ref, a chunk
    of w lanes at a time (python-unrolled: static, lane-aligned slices)."""
    half = d // 2
    first = None
    if d < w:
        lane = lax.broadcasted_iota(jnp.int32, cos.shape, 1)
        first = lane % d < half
    for c in range(0, hd, w):
        x = x_ref[:, c:c + w].astype(jnp.float32)
        partner = pltpu.roll(x, w - half, 1)                # x[l + d/2]
        if first is not None:
            partner = jnp.where(first, partner,
                                pltpu.roll(x, half, 1))     # x[l - d/2]
        o_ref[:, col + c:col + c + w] = (
            x * cos + partner * sin).astype(o_ref.dtype)


def _rope_qk_kernel(cos_ref, sin_ref, q_ref, k_ref, qo_ref, ko_ref, **dims):
    cos, sin = cos_ref[:], sin_ref[:]
    _rotate_into(q_ref, qo_ref, 0, cos, sin, **dims)
    _rotate_into(k_ref, ko_ref, 0, cos, sin, **dims)


def _rope_bwd_kernel(cos_ref, sin_ref, dq_ref, dk_ref, dv_ref, o_ref, *, hd,
                     **dims):
    cos, sin = cos_ref[:], -sin_ref[:]
    _rotate_into(dq_ref, o_ref, 0, cos, sin, hd=hd, **dims)
    _rotate_into(dk_ref, o_ref, hd, cos, sin, hd=hd, **dims)
    o_ref[:, 2 * hd:] = dv_ref[:]


def _flat_dims(hd: int, n_heads: int, seq: int):
    d = hd // n_heads
    w = max(d, _LANES)
    if d % 2 or hd != n_heads * d or w % d or hd % w:
        raise ValueError(
            f"flat rotary needs whole heads a {_LANES}-lane tile: "
            f"{n_heads} heads over {hd} columns do not pack")
    rows = min(_ROPE_ROWS, seq)
    while seq % rows:       # as the flash kernels fit their blocks
        rows //= 2
    return dict(hd=hd, d=d, w=w), rows


def flat_rope_tables(positions, hd: int, n_heads: int, *,
                     theta: float = 10000.0):
    """The (cos, sin) float32 (s, w) tables `rope_flat_qk` and
    `rope_flat_bwd` take, for (s,) int positions shared by the batch:
    apply_rope's angles laid over the w lanes of a chunk of whole heads,
    sin negative over each head's first half."""
    if positions.ndim != 1:
        raise ValueError(
            f"flat rotary shares (s,) positions, got ndim {positions.ndim}")
    dims, _ = _flat_dims(hd, n_heads, positions.shape[0])
    d, w = dims["d"], dims["w"]
    angles = _angles(positions, d // 2, theta)                 # (s, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.tile(cos, (1, 2 * w // d)),
            jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, w // d)))


def _rope_call(kernel, name, tables, ins, in_blocks, out_widths, *, dims,
               rows, interpret):
    b, s, _ = ins[0].shape
    w = dims["w"]
    # sequence blocks outermost: a table block is fetched once and stays
    # for the whole batch
    table = pl.BlockSpec((rows, w), lambda i, b_: (i, 0))

    def rows_of(width, column_block=0):
        return pl.BlockSpec((None, rows, width),
                            lambda i, b_: (b_, i, column_block))

    return pl.pallas_call(
        functools.partial(kernel, **dims),
        name=name,
        grid=(s // rows, b),
        in_specs=[table, table] + [rows_of(dims["hd"], blk)
                                   for blk in in_blocks],
        out_specs=[rows_of(width) for width in out_widths],
        out_shape=[jax.ShapeDtypeStruct((b, s, width), ins[0].dtype)
                   for width in out_widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*tables, *ins)


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret"))
def _rope_flat_qk(qkv, cos, sin, *, n_heads, interpret):
    hd = qkv.shape[-1] // 3
    dims, rows = _flat_dims(hd, n_heads, qkv.shape[1])
    # q and k are column blocks 0 and 1 of the projection; v is not read
    return _rope_call(_rope_qk_kernel, "rope_flat_qk", (cos, sin),
                      (qkv, qkv), (0, 1), (hd, hd), dims=dims, rows=rows,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret"))
def _rope_flat_bwd(dq, dk, dv, cos, sin, *, n_heads, interpret):
    hd = dq.shape[-1]
    dims, rows = _flat_dims(hd, n_heads, dq.shape[1])
    return _rope_call(_rope_bwd_kernel, "rope_flat_bwd", (cos, sin),
                      (dq, dk, dv), (0, 0, 0), (3 * hd,), dims=dims,
                      rows=rows, interpret=interpret)[0]


def rope_flat_qk(qkv, cos, sin, *, n_heads: int):
    """(q', k'): the q and k column windows of the flat (b, s, 3*h*d) qkv
    projection ([q heads | k heads | v heads]) rotated as `apply_rope`
    rotates (b, s, h, d), written as two row-major (b, s, h*d) arrays in
    qkv's dtype; `cos`, `sin` from `flat_rope_tables`. One pass: 2 units
    read, 2 written."""
    return tuple(_rope_flat_qk(qkv, cos, sin, n_heads=n_heads,
                               interpret=not backend.on_tpu()))


def rope_flat_bwd(dq, dk, dv, cos, sin, *, n_heads: int):
    """The projection's cotangent, ONE flat (b, s, 3*h*d) array: the
    cotangents of q' and k' rotated back (the sine negated, the same
    float32 arithmetic and one cast) beside dv as it is. 3 units read,
    3 written, where a concatenate alone moves as much."""
    return _rope_flat_bwd(dq, dk, dv, cos, sin, n_heads=n_heads,
                          interpret=not backend.on_tpu())
