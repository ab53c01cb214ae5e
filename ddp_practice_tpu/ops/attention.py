"""Attention ops.

No attention exists in the reference (its model is a 2-conv CNN,
origin_main.py:9-31); this implements the transformer path of the model
ladder. Two execution paths:

- fused single-device/GSPMD path: plain jnp softmax attention, fp32
  accumulation, fused by XLA onto the MXU (`_attention`). It writes the
  (b, h, s, s) scores to HBM, so the models do not take it everywhere:
  `attn_impl="auto"` (models/vit.py SelfAttention.resolve_attn_impl)
  hands short unsharded sequences on a TPU to the whole-sequence Pallas
  kernels (ops/flash_attention.py flash_short_qkv) before they reach
  this module, and resolves to "xla" (this path) everywhere else;
  "flash" names the streaming kernels for long sequences.
- sequence-parallel path: `parallel.ring.ring_attention` — blockwise
  attention with online softmax, K/V blocks rotated around the 'seq' mesh
  axis with `lax.ppermute` (ring attention; long-context first-class).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp

from ddp_practice_tpu.utils import backend

# Decode query-broadcast tuning, measured on TPU v5e (2026-07-30 profile,
# BENCHMARKS.md decode section): 8 = the sublane width (smallest MXU row
# tile); b <= 16 because at larger batches the batch dim already feeds
# the vector units and the 8x score/prob tensors cost more than the
# matvec saves (measured 2x SLOWER at bs 64). Other chips may warrant
# different values — they are constants, not hardware-derived.
_Q8_ROWS = 8
_Q8_MAX_BATCH = 16


def dot_product_attention(
    q: jnp.ndarray,  # (batch, seq, heads, head_dim)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    seq_axis: Optional[str] = None,
    sp_impl: str = "ring",
    impl: str = "xla",
) -> jnp.ndarray:
    """Multi-head attention; dispatches to a sequence-parallel scheme when
    `seq_axis` names a mesh axis the sequence dimension is sharded over:
    "ring" (K/V rotation, extreme lengths) or "ulysses" (all-to-all head
    scatter, maximally fused local attention). `impl` picks the local
    kernel: "xla" (fused by the XLA compiler) or "flash" (the Pallas
    tiled online-softmax kernel, ops.flash_attention) — and composes with
    both sequence-parallel schemes (flash runs as the per-block local
    attention inside ring, and as the full-sequence attention after
    Ulysses' head scatter)."""
    if impl not in ("xla", "flash"):
        raise ValueError(f"unknown attention impl {impl!r} (want 'xla'|'flash')")
    if seq_axis is not None:
        if sp_impl == "ring":
            from ddp_practice_tpu.parallel.ring import ring_attention

            return ring_attention(
                q, k, v, axis_name=seq_axis, causal=causal, impl=impl
            )
        if sp_impl == "ulysses":
            from ddp_practice_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention(
                q, k, v, axis_name=seq_axis, causal=causal, impl=impl
            )
        raise ValueError(f"unknown sp_impl {sp_impl!r} (want 'ring'|'ulysses')")
    if impl == "flash":
        from ddp_practice_tpu.ops.flash_attention import flash_attention
        from ddp_practice_tpu.parallel.ring import BSHD_SPEC, kernel_island

        return kernel_island(
            functools.partial(flash_attention, causal=causal),
            in_specs=(BSHD_SPEC,) * 3, out_specs=BSHD_SPEC,
        )(q, k, v)
    return _attention(q, k, v, causal=causal)


def attention_with_mask(q, k, v, mask) -> jnp.ndarray:
    """Attention under an explicit boolean mask (True = attend).

    `mask` broadcasts against scores (b, h, sq, sk); a 2D (sq, sk) mask is
    promoted. This is the KV-cache decode path (models/vit.py SelfAttention
    `decode=True`): the query block sits at a dynamic offset inside a
    pre-allocated key/value buffer, so validity is position arithmetic, not
    a static triangle.
    """
    if mask.ndim == 2:
        mask = mask[None, None]
    if (
        q.shape[1] == 1
        and q.shape[0] <= _Q8_MAX_BATCH
        and backend.on_tpu()
    ):
        # small-batch single-token decode steps: a 1-row query makes both
        # attention contractions matvecs, which XLA lowers to VPU
        # multiply-reduce loop fusions at ~1/5 of HBM bandwidth — 81% of
        # the decode step in the bs=8 profile (BENCHMARKS.md).
        # Since round 4 the hot single-token path uses the packed Pallas
        # decode kernel (ops/decode_attention.py) instead; this broadcast
        # remains for unpackable head shapes. Skipped on the CPU backend,
        # where there is no MXU and the 8x score/prob inflation was never
        # measured to pay for itself (tests still pin the branch's
        # numerics by calling _q8_attention directly).
        return _q8_attention(q, k, v, mask)
    return _attention(q, k, v, causal=False, mask=mask)


def _q8_attention(q, k, v, mask) -> jnp.ndarray:
    """Single-token attention with the query broadcast to _Q8_ROWS
    sublane rows so both contractions are real MXU matmuls; rows 1..n
    compute the identical result and are discarded — FLOPs are free in a
    bandwidth-bound decode step."""
    q8 = jnp.broadcast_to(q, (q.shape[0], _Q8_ROWS) + q.shape[2:])
    return _attention(q8, k, v, causal=False, mask=mask)[:, :1]


def _attention(q, k, v, *, causal: bool, mask=None) -> jnp.ndarray:
    in_dtype = q.dtype
    head_dim = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    # (b, s, h, d) -> scores (b, h, sq, sk), accumulate in fp32
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        tri = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(tri, scores, jnp.asarray(-1e30, scores.dtype))
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    probs = jnp.exp(
        scores - jnp.max(scores, axis=-1, keepdims=True)
    )
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(in_dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(in_dtype)
