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
everything as two contiguous (…, d/2) slabs — no interleaved gathers, so
XLA fuses the whole thing into the surrounding matmul's prologue.
"""

from __future__ import annotations

import jax.numpy as jnp


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
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
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
