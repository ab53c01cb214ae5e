"""A decoder with multi-head latent attention and gated experts: the
DeepSeek-V3 layout (`create_model("deepseek_v3", ...)`).

    x = x + attn_i(RMSNorm(x));  x = x + ffn_i(RMSNorm(x))     every layer
    ffn_i   a SwiGLU MLP for the first `first_dense` layers, then
            `GatedMoE` (ops/moe.py): sigmoid router over all experts,
            top-k, a shared SwiGLU expert
    logits = RMSNorm(x) W_head           untied, over `vocab_size` rows

Attention (`LatentAttention`) keeps ONE row a token and layer instead of
a K and a V row a head:

    q = h W_q                      a head: [q_nope (nope) | q_rope (rope)]
    [c | k_rope] = h W_kv_a;       c = RMSNorm(c), `latent_dim` wide
    q_rope, k_rope = RoPE(., pos)  one k_rope for all heads
    [k_nope_h | v_h] = c W_kv_b    expanded from the latent when needed
    score_h(t, s) = (q_nope_h . k_nope_h(s) + q_rope_h . k_rope(s))
                    / sqrt(nope + rope),  causal softmax, out W_o

What is cached is `[c | k_rope]`, padded with zeros to whole 128-lane
tiles (576 -> 640 at the published widths): the leaf `cached_latent`,
(batch, max_len, row) flat or (num_blocks, block_size, row) as pages
(serve/kv_pages.py takes either by the leaf's rank, as a K or V leaf).
Two paths read it:

- several tokens (training, a prompt's prefill, a suffix or chunk
  appended through pages): UN-absorbed. K and V are expanded from the
  cached rows and attention is the usual masked softmax
  (`ops/attention.py`); through pages the span is folded a block of
  1,024 positions at a time, up to the last query's block and no
  further (`_span_attention`).
- one token a sequence through pages (a decode step): ABSORBED.
  q~_h = q_nope_h (W_kv_b^K,h)^T is `latent_dim` wide, so the score is
  [q~_h | q_rope_h] . row and the output (sum_s p row(s)[:latent])
  W_kv_b^V,h: multi-query attention over the rows themselves, ONE
  kernel walking each slot's live pages
  (ops/decode_attention.py `paged_decode_mla`).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

from ddp_practice_tpu.models.hybrid_lm import RMSNorm
from ddp_practice_tpu.ops.attention import _attention, attention_with_mask
from ddp_practice_tpu.ops.decode_attention import paged_decode_mla
from ddp_practice_tpu.ops.moe import GatedMLP, GatedMoE
from ddp_practice_tpu.ops.rope import apply_rope

_LANES = 128
# Cached positions a block of the several-token paged path expands and scores
# at a time (`LatentAttention._span_attention`).
_SPAN_TOKENS = 1024


class LatentAttention(nn.Module):
    num_heads: int
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    latent_dim: int = 512
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @property
    def row_width(self) -> int:
        """Lanes of a cached row: latent + rope, in whole lane tiles."""
        return -(-(self.latent_dim + self.rope_dim) // _LANES) * _LANES

    def _expand(self, rows, kv_b):
        """Cached rows (b, s, row) -> K (b, s, h, nope + rope) and V
        (b, s, h, v): every position's keys and values from its latent."""
        lat, r = self.latent_dim, self.rope_dim
        kv = jnp.einsum("bsl,lhe->bshe", rows[..., :lat], kv_b,
                        preferred_element_type=jnp.float32
                        ).astype(rows.dtype)
        k_rope = jnp.broadcast_to(
            rows[:, :, None, lat:lat + r],
            rows.shape[:2] + (self.num_heads, r))
        return (jnp.concatenate([kv[..., :self.nope_dim], k_rope], axis=-1),
                kv[..., self.nope_dim:])

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 page_table=None, kv_lengths=None):
        b, s, d = x.shape
        h, lat, r = self.num_heads, self.latent_dim, self.rope_dim
        cd = self.dtype
        kw = dict(use_bias=False, dtype=cd, param_dtype=self.param_dtype)
        q = nn.DenseGeneral((h, self.nope_dim + r), name="q", **kw)(x)
        kv_a = nn.Dense(lat + r, name="kv_a", **kw)(x)
        c = RMSNorm(self.norm_eps, cd, self.param_dtype,
                    name="kv_norm")(kv_a[..., :lat])
        kv_b = self.param(
            "kv_b", nn.initializers.normal(0.02),
            (lat, h, self.nope_dim + self.v_dim), self.param_dtype
        ).astype(cd)
        paged = page_table is not None
        cached = index = None
        if decode:
            if paged and (kv_lengths is None or self.is_initializing()):
                raise ValueError(
                    "a paged call needs kv_lengths, and its pools come "
                    "from serve/kv_pages.py make_paged_cache")
            cached = self.variable("cache", "cached_latent", jnp.zeros,
                                   (b, s, self.row_width), cd)
            # tree parity with the other models' caches: the flat layout's
            # cursor; a block pool has no clock and leaves it alone
            index = self.variable("cache", "cache_index",
                                  lambda: jnp.zeros((), jnp.int32))
        live = decode and not self.is_initializing()
        if paged:
            pos0 = jnp.asarray(kv_lengths, jnp.int32)
            positions = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)
        else:
            positions = (index.value if live else 0) + jnp.arange(s)
        rope = dict(theta=self.rope_theta, interleaved=self.rope_interleave)
        q_rope = apply_rope(q[..., self.nope_dim:], positions, **rope)
        k_rope = apply_rope(kv_a[:, :, None, lat:], positions, **rope)[:, :, 0]
        q = jnp.concatenate([q[..., :self.nope_dim], q_rope], axis=-1)
        rows = jnp.concatenate(
            [c, k_rope, jnp.zeros((b, s, self.row_width - lat - r), cd)],
            axis=-1)
        if not live:
            out = _attention(q, *self._expand(rows, kv_b), causal=True)
        elif not paged:
            cur, span = index.value, cached.value.shape[1]
            cached.value = lax.dynamic_update_slice(
                cached.value, rows.astype(cached.value.dtype), (0, cur, 0))
            index.value = cur + s
            kpos = jnp.arange(span)
            mask = kpos[None, :] <= positions[:, None]          # (s, span)
            if attn_start is not None:
                mask = mask[None] & (kpos[None, None, :]
                                     >= attn_start[:, None, None])
                mask = mask[:, None]                    # (b, 1, s, span)
            out = attention_with_mask(
                q, *self._expand(cached.value, kv_b), mask)
        else:
            pool = cached.value
            bs = pool.shape[1]
            # the clamp keeps a retired slot (page row 0, length pinned)
            # writing inside the table, as in models/vit.py _paged_decode
            col = jnp.minimum(positions // bs, page_table.shape[1] - 1)
            blk = jnp.take_along_axis(page_table, col, axis=1)
            pool = pool.at[blk, positions % bs].set(rows.astype(pool.dtype))
            cached.value = pool
            if s == 1:
                out = self._absorbed_step(q[:, 0], kv_b, pool, page_table,
                                          pos0, attn_start)[:, None]
            else:
                out = self._span_attention(q, kv_b, pool, page_table,
                                           positions, attn_start)
        return nn.DenseGeneral(d, axis=(-2, -1), name="out", **kw)(out)

    def _span_attention(self, q, kv_b, pool, page_table, positions,
                        attn_start):
        """Several tokens a slot against the slot's pages, un-absorbed:
        q (b, s, h, nope + rope) at slot-local `positions` (b, s) ->
        (b, s, h, v). The span is taken `_SPAN_TOKENS` at a time, K and V
        expanded from each block of rows and folded into a running
        softmax, from the block of the first position any row may see to
        the block of the last query and NO further: a chunk at position
        2,048 of an 8,960-position table scores 3 blocks, not 9, and the
        (h, s, span) scores are never whole in memory."""
        b, s, h, _ = q.shape
        bs, mb = pool.shape[1], page_table.shape[1]
        pages = max(1, min(_SPAN_TOKENS // bs, mb))
        tile = pages * bs
        n_blocks = -(-mb // pages)
        table = jnp.pad(page_table, ((0, 0), (0, n_blocks * pages - mb)))
        start = jnp.zeros((b,), jnp.int32) if attn_start is None \
            else jnp.asarray(attn_start, jnp.int32)
        first = jnp.min(start) // tile
        last = jnp.minimum(jnp.max(positions) // tile, n_blocks - 1)
        scale = 1.0 / (q.shape[-1] ** 0.5)

        def block(j, carry):
            m, l, acc = carry
            cols = lax.dynamic_slice(table, (0, j * pages), (b, pages))
            rows = jnp.take(pool, cols, axis=0).reshape(b, tile, -1)
            k, v = self._expand(rows.astype(q.dtype), kv_b)
            kpos = j * tile + jnp.arange(tile, dtype=jnp.int32)
            seen = (kpos[None, None, :] <= positions[:, :, None]) \
                & (kpos[None, None, :] >= start[:, None, None])
            seen = seen[:, None]                          # (b, 1, s, tile)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) * scale
            m_new = jnp.maximum(m, jnp.max(
                jnp.where(seen, scores, -1e30), axis=-1, keepdims=True))
            p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(q.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        m0 = jnp.full((b, h, s, 1), -1e30, jnp.float32)
        _, l, acc = lax.fori_loop(
            first, last + 1, block,
            (m0, jnp.zeros_like(m0),
             jnp.zeros((b, h, s, self.v_dim), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)
        return jnp.swapaxes(out, 1, 2).astype(q.dtype)

    def _absorbed_step(self, q, kv_b, pool, page_table, pos0, attn_start):
        """q (b, h, nope + rope) of one token a slot -> (b, h, v)."""
        n, lat = self.nope_dim, self.latent_dim
        q_abs = jnp.einsum("bhn,lhn->bhl", q[..., :n], kv_b[..., :n],
                           preferred_element_type=jnp.float32
                           ).astype(q.dtype)
        pad = self.row_width - lat - self.rope_dim
        q_row = jnp.concatenate(
            [q_abs, q[..., n:], jnp.zeros(q.shape[:2] + (pad,), q.dtype)],
            axis=-1).astype(pool.dtype)
        ctx = paged_decode_mla(
            q_row, pool, page_table, pos0, attn_start, v_lanes=lat,
            sm_scale=1.0 / (n + self.rope_dim) ** 0.5)
        return jnp.einsum("bhl,lhv->bhv", ctx, kv_b[..., n:],
                          preferred_element_type=jnp.float32).astype(q.dtype)


class MLALM(nn.Module):
    vocab_size: int = 256
    hidden_dim: int = 64
    num_layers: int = 3
    max_len: int = 32768
    # attention
    num_heads: int = 4
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    latent_dim: int = 32
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    # the first `first_dense` layers: a SwiGLU MLP of width `mlp_dim`
    first_dense: int = 1
    mlp_dim: int = 128
    # the others: gated experts
    num_experts: int = 16
    top_k: int = 3
    expert_dim: int = 48
    shared_dim: int = 96
    experts_held: int = 16
    expert_offset: int = 0
    routed_scaling: float = 1.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # what the serving engines ask a model (models/hybrid_lm.py)
    pos_emb: str = "rope"
    recurrent: bool = False
    axis_name: Optional[str] = None  # registry uniformity

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 attn_start=None, page_table=None, kv_lengths=None):
        """tokens (batch, seq) int32 -> logits (batch, seq, vocab_size) in
        the compute dtype. `decode`, `attn_start`, `page_table` and
        `kv_lengths` as in models/lm.py TransformerLM."""
        del train
        if (page_table is not None or attn_start is not None) and not decode:
            raise ValueError("page_table / attn_start are decode features")
        if tokens.shape[1] > self.max_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_len {self.max_len}")
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        x = nn.Embed(self.vocab_size, self.hidden_dim, name="tok_embed",
                     **kw)(tokens)
        for i in range(self.num_layers):
            y = RMSNorm(self.norm_eps, name=f"norm_attn{i}", **kw)(x)
            x = x + LatentAttention(
                self.num_heads, self.nope_dim, self.rope_dim, self.v_dim,
                self.latent_dim, self.rope_theta, self.rope_interleave,
                self.norm_eps, name=f"attn{i}", **kw,
            )(y, decode=decode, attn_start=attn_start,
              page_table=page_table, kv_lengths=kv_lengths)
            y = RMSNorm(self.norm_eps, name=f"norm_ffn{i}", **kw)(x)
            if i < self.first_dense:
                y = GatedMLP(self.mlp_dim, name=f"mlp{i}", **kw)(y)
            else:
                y = GatedMoE(
                    self.num_experts, self.top_k, self.expert_dim,
                    self.shared_dim, self.experts_held, self.expert_offset,
                    self.routed_scaling, name=f"moe{i}", **kw,
                )(y, decode=decode)
            x = x + y
        x = RMSNorm(self.norm_eps, name="norm_f", **kw)(x)
        return nn.Dense(self.vocab_size, use_bias=False, name="lm_head",
                        **kw)(x)
