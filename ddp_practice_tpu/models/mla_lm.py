"""A decoder with multi-head latent attention and gated experts: the
DeepSeek-V3 layout (`create_model("deepseek_v3", ...)`).

    x = x + attn_i(RMSNorm(x));  x = x + ffn_i(RMSNorm(x))     every layer
    ffn_i   a SwiGLU MLP for the first `first_dense` layers, then
            `GatedMoE` (ops/moe.py): sigmoid router over all experts,
            top-k, a shared SwiGLU expert
    logits = RMSNorm(x) W_head           untied, over `vocab_size` rows

Attention (`LatentAttention`, models/hybrid_lm.py: the class `HybridLM`
runs as its sub-layer 'T') keeps ONE row a token and layer instead of a K
and a V row a head:

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

from ddp_practice_tpu.models.hybrid_lm import LatentAttention, RMSNorm
from ddp_practice_tpu.ops.moe import GatedMLP, GatedMoE


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
