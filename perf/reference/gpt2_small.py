"""Plain reference for `perf/configs/gpt2_small.json`.

GPT-2-small as the configuration file runs it: token embedding, 12 pre-LN
blocks (causal attention with rotary positions in place of GPT-2's learned
table — the departure the file states — and a tanh-GELU MLP), final
LayerNorm, head tied to the embedding. Float32, precision "highest".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False):
    """tokens (b, s) int32 -> logits (b, s, vocab) float32."""
    emb = params["tok_embed"]["embedding"].astype(jnp.float32)
    x = emb[tokens]
    eps = cfg["layer_norm_epsilon"]

    def one(x, p):
        return blocks.block(x, p, causal=True, use_rope=True, eps=eps,
                            quant=quant)

    if remat:
        one = jax.checkpoint(one)
    for i in range(cfg["n_layer"]):
        x = one(x, params[f"block{i}"])
    x = blocks.layer_norm(x, params["ln_f"], eps)
    if cfg["tie_word_embeddings"]:
        return blocks.mm("bsd,vd->bsv", x, emb, quant)
    return blocks.mm("bsd,dv->bsv", x, params["lm_head"]["kernel"], quant)


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant, remat=True)
    return blocks.softmax_xent(logits, tokens[:, 1:])
