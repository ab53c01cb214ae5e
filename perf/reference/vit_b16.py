"""Plain reference for `perf/configs/vit_b16.json`.

ViT-B/16 as the configuration file runs it: 16x16 patches through a strided
convolution (written here as a matmul over flattened patches), learned
positions, 12 pre-LN blocks (full attention, tanh-GELU MLP), final LayerNorm,
MEAN POOL over the 196 patches in place of a class token (the departure the
file states), dense head. Float32, precision "highest". Images arrive uint8
and are scaled to [0, 1].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks


def forward(params, images, cfg: dict, quant=None, remat: bool = False):
    """images (b, H, W, C) uint8 or float -> logits (b, classes)."""
    x = images.astype(jnp.float32)
    if images.dtype == jnp.uint8:
        x = x / 255.0
    b, hh, ww, c = x.shape
    p = cfg["patch_size"]
    gh, gw = hh // p, ww // p
    x = x.reshape(b, gh, p, gw, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, gh * gw, p * p * c)
    emb = params["embed"]
    kernel = emb["patch_embed"]["kernel"].reshape(p * p * c, -1)
    x = blocks.mm("bsk,kd->bsd", x, kernel, quant) \
        + emb["patch_embed"]["bias"].astype(jnp.float32)
    x = x + emb["pos_embed"].astype(jnp.float32)
    eps = cfg["layer_norm_eps"]

    def one(x, blk):
        return blocks.block(x, blk, causal=False, use_rope=False, eps=eps,
                            quant=quant)

    if remat:
        one = jax.checkpoint(one)
    for i in range(cfg["num_hidden_layers"]):
        x = one(x, params[f"block{i}"])
    head = params["classifier"]
    x = jnp.mean(blocks.layer_norm(x, head["ln_f"], eps), axis=1)
    return blocks.mm("bd,dc->bc", x, head["head"]["kernel"], quant) \
        + head["head"]["bias"].astype(jnp.float32)


def loss(params, batch, cfg: dict, quant=None):
    logits = forward(params, batch["image"], cfg, quant, remat=True)
    return blocks.softmax_xent(logits, batch["label"])
