"""ViT-MoE: Vision Transformer with mixture-of-experts MLP blocks.

The expert-parallel rung of the model ladder (no MoE anywhere in the
reference — SURVEY §2.3). Every `moe_every`-th encoder block swaps its
dense MLP for `ops.moe.MoEMlp`: top-k routed experts stacked on a leading
dim sharded over the 'expert' mesh axis, dispatch/combine lowered to
all-to-alls by GSPMD. Attention blocks are the standard ones (TP/SP
compose as in plain ViT).
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ddp_practice_tpu.models.vit import EncoderBlock, ViTEmbed, ViTHead


class ViTMoE(nn.Module):
    num_classes: int = 10
    patch_size: int = 4
    hidden_dim: int = 192
    depth: int = 12
    num_heads: int = 3
    mlp_dim: int = 768
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_every: int = 2               # every 2nd block is MoE (GShard layout)
    # routing scheme: "topk" | "expert_choice" (ops/moe.py MoEMlp.router)
    moe_router: str = "topk"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None
    sp_impl: str = "ring"
    attn_impl: str = "auto"
    axis_name: Optional[str] = None  # registry uniformity (no BN)

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = ViTEmbed(
            patch_size=self.patch_size,
            hidden_dim=self.hidden_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="embed",
        )(x)
        for i in range(self.depth):
            # the one shared dense/MoE block swap (models/vit.py
            # EncoderBlock use_moe) — identical submodule names keep
            # existing vit_tiny_moe param trees valid
            x = EncoderBlock(
                self.num_heads,
                self.mlp_dim,
                num_experts=self.num_experts,
                moe_top_k=self.top_k,
                capacity_factor=self.capacity_factor,
                moe_router=self.moe_router,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                seq_axis=self.seq_axis,
                sp_impl=self.sp_impl,
                attn_impl=self.attn_impl,
                use_moe=(i % self.moe_every == self.moe_every - 1),
                name=f"block{i}",
            )(x, False, train)
        return ViTHead(
            num_classes=self.num_classes,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="classifier",
        )(x)
