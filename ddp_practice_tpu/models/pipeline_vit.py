"""Pipeline-parallel ViT: stage-sharded encoder stack under GPipe schedule.

No pipeline parallelism exists in the reference (SURVEY §2.3). This model
partitions the ViT encoder depth across the 'pipe' mesh axis: parameters of
all blocks are stacked on a leading depth dimension (initialized with a
vmap over per-block PRNG keys), sharded stage-wise, and applied through
`parallel.pipeline.pipeline_apply` — one compiled SPMD program, activations
hopping stages via ppermute (see that module for the schedule).

Embed (patch + position) and head (LN + pool + classifier) run outside the
pipeline under plain GSPMD, replicated over 'pipe'. Composes with the
'data' axis (microbatches split the per-shard batch), with 'tensor'
(Megatron specs on the stacked block leaves ride GSPMD inside each stage
— the pipeline shard_map is manual over 'pipe'/'data' only), and with
'seq' (ring/Ulysses open a nested island over the still-automatic seq
axis inside each stage). `init`/`apply` duck-type the flax module
interface the train steps consume, so the same `make_train_step` drives
pipelined and sequential models identically.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.models.vit import EncoderBlock, ViTEmbed, ViTHead
from ddp_practice_tpu.parallel.pipeline import pipeline_apply, stack_stages


class PipelinedViT:
    """Duck-typed model: init(rng, x) -> variables; apply(variables, x)."""

    def __init__(
        self,
        *,
        num_classes: int = 10,
        patch_size: int = 4,
        hidden_dim: int = 192,
        depth: int = 12,
        num_heads: int = 3,
        mlp_dim: int = 768,
        dtype: jnp.dtype = jnp.float32,
        param_dtype: jnp.dtype = jnp.float32,
        num_stages: int = 1,
        num_microbatches: int = 4,
        pipe_axis: str = MeshConfig.AXIS_PIPE,
        remat: bool = True,
        seq_axis: Optional[str] = None,
        sp_impl: str = "ring",
        attn_impl: str = "auto",
        axis_name: Optional[str] = None,
    ):
        if depth % max(num_stages, 1) != 0:
            raise ValueError(f"depth {depth} % stages {num_stages} != 0")
        self.depth = depth
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.pipe_axis = pipe_axis
        self.remat = remat
        self.embed = ViTEmbed(
            patch_size=patch_size,
            hidden_dim=hidden_dim,
            dtype=dtype,
            param_dtype=param_dtype,
        )
        # seq_axis rides into each stage's attention: the pipeline
        # shard_map is manual over 'pipe'/'data' only, so ring/Ulysses
        # open their own nested island over the still-automatic 'seq'
        # axis (parallel/ring.py _island_mesh_and_spec) — sp x pp composes
        self.block = EncoderBlock(
            num_heads, mlp_dim, dtype=dtype, param_dtype=param_dtype,
            attn_impl=attn_impl, seq_axis=seq_axis, sp_impl=sp_impl,
        )
        self.head = ViTHead(
            num_classes=num_classes, dtype=dtype, param_dtype=param_dtype
        )

    def init(self, rng, x, *, train: bool = False):
        r_embed, r_blocks, r_head = jax.random.split(rng, 3)
        embed_vars = self.embed.init(r_embed, x)
        tokens = self.embed.apply(embed_vars, x)
        keys = jax.random.split(r_blocks, self.depth)
        block_params = jax.vmap(
            lambda k: self.block.init(k, tokens)["params"]
        )(keys)
        head_vars = self.head.init(r_head, tokens)
        return {
            "params": {
                "embed": embed_vars["params"],
                "blocks": block_params,
                "head": head_vars["params"],
            }
        }

    def apply(self, variables, x, *, train: bool = False, mutable=None,
              rngs=None):
        # rngs accepted for step-interface uniformity; unused (the
        # pipelined blocks have no stochastic layers — dropout_rate is not
        # a PipelinedViT knob, and the Trainer refuses --dropout for it)
        p = variables["params"]
        tokens = self.embed.apply({"params": p["embed"]}, x)
        tokens = self.run_blocks(p["blocks"], tokens)
        out = self.head.apply({"params": p["head"]}, tokens)
        if mutable is not None:
            return out, {}  # flax mutable-apply contract; nothing sown here
        return out

    def run_blocks(self, block_params, tokens):
        if self.num_stages <= 1:
            return self._sequential(block_params, tokens)
        stages = stack_stages(block_params, self.num_stages)

        def block_fn(stage_params, xb):
            def body(h, bp):
                return self.block.apply({"params": bp}, h), None

            h, _ = lax.scan(body, xb, stage_params)
            return h

        return pipeline_apply(
            block_fn,
            stages,
            tokens,
            num_microbatches=self.num_microbatches,
            axis_name=self.pipe_axis,
            remat=self.remat,
        )

    def _sequential(self, block_params, tokens):
        """Reference path (also used for numerics tests): same stacked
        params applied depth-sequentially without the pipeline."""

        def body(h, bp):
            return self.block.apply({"params": bp}, h), None

        h, _ = lax.scan(body, tokens, block_params)
        return h
