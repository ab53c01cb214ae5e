"""Pipeline-parallel decoder LM: stage-sharded causal block stack.

The LM counterpart of PipelinedViT (models/pipeline_vit.py) — nothing
like either exists in the reference (SURVEY §2.3, "Pipeline parallel —
No"). Embedding (token table + learned positions, or RoPE inside the
blocks) and the final LN + vocab projection run outside the pipeline
under plain GSPMD; the causal EncoderBlock stack is depth-stacked,
stage-sharded over 'pipe', and scheduled by `pipeline_apply` (GPipe
microbatches over the BATCH dim — the sequence stays whole per
microbatch, so causal masking is untouched by the schedule).

Composes like the ViT pipeline: 'data' (microbatch split), 'tensor'
(Megatron specs on the stacked leaves ride GSPMD inside each stage),
'seq' (ring/Ulysses nested island inside each stage — causal ring).
Decode/KV-cache generation is NOT wired for the pipelined variant
(generate from the equivalent lm_tiny/lm_base checkpoint instead);
tied embeddings and dropout are likewise the dense family's features.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.models.vit import EncoderBlock
from ddp_practice_tpu.parallel.pipeline import pipeline_apply, stack_stages


class _LMEmbed(nn.Module):
    """Token embedding + (optionally) learned positions.

    Mirrors TransformerLM's inline embed (models/lm.py) — the layouts are
    hand-synchronized, and tests/test_pipeline_lm.py pins the numeric
    equivalence by mapping a dense param tree into this layout."""

    vocab_size: int
    max_len: int
    hidden_dim: int
    pos_emb: str = "learned"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        b, s = tokens.shape
        x = nn.Embed(
            self.vocab_size,
            self.hidden_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="tok_embed",
        )(tokens)
        if self.pos_emb == "learned":
            pos = self.param(
                "pos_embed",
                nn.initializers.normal(stddev=0.02),
                (1, self.max_len, self.hidden_dim),
                self.param_dtype,
            )
            x = x + pos[:, :s].astype(self.dtype)
        return x


class _LMHead(nn.Module):
    """Final LN + vocab projection; logits fp32.

    DELIBERATELY fp32 (unlike TransformerLM's policy-dtype logits): these
    logits cross the pipeline shard_map's masked-psum boundary
    (parallel/pipeline_1f1b.py:79), and sub-fp32 psums over manual axes
    CHECK-fail in JAX 0.9 (the workaround documented at
    pipeline_1f1b.py:36). The bf16-logit HBM saving applies only to the
    dense LM."""

    vocab_size: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(
            dtype=self.dtype, param_dtype=self.param_dtype, name="ln_f"
        )(x)
        logits = nn.Dense(
            self.vocab_size,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            use_bias=False,  # GPT-2 convention, matching TransformerLM
            name="lm_head",
        )(x)
        return logits.astype(jnp.float32)


class PipelinedLM:
    """Duck-typed model: init(rng, tokens) -> variables; apply(...)."""

    def __init__(
        self,
        *,
        vocab_size: int = 256,
        max_len: int = 2048,
        hidden_dim: int = 256,
        depth: int = 4,
        num_heads: int = 8,
        mlp_dim: int = 1024,
        dtype: jnp.dtype = jnp.float32,
        param_dtype: jnp.dtype = jnp.float32,
        num_stages: int = 1,
        num_microbatches: int = 4,
        pipe_axis: str = MeshConfig.AXIS_PIPE,
        remat: bool = True,
        pos_emb: str = "learned",
        seq_axis: Optional[str] = None,
        sp_impl: str = "ring",
        attn_impl: str = "auto",
        schedule: str = "gpipe",
        # interleaved schedule only: layer chunks per device (virtual
        # pipeline stages, Megatron-style — parallel/interleave.py)
        num_virtual: int = 2,
        axis_name: Optional[str] = None,
    ):
        if pos_emb not in ("learned", "rope"):
            raise ValueError(f"unknown pos_emb {pos_emb!r}")
        if schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"unknown schedule {schedule!r} (gpipe|1f1b|interleaved)"
            )
        n_logical = (
            num_stages * num_virtual if schedule == "interleaved"
            else num_stages
        )
        if depth % max(n_logical, 1) != 0:
            raise ValueError(
                f"depth {depth} % logical stages {n_logical} != 0"
            )
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.hidden_dim = hidden_dim
        self.depth = depth
        self.num_heads = num_heads
        self.mlp_dim = mlp_dim
        self.num_stages = num_stages
        self.num_virtual = num_virtual
        self.num_microbatches = num_microbatches
        self.pipe_axis = pipe_axis
        self.remat = remat
        self.schedule = schedule
        self.dtype = dtype
        self.embed = _LMEmbed(
            vocab_size=vocab_size,
            max_len=max_len,
            hidden_dim=hidden_dim,
            pos_emb=pos_emb,
            dtype=dtype,
            param_dtype=param_dtype,
        )
        self.block = EncoderBlock(
            num_heads, mlp_dim, dtype=dtype, param_dtype=param_dtype,
            attn_impl=attn_impl, seq_axis=seq_axis, sp_impl=sp_impl,
            causal=True, rope=pos_emb == "rope",
        )
        self.head = _LMHead(
            vocab_size=vocab_size, dtype=dtype, param_dtype=param_dtype
        )

    def init(self, rng, tokens, *, train: bool = False):
        if tokens.shape[1] > self.max_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_len {self.max_len}"
            )
        r_embed, r_blocks, r_head = jax.random.split(rng, 3)
        embed_vars = self.embed.init(r_embed, tokens)
        x = self.embed.apply(embed_vars, tokens)
        keys = jax.random.split(r_blocks, self.depth)
        block_params = jax.vmap(
            lambda k: self.block.init(k, x)["params"]
        )(keys)
        head_vars = self.head.init(r_head, x)
        return {
            "params": {
                "embed": embed_vars["params"],
                "blocks": block_params,
                "head": head_vars["params"],
            }
        }

    def apply(self, variables, tokens, *, train: bool = False, mutable=None,
              rngs=None):
        # train/rngs accepted for step-interface uniformity; the pipelined
        # blocks have no stochastic layers (dropout is a dense-LM feature)
        if tokens.shape[1] > self.max_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_len {self.max_len}"
            )
        p = variables["params"]
        x = self.embed.apply({"params": p["embed"]}, tokens)
        x = self.run_blocks(p["blocks"], x)
        out = self.head.apply({"params": p["head"]}, x)
        if mutable is not None:
            return out, {}
        return out

    def loss_and_grad(self, params, inputs, targets, *, weight=None,
                      label_smoothing: float = 0.0,
                      with_accuracy: bool = True):
        """((loss, counts), grads) via the 1F1B schedule — the train-step
        entry point when schedule='1f1b' (train/steps.py dispatches here
        instead of jax.value_and_grad; apply() stays on the GPipe forward
        for eval, where there is no backward to schedule). `counts` is
        {"correct", "total"} — accuracy pieces accumulated as SCALARS in
        the last stage's ticks; full logits are deliberately never
        materialized (an (M, mb, s, V) metrics buffer would dwarf the
        schedule's O(P) activation stash at real vocab sizes).

        Embedding runs OUTSIDE the pipeline region under plain GSPMD (its
        vjp closes the loop with the dx cotangents the schedule emits at
        stage 0); head + loss fold into the LAST stage's backward ticks
        inside parallel/pipeline_1f1b.py.
        """
        from ddp_practice_tpu.ops.losses import (
            accuracy_counts,
            cross_entropy_sum,
        )
        from ddp_practice_tpu.parallel.pipeline_1f1b import (
            pipeline_1f1b_loss_and_grad,
            pipeline_interleaved_loss_and_grad,
        )

        M = self.num_microbatches
        b, s = inputs.shape
        if b % M != 0:
            raise ValueError(f"batch {b} not divisible by microbatches {M}")
        if weight is None:
            weight = jnp.ones((b, s), jnp.float32)

        def embed_fn(ep):
            return self.embed.apply(
                {"params": ep}, inputs
            ).astype(jnp.float32)

        x, embed_vjp = jax.vjp(embed_fn, params["embed"])
        xs = x.reshape((M, b // M) + x.shape[1:])

        # honor remat here exactly like run_blocks/_sequential do: the
        # backward tick's vjp otherwise stashes every block's internals
        # (attention matrices, 4x MLP hiddens) — in the schedule whose
        # whole point is bounded activation memory
        apply_block = (
            jax.checkpoint(self.block.apply) if self.remat
            else self.block.apply
        )

        def block_fn(stage_params, xb):
            def body(h, bp):
                return apply_block({"params": bp}, h), None

            h, _ = lax.scan(body, xb, stage_params)
            return h

        def head_loss_fn(hp, y, tgt, wgt):
            logits = self.head.apply({"params": hp}, y)
            loss_sum, wsum = cross_entropy_sum(
                logits, tgt, weight=wgt, label_smoothing=label_smoothing
            )
            aux = {"weight": wsum}
            if with_accuracy:
                # the argmax is a full extra pass over the microbatch
                # logits; with_accuracy=False (the bench) drops it, same
                # contract as _lm_train_step_fn
                correct, total = accuracy_counts(logits, tgt, weight=wgt)
                aux.update(correct=correct, total=total)
            return loss_sum, aux

        if self.schedule == "interleaved":
            stages = stack_stages(
                params["blocks"], self.num_stages * self.num_virtual
            )
            loss_sum, aux, stage_grads, head_grads, dxs = (
                pipeline_interleaved_loss_and_grad(
                    block_fn,
                    head_loss_fn,
                    stages,
                    params["head"],
                    xs,
                    targets.reshape((M, b // M, s)),
                    weight.reshape((M, b // M, s)),
                    num_microbatches=M,
                    num_virtual=self.num_virtual,
                    compute_dtype=self.dtype,
                    axis_name=self.pipe_axis,
                )
            )
        else:
            stages = stack_stages(params["blocks"], self.num_stages)
            loss_sum, aux, stage_grads, head_grads, dxs = (
                pipeline_1f1b_loss_and_grad(
                    block_fn,
                    head_loss_fn,
                    stages,
                    params["head"],
                    xs,
                    targets.reshape((M, b // M, s)),
                    weight.reshape((M, b // M, s)),
                    num_microbatches=M,
                    compute_dtype=self.dtype,
                    axis_name=self.pipe_axis,
                )
            )
        denom = jnp.maximum(aux["weight"], 1.0)
        loss = loss_sum / denom
        # the schedule differentiates the loss SUM; rescale to mean-loss
        # gradients and close the embedding's own vjp with the rescaled dx
        scale = 1.0 / denom
        (embed_grads,) = embed_vjp(
            (dxs * scale).reshape(x.shape).astype(x.dtype)
        )
        unstack = jax.tree.map(
            lambda g: g.reshape((self.depth,) + g.shape[2:]), stage_grads
        )
        grads = {
            "embed": embed_grads,
            "blocks": jax.tree.map(
                lambda g, p: (g * scale).astype(p.dtype),
                unstack, params["blocks"],
            ),
            "head": jax.tree.map(
                lambda g, p: (g * scale).astype(p.dtype),
                head_grads, params["head"],
            ),
        }
        counts = (
            {"correct": aux["correct"], "total": aux["total"]}
            if with_accuracy else None
        )
        return (loss, counts), grads

    def run_blocks(self, block_params, x):
        if self.num_stages <= 1:
            return self._sequential(block_params, x)
        stages = stack_stages(block_params, self.num_stages)

        def block_fn(stage_params, xb):
            def body(h, bp):
                return self.block.apply({"params": bp}, h), None

            h, _ = lax.scan(body, xb, stage_params)
            return h

        return pipeline_apply(
            block_fn,
            stages,
            x,
            num_microbatches=self.num_microbatches,
            axis_name=self.pipe_axis,
            remat=self.remat,
        )

    def _sequential(self, block_params, x):
        # honor remat on the unpipelined path too (num_stages == 1): the
        # trainer forwards --remat here, and silently training with full
        # O(depth) activation memory would contradict the flag
        apply_block = (
            jax.checkpoint(self.block.apply) if self.remat
            else self.block.apply
        )

        def body(h, bp):
            return apply_block({"params": bp}, h), None

        h, _ = lax.scan(body, x, block_params)
        return h
