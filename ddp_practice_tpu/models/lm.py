"""Decoder-only transformer language model — the long-context flagship.

Nothing like this exists in the reference (a 2-conv MNIST CNN,
origin_main.py:9-31); this is the model family that exercises the
framework's long-context machinery at the scale it was built for:
causal attention through `ops.attention.dot_product_attention`, so one
flag each selects the Pallas flash kernel (`attn_impl="flash"`, O(seq)
training memory) and sequence parallelism over the 'seq' mesh axis
(`seq_axis=...`, ring K/V rotation or Ulysses head scatter) — the same
composition matrix as the ViTs, now with the future masked.

TPU notes: the block stack reuses `models.vit.EncoderBlock` (pre-LN,
causal=True), so the tensor-parallel PartitionSpec rules that match the
ViT param names (`parallel/sharding_rules.py`) apply unchanged. The
embedding table and the (untied) output projection both shard over
'tensor' by name. Logits are fp32 (softmax stability under bf16 compute).

Wired surfaces: `perf/run.py --workload gpt2s_train_2k` (MFU at seq 2048
on the real chip), `__graft_entry__.dryrun_multichip` (dp x sp
causal ring + flash case), `train/steps.py make_lm_train_step` (next-token
loss), `tests/test_lm.py`.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from ddp_practice_tpu.models.vit import EncoderBlock


class TransformerLM(nn.Module):
    vocab_size: int = 256           # byte-level by default
    max_len: int = 2048
    hidden_dim: int = 256
    depth: int = 4
    num_heads: int = 8
    mlp_dim: int = 1024
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None  # mesh axis for sequence parallelism
    sp_impl: str = "ring"
    attn_impl: str = "auto"         # models/vit.py SelfAttention.attn_impl
    # KV-cache storage dtype for decode: None (= compute dtype), a
    # jnp.dtype, or the string "int8" (quantized cache + scales); see
    # models/vit.py SelfAttention.kv_cache_dtype
    kv_cache_dtype: object = None
    # rematerialize each block's activations in the backward pass
    # (jax.checkpoint): trades ~1/3 more FLOPs for O(depth) less
    # activation memory — the standard long-context lever (with the
    # streaming flash kernels it makes training memory per block O(seq·d)
    # instead of O(seq·d·n_intermediates))
    remat: bool = False
    # "learned": absolute position table added to the embedding (GPT-2
    # style, tied to max_len). "rope": rotary Q/K inside every attention —
    # relative positions, the long-context default (ops/rope.py).
    pos_emb: str = "learned"
    # share the token-embedding table with the output projection (GPT-2
    # weight tying): logits = x @ tok_embed.T — removes the (d, vocab)
    # lm_head parameter. TP-consistent: tok_embed shards its vocab rows
    # over 'tensor' (sharding_rules._lm_rule), so the tied logits come out
    # vocab-sharded exactly like the untied column-parallel head.
    tied_embeddings: bool = False
    # embedding + residual-branch dropout (GPT-2 placement); never active
    # in decode mode (generation always runs deterministic)
    dropout_rate: float = 0.0
    # MoE composition: every `moe_every`-th block (GShard layout) swaps
    # its dense MLP for a routed expert MLP (ops/moe.py). 0 = dense.
    # Router health flows out as moe_* metrics (train/steps.py).
    moe_every: int = 0
    num_experts: int = 8
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # load-balance aux-loss weight: 0.01 (Switch/GShard convention) keeps
    # the warm router's drop rate ~10% on unstructured data; the bench
    # and balance test use the same knob (ops/moe.py top_k_gating)
    moe_aux_weight: float = 0.01
    # online selection-bias update rate (ops/moe.py MoEMlp
    # bias_update_rate); 0 disables the aux-free balancer
    moe_bias_rate: float = 0.02
    # tokens per routing group (0 = whole sequence); smaller groups cut
    # the dispatch einsum cost ~linearly at a measured capacity tradeoff
    # (ops/moe.py group_size)
    moe_group_size: int = 0
    moe_group_stride: bool = True
    # routing scheme: "topk" | "expert_choice" (ops/moe.py MoEMlp.router)
    moe_router: str = "topk"
    # run each block as ONE Pallas kernel per direction with causal
    # masking (ops/fused_encoder.py, round 4) — the small-d short-seq
    # HBM-bound fix, now available to decoder LMs. Training-only
    # execution strategy: params are identical to the unfused model, so
    # checkpoints generate through the normal (unfused) decode path.
    # Composes with pos_emb="learned" only (the kernel refuses rope).
    # "auto" (default, round 5) fuses when the EncoderBlock's
    # constraints hold — e.g. lm_tiny needs num_heads=4 for the 64-
    # aligned head_dim; the default heads=8 silently keeps per-op.
    fused: object = "auto"  # bool | "auto"
    axis_name: Optional[str] = None  # registry uniformity (no BN anywhere)

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 attn_start=None, page_table=None, kv_lengths=None):
        """tokens (batch, seq) int32 -> logits (batch, seq, vocab) in the
        policy compute dtype (consumers upcast — see the return comment).

        `decode=True` is KV-cache inference mode (inference.py): the call
        appends `s` tokens at the cache cursor instead of reading positions
        from zero, so the same instance serves training, prompt prefill
        (s = prompt length) and single-token generation steps (s = 1).
        Initialize the cache collection by calling `init`/`eval_shape` with
        a max-generation-length input and `decode=True`.

        `attn_start` (b,) int32, decode-only: first real (non-pad) key
        position per sequence — the variable-length-prompt mask for
        LEFT-padded batches (inference.py). Requires pos_emb="rope":
        rotary scores depend only on relative offsets, so a uniform left
        shift is invisible; a learned absolute table would silently
        misplace every real token, so that combination raises.

        `page_table` (b, max_blocks_per_slot) + `kv_lengths` (b,) int32,
        decode-only: paged KV-cache mode (serve/kv_pages.py) — the cache
        collection holds a pool of fixed-size blocks, each sequence
        writes/attends at its OWN slot-local position through its page
        table row, and attn_start/positions are slot-local. Requires
        pos_emb="rope" (per-slot offsets). s == 1 is the decode step;
        s > 1 is the paged PREFILL (prefix-cache admissions append a
        prompt suffix at kv_lengths, attending the shared prefix blocks
        through the table — models/vit.py `_paged_decode`).
        """
        if page_table is not None and self.pos_emb != "rope":
            raise ValueError(
                "paged decode needs pos_emb='rope' — per-slot positions "
                "require relative position encoding"
            )
        if page_table is not None and not decode:
            raise ValueError("page_table is a KV-cache decode feature")
        if attn_start is not None and self.pos_emb != "rope":
            raise ValueError(
                "variable-length (left-padded) prompts need pos_emb='rope' "
                "— learned absolute positions would shift with the padding"
            )
        if attn_start is not None and not decode:
            raise ValueError(
                "attn_start is a KV-cache decode feature (inference.py); "
                "the training forward has no left-padding mask"
            )
        b, s = tokens.shape
        if s > self.max_len:
            raise ValueError(f"sequence {s} exceeds max_len {self.max_len}")
        embed = nn.Embed(
            self.vocab_size,
            self.hidden_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="tok_embed",
        )
        x = embed(tokens)
        if self.pos_emb not in ("learned", "rope"):
            raise ValueError(
                f"unknown pos_emb {self.pos_emb!r} (want 'learned'|'rope')"
            )
        if self.pos_emb == "learned":
            pos = self.param(
                "pos_embed",
                nn.initializers.normal(stddev=0.02),
                (1, self.max_len, self.hidden_dim),
                self.param_dtype,
            )
            if decode:
                # the position cursor mirrors the attention caches' write
                # index (they advance in lockstep; this one lives at the top
                # level so the embedding lookup doesn't reach into a block's
                # variables)
                pos_index = self.variable(
                    "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
                )
                if self.is_initializing():
                    x = x + pos[:, :s].astype(self.dtype)
                else:
                    from jax import lax

                    p = lax.dynamic_slice(
                        pos, (0, pos_index.value, 0), (1, s, self.hidden_dim)
                    )
                    x = x + p.astype(self.dtype)
                    pos_index.value = pos_index.value + s
            else:
                x = x + pos[:, :s].astype(self.dtype)
        # rope: positions enter inside each attention (the blocks' caches
        # already track the decode cursor; nothing to add at the embedding)
        x = nn.Dropout(
            self.dropout_rate, deterministic=not (train and not decode)
        )(x)
        # remat only matters for the training backward pass; the decode path
        # mutates cache variables, which jax.checkpoint must not wrap. The
        # (decode, train) call args are static under remat (argnums 2, 3 —
        # self is 0), so dropout composes with rematerialization.
        block_cls = (
            nn.remat(EncoderBlock, static_argnums=(2, 3))
            if (self.remat and not decode)
            else EncoderBlock
        )
        for i in range(self.depth):
            block_moe = (
                self.moe_every > 0
                and i % self.moe_every == self.moe_every - 1
            )
            block = block_cls(
                self.num_heads,
                self.mlp_dim,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                seq_axis=self.seq_axis,
                sp_impl=self.sp_impl,
                attn_impl=self.attn_impl,
                causal=True,
                rope=self.pos_emb == "rope",
                kv_cache_dtype=self.kv_cache_dtype,
                dropout_rate=self.dropout_rate,
                use_moe=block_moe,
                num_experts=self.num_experts,
                moe_top_k=self.moe_top_k,
                capacity_factor=self.capacity_factor,
                moe_aux_weight=self.moe_aux_weight,
                moe_bias_rate=self.moe_bias_rate,
                moe_group_size=self.moe_group_size,
                moe_group_stride=self.moe_group_stride,
                moe_router=self.moe_router,
                # tri-state pass-through ("auto" must survive; `and` would
                # collapse it to a bool). decode always takes the per-op
                # KV-cache path; routed blocks can never fuse (the kernel
                # has no expert dispatch), so a forced fused=True means
                # "fuse every DENSE block" rather than raising on the
                # MoE-interleaved layout
                fused=False if (decode or block_moe) else self.fused,
                name=f"block{i}",
            )
            # positional (decode, train): nn.remat's static_argnums are
            # positional indices. Dropout never fires in decode mode —
            # generation is deterministic whatever the caller passes.
            # attn_start only rides the decode path (remat never applies
            # there, so the array kwarg never meets jax.checkpoint).
            if decode and (attn_start is not None or page_table is not None):
                x = block(x, True, False, attn_start=attn_start,
                          page_table=page_table, kv_lengths=kv_lengths)
            else:
                x = block(x, decode, train and not decode)
        x = nn.LayerNorm(
            dtype=self.dtype, param_dtype=self.param_dtype, name="ln_f"
        )(x)
        if self.tied_embeddings:
            logits = embed.attend(x)  # x @ tok_embed.T, no lm_head param
        else:
            # bias-free, the GPT-2 convention — and not only cosmetics:
            # the bias GRADIENT is a full rowsum pass over the
            # (tokens, V) dlogits tensor, 1.4 ms/step of pure HBM reads
            # at lm_base/32k-vocab (round-4 profile), for a learned
            # per-class log-prior offset that GPT-family models train
            # fine without
            logits = nn.Dense(
                self.vocab_size,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                use_bias=False,
                name="lm_head",
            )(x)
        # logits stay in the policy compute dtype: at LM vocab sizes an
        # fp32 logit tensor is gigabytes of HBM traffic per step (~5% of
        # the lm_base step, round-4 profile), and every consumer
        # (ops.losses cross-entropy, inference.sample_logits) upcasts
        # per-element inside its own fused reductions. This mirrors the
        # reference's autocast semantics exactly: its model emits
        # half-precision logits and nn.CrossEntropyLoss upcasts
        # (origin_main.py autocast block).
        return logits


def LMTiny(**kw):
    """Test-sized decoder (d=256, L=4): the LM numerics/composition pin."""
    kw.setdefault("hidden_dim", 256)
    kw.setdefault("depth", 4)
    kw.setdefault("num_heads", 8)
    kw.setdefault("mlp_dim", 1024)
    return TransformerLM(**kw)


def LMBase(**kw):
    """Bench-sized decoder (d=768, L=12, GPT-2-small shape) for the
    long-context throughput/MFU measurements (perf/ gpt2s_* cells)."""
    kw.setdefault("hidden_dim", 768)
    kw.setdefault("depth", 12)
    kw.setdefault("num_heads", 12)
    kw.setdefault("mlp_dim", 3072)
    kw.setdefault("max_len", 8192)
    return TransformerLM(**kw)
