"""Vision Transformer, TPU-first flax.linen implementation.

Not in the reference (no attention anywhere, origin_main.py:9-31); this is
the BASELINE.json transformer rung ("ViT-Tiny on CIFAR-10, pjit DP") and the
flagship model for sharded training: its parameter names line up with the
tensor-parallel sharding rules in `ddp_practice_tpu/parallel/sharding_rules.py`
(attention QKV/out projections and MLP in/out projections shard over the
'tensor' mesh axis), and its attention can run under sequence parallelism via
`ddp_practice_tpu.parallel.ring.ring_attention`.

TPU notes: everything is batched matmul (MXU-friendly); attention uses the
framework's own `ops.attention` (switchable between a fused jnp path and the
ring path); compute dtype policy-driven (bf16), logits fp32.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ddp_practice_tpu.config import MeshConfig

from ddp_practice_tpu.ops.attention import dot_product_attention
from ddp_practice_tpu.ops.rope import apply_rope


class ViTEmbed(nn.Module):
    """Patch + position embedding stem (shared by ViT/ViT-MoE/PipelinedViT)."""

    patch_size: int = 4
    hidden_dim: int = 192
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        p = self.patch_size
        x = nn.Conv(
            self.hidden_dim,
            kernel_size=(p, p),
            strides=(p, p),
            padding="VALID",
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="patch_embed",
        )(x)
        b, h, w, d = x.shape
        x = x.reshape((b, h * w, d))
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, h * w, d),
            self.param_dtype,
        )
        return x + pos.astype(self.dtype)


class ViTHead(nn.Module):
    """Final LN + global average pool + classifier (shared across ViTs)."""

    num_classes: int = 10
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype, name="ln_f")(x)
        x = jnp.mean(x, axis=1)  # global average pool (no class token; MXU-friendlier)
        x = nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=self.param_dtype, name="head"
        )(x)
        return x.astype(jnp.float32)


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        d = x.shape[-1]
        x = nn.Dense(
            self.mlp_dim, dtype=self.dtype, param_dtype=self.param_dtype, name="fc_in"
        )(x)
        x = nn.gelu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(d, dtype=self.dtype, param_dtype=self.param_dtype, name="fc_out")(x)
        return x


# What SelfAttention resolved `attn_impl` to in the calls traced while
# `resolved_attn_impls()` is open (None: nobody is asking).
_RESOLVED: Optional[set] = None


@contextlib.contextmanager
def resolved_attn_impls():
    """Collect, into the set this yields, what every SelfAttention traced
    inside resolved its `attn_impl` to outside decode ("xla", "flash",
    "flash_short", or "flash_flat": the streaming kernels on a block that
    stays flat, `SelfAttention._flat_block`). The choice is static, made
    once a trace from the shape, so whoever traces a model once (the
    Trainer's abstract init) knows which attention its programs run."""
    global _RESOLVED
    was, _RESOLVED = _RESOLVED, set()
    try:
        yield _RESOLVED
    finally:
        _RESOLVED = was


class SelfAttention(nn.Module):
    num_heads: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None  # mesh axis for sequence parallelism
    sp_impl: str = "ring"           # "ring" | "ulysses"
    # the attention core outside decode: "xla" (ops/attention.py
    # _attention), "flash" (the streaming Pallas kernels; where a device
    # holds whole heads the block around them stays flat, rope included:
    # `_flat_block`), or "auto": chosen at trace time from the shape
    # (resolve_attn_impl below)
    attn_impl: str = "auto"
    causal: bool = False            # decoder (LM) blocks mask the future
    rope: bool = False              # rotary Q/K (ops/rope.py) vs none here
    # decode-mode KV-cache storage dtype. None = the compute dtype (bf16
    # under the bf16 policy — already the small option there); set
    # jnp.bfloat16 to halve cache traffic under an fp32 policy. The
    # string "int8" stores a QUANTIZED cache (1 byte/element + per-
    # (batch, head, position) fp32 scales; ~1% relative logit error,
    # pinned in tests/test_decode_attention.py) — measured +17.5%
    # decode tokens/s at bs=8/L=1024 where the cache read dominates;
    # below L~768 the scale traffic eats the saving (BENCHMARKS.md).
    # Writes round to this dtype; attention math runs at the q/k
    # promotion (int8 dequantizes inside the packed kernel).
    kv_cache_dtype: object = None  # None | jnp.dtype | "int8"
    # grouped-query attention: `kv_heads` key/value heads serve
    # num_heads query heads (query head i reads KV head i // group).
    # None = num_heads (the fused three-way `qkv` projection); fewer
    # splits the projection into `q` and `kv`, and every cache leaf is
    # kv_heads * head_dim wide.
    kv_heads: Optional[int] = None
    use_bias: bool = True
    # what a later decoder block adds, each off by default (the plain XLA
    # attention and the decode paths take them; the flash paths refuse):
    # a head size that is not width / heads;
    head_dim: Optional[int] = None
    # a norm over each head of q and of k before the rotation: a module
    # constructor called as `qk_norm(name=...)`;
    qk_norm: Optional[Callable] = None
    # the rotation on a head's first `rope_dim` lanes only, at this base;
    rope_dim: Optional[int] = None
    rope_theta: float = 10000.0
    # and a sigmoid gate on the attention output from the query
    # projection's second half (a head projects to [query | gate])
    out_gate: bool = False
    # a sliding window: a query attends the `window` latest keys, its own
    # among them (None: every key before it). Under pages the layer's
    # leaves are the window GROUP's (serve/kv_pages.py CacheSpec): the pages
    # behind the window go back to the pool, a decode step walks from
    # `window_start` under the name `window_walk`, and a per-slot leaf
    # `window_stats` counts the pages it read beside a whole walk's
    window: Optional[int] = None
    # a paged call of several tokens (a prompt's chunk): "xla" gathers the
    # slot's whole span, widens K and V to a head a query head and masks;
    # "kernel" is ops/window_attention.py `window_prefill` (grouped KV
    # heads read as they are, pages behind a window neither fetched nor
    # computed, nothing the size of the span)
    paged_prefill: str = "xla"

    def _project(self, x, head_dim: int):
        """(q, k, v, fused): q (b, s, h, hd), k and v (b, s, kv_heads,
        hd); `fused` is the raw (b, s, 3, h, hd) projection when there is
        one (the packed flash kernels window it)."""
        dense = functools.partial(
            nn.DenseGeneral, dtype=self.dtype, param_dtype=self.param_dtype,
            use_bias=self.use_bias)
        kvh = self.kv_heads or self.num_heads
        if kvh == self.num_heads:
            qkv = dense((3, self.num_heads, head_dim), name="qkv")(x)
            return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], qkv
        if self.num_heads % kvh:
            raise ValueError(
                f"kv_heads {kvh} must divide num_heads {self.num_heads}")
        q = dense((self.num_heads, (1 + self.out_gate) * head_dim),
                  name="q")(x)
        kv = dense((2, kvh, head_dim), name="kv")(x)
        return q, kv[:, :, 0], kv[:, :, 1], None

    def _rotate(self, q, k, positions):
        """Rotary q and k at `positions`: whole heads, or the first
        `rope_dim` lanes of each (the frequencies are the part's)."""
        r = self.rope_dim
        if r is None:
            return (apply_rope(q, positions, theta=self.rope_theta),
                    apply_rope(k, positions, theta=self.rope_theta))
        part = lambda x: jnp.concatenate(
            [apply_rope(x[..., :r], positions, theta=self.rope_theta),
             x[..., r:]], axis=-1)
        return part(q), part(k)

    def _dense_flat(self, name: str, x):
        """The DenseGeneral `name` once more (same parameters, after the
        module made them), as ONE matmul between flat feature dims: its
        (in, ...) or (..., out) kernel merged to two dims."""
        from flax.linen.dtypes import promote_dtype

        p = self.variables["params"][name]
        kernel = p["kernel"].reshape(x.shape[-1], -1)
        bias = p["bias"].reshape(-1) if self.use_bias else None
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = x @ kernel
        return y if bias is None else y + bias

    def _project_flat(self, x):
        """The fused `qkv` projection once more (after `_project` made
        its parameters), onto a flat (b, s, 3*h*hd) output: the layout
        the packed kernels window. Through DenseGeneral the output is
        (b, s, 3, h, hd), which XLA lays out batch-minor when s is not a
        multiple of 8 (ViT's 196) or sequence-minor once sliced to
        (b, s, h, 64) (the LM's 2048), and then relays out around the
        kernels, 115 MB a copy at ViT-B/16's shape; its result is dead
        code here. No mesh axis may split the heads: merging a sharded
        (3, h, hd) into one dim would gather them."""
        return self._dense_flat("qkv", x)

    def _out_proj_flat(self, out):
        """`_out_proj` of a flat (b, s, h*hd) attention output, as ONE
        matmul from the same `out` parameters: the kernels' row-major
        output is the dot's operand as it was written, where
        DenseGeneral(axis=(-2, -1)) over (b, s, h, hd) has it turned
        sequence-minor first."""
        if self.is_initializing():  # DenseGeneral makes the parameters
            return self._out_proj(
                out.reshape(*out.shape[:2], self.num_heads, -1))
        return self._dense_flat("out", out)

    def _flat_block(self, head_dim: int, decode: bool) -> bool:
        """Whether a "flash" call keeps the block flat (`__call__`): no
        decode, no sequence parallelism, the fused `qkv` projection (no
        grouped K/V heads), heads that pack into 128 lanes, and no mesh
        axis over the heads (merging a sharded (3, h, hd) into one dim
        would gather them: `_project_flat`). Observed, never asked for."""
        from ddp_practice_tpu.ops.flash_attention import _heads_per_pack

        return (not decode and self.seq_axis is None
                and (self.kv_heads or self.num_heads) == self.num_heads
                and _heads_per_pack(self.num_heads, head_dim) is not None
                and self._tensor_parallel() == 1)

    def _widen_kv(self, k, v):
        """K and V repeated to one head a query head (the dense attention
        paths; the paged walk kernel reads the grouped cache as it is)."""
        group = self.num_heads // k.shape[2]
        if group == 1:
            return k, v
        return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)

    @staticmethod
    def _tensor_parallel() -> int:
        """Devices the registered mesh splits the heads over."""
        from ddp_practice_tpu.parallel.ring import get_current_mesh

        mesh = get_current_mesh()
        return 1 if mesh is None else mesh.shape.get(
            MeshConfig.AXIS_TENSOR, 1)

    def resolve_attn_impl(self, seq: int, head_dim: int, *,
                          decode: bool = False) -> str:
        """What `attn_impl` means for a call of this shape: "xla", "flash",
        or "flash_short" (ops/flash_attention.py's whole-sequence kernels).
        "xla" and "flash" are taken at their word. "auto" takes the short
        kernels where they run and the chip says they win: no decode, no
        rope, no sequence parallelism, the fused `qkv` projection (no
        grouped K/V heads), heads that pack into 128
        lanes on every device of the mesh, a sequence inside
        [SHORT_SEQ_MIN, SHORT_SEQ_MAX], compiled TPU execution (off the TPU
        a kernel nobody asked for would be interpreted); `_attention`
        everywhere else. No model is named: the shape decides."""
        if self.attn_impl != "auto":
            return self.attn_impl
        if (decode or self.rope or self.seq_axis is not None
                or (self.kv_heads or self.num_heads) != self.num_heads):
            return "xla"
        from ddp_practice_tpu.utils import backend

        if not backend.on_tpu():
            return "xla"
        from ddp_practice_tpu.ops.flash_attention import (
            SHORT_SEQ_MIN,
            short_seq_supported,
        )

        tensor = self._tensor_parallel()
        if (self.num_heads % tensor or seq < SHORT_SEQ_MIN
                or not short_seq_supported(
                    seq, self.num_heads // tensor, head_dim)):
            return "xla"
        return "flash_short"

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 page_table=None, kv_lengths=None, real_lengths=None):
        b, s, d = x.shape
        if self.head_dim is None:
            assert d % self.num_heads == 0, (d, self.num_heads)
        head_dim = self.head_dim or d // self.num_heads
        q, k, v, qkv = self._project(x, head_dim)
        impl = self.resolve_attn_impl(s, head_dim, decode=decode)
        later = (self.head_dim is not None or self.qk_norm is not None
                 or self.rope_dim is not None or self.out_gate
                 or self.window is not None)
        if later and (impl != "xla" or qkv is not None):
            raise ValueError(
                "head_dim, qk_norm, rope_dim, out_gate and window run on the "
                "plain attention path with a split q / kv projection: "
                f"attn_impl resolved to {impl!r}, kv_heads {self.kv_heads}")
        gate = None
        if self.out_gate:
            q, gate = q[..., :head_dim], q[..., head_dim:]
        if self.qk_norm is not None:
            q = self.qk_norm(name="q_norm")(q)
            k = self.qk_norm(name="k_norm")(k)
        flat = impl == "flash" and self._flat_block(head_dim, decode)
        if _RESOLVED is not None and not decode:
            _RESOLVED.add("flash_flat" if flat else impl)
        if flat:
            # training on whole heads: the block stays (b, s, features)
            # row-major from the qkv matmul to the out matmul, rope
            # included. Through the 4-D code below XLA lays every
            # (b, s, h, 64) activation out sequence-minor and copies it
            # back to row-major on each side of the kernels (eight
            # relayouts a layer, the cotangents' through float32:
            # ops/flash_attention.py flash_attention_flat).
            from ddp_practice_tpu.ops.flash_attention import (
                flash_attention_flat,
            )
            from ddp_practice_tpu.parallel.ring import kernel_island

            batch_split = P(MeshConfig.AXIS_DATA)
            out = kernel_island(
                functools.partial(flash_attention_flat,
                                  n_heads=self.num_heads,
                                  causal=self.causal, rope=self.rope),
                in_specs=(batch_split,), out_specs=batch_split,
            )(self._project_flat(x))
            return self._out_proj_flat(out)
        if (
            impl in ("flash", "flash_short")
            and not decode
            and not self.rope
            and self.seq_axis is None
            and qkv is not None
        ):
            # hand the raw projection output to the packed kernels: the
            # (3, h, hd) feature flatten IS the [q|k|v] column layout they
            # window at offsets, so q/k/v never materialize as slices
            # (~4 ms/step of layout traffic at lm_base — round-4 profile).
            # What reaches this branch is "flash_short", or "flash" with
            # the heads split over 'tensor' or unpackable (the flat
            # branch above took the rest); flash_attention_qkv itself
            # falls back for unpackable head shapes. rope never does: it
            # rotates q/k in 4-D below, in sequence-minor fusions with a
            # relayout copy each side of the kernel.
            # Under a mesh the kernel runs in a shard_map island
            # (parallel/ring.py kernel_island): the split is taken on
            # the (3, h, hd) dims, heads over 'tensor', and each device
            # flattens its OWN heads — sharding the flat 3*h*hd dim
            # would hand a device q heads without their k and v.
            from ddp_practice_tpu.ops import flash_attention as fa
            from ddp_practice_tpu.parallel.ring import (
                BSHD_SPEC,
                QKV_SPEC,
                kernel_island,
            )

            if impl == "flash_short" and self._tensor_parallel() == 1:
                # whole heads on every device: the flat projection, its
                # batch split over 'data' alone
                out = kernel_island(
                    functools.partial(fa.flash_short_qkv,
                                      n_heads=self.num_heads,
                                      causal=self.causal),
                    in_specs=(P(MeshConfig.AXIS_DATA),),
                    out_specs=BSHD_SPEC,
                )(self._project_flat(x))
                return self._out_proj(out)
            kernel = (fa.flash_short_qkv if impl == "flash_short"
                      else fa.flash_attention_qkv)

            def local_qkv(x):
                lb, ls, _, lh, lhd = x.shape
                return kernel(
                    x.reshape(lb, ls, 3 * lh * lhd), lh, causal=self.causal
                )

            out = kernel_island(
                local_qkv,
                in_specs=(QKV_SPEC,),
                out_specs=BSHD_SPEC,
            )(qkv)
            return self._out_proj(out)
        if self.rope and not decode:
            # global positions: under GSPMD jit the sequence dim is sharded
            # by annotation, not split — s IS the global length (the SP
            # shard_map island opens inside ring/ulysses, after this).
            # Rotations bake absolute position into Q/K, so attention
            # scores depend only on relative offsets downstream.
            positions = jnp.arange(s)
            q, k = self._rotate(q, k, positions)
        if decode:
            # KV-cache incremental decoding: the cache collection holds
            # pre-allocated FLAT (b, max_len, h*hd) key/value buffers
            # (shaped by a full-length init call) plus the write cursor.
            # The flat layout is load-bearing, not cosmetic: minor dims
            # (h, hd) tile-pad on TPU and a padded buffer defeats
            # in-place dynamic_update_slice — every per-token write
            # became a full cache relayout copy, 53.6% of the bs=8
            # decode step (round-4 profile; probes in
            # experiments/decode_layouts.py). Flat updates run in-place
            # (~0.2 us). One code path serves prefill (s = prompt length
            # at cursor 0) and single-token steps (s = 1): the step
            # attention is a packed Pallas kernel reading the flat cache
            # per head (ops/decode_attention.py), prefill reshapes once
            # and takes the masked XLA path.
            if not self.causal:
                raise ValueError("decode=True requires causal attention")
            if self.seq_axis is not None:
                raise ValueError(
                    "decode (KV-cache) mode does not compose with sequence "
                    "parallelism — generate on a data/tensor-sharded mesh"
                )
            if page_table is not None:
                # paged KV cache (serve/kv_pages.py): block-pool leaves,
                # per-slot page tables and write positions — no shared
                # cursor. Declares its own cache variables, so it must
                # branch before the flat-cache declarations below.
                return self._out_proj(self._paged_decode(
                    q, k, v, page_table, kv_lengths, attn_start,
                    real_lengths
                ), gate, d)
            # "int8": quantized cache — 1 byte/element plus per-(batch,
            # head, position) fp32 scales. Decode is HBM-bound and the
            # cache is ~40% of its traffic at batched sizes, so this is
            # the decode-MBU lever (round 5; ops/decode_attention.py
            # folds the scales into the kernel's score/probability
            # rows). The scale buffers are small ((b, h, L) f32); their
            # minor-dim dynamic updates may copy, which at ~KB scale is
            # noise next to the MB-scale cache stream they halve.
            quant = self.kv_cache_dtype == "int8"
            cache_dtype = (
                jnp.int8 if quant else (self.kv_cache_dtype or k.dtype)
            )
            b_, s_, h_, hd_ = k.shape
            flat_kv = (b_, s_, h_ * hd_)
            cached_key = self.variable(
                "cache", "cached_key", jnp.zeros, flat_kv, cache_dtype
            )
            cached_value = self.variable(
                "cache", "cached_value", jnp.zeros, flat_kv, cache_dtype
            )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            key_scale = value_scale = None
            if quant:
                key_scale = self.variable(
                    "cache", "cached_key_scale", jnp.zeros,
                    (b_, h_, s_), jnp.float32,
                )
                value_scale = self.variable(
                    "cache", "cached_value_scale", jnp.zeros,
                    (b_, h_, s_), jnp.float32,
                )
            if self.window is not None:   # tree parity with the pages'
                self.variable("cache", "window_stats", jnp.zeros, (b_, 2),
                              jnp.int32)
            if self.is_initializing():
                out = dot_product_attention(
                    q, *self._widen_kv(k, v), causal=True, impl="xla")
            else:
                from jax import lax

                from ddp_practice_tpu.ops.attention import attention_with_mask
                from ddp_practice_tpu.ops.decode_attention import (
                    decode_attention_packed,
                )
                from ddp_practice_tpu.ops.flash_attention import (
                    _heads_per_pack,
                )

                max_len = cached_key.value.shape[1]
                cur = cache_index.value
                if self.rope:
                    # cached keys are stored rotated, so only the incoming
                    # block needs rotation — at its absolute positions
                    positions = cur + jnp.arange(s)
                    q, k = self._rotate(q, k, positions)
                if quant:
                    def _quantize(x4):
                        # per-(batch, token, head) symmetric int8: the
                        # scale is that row's max |.| mapped to 127
                        amax = jnp.max(
                            jnp.abs(x4.astype(jnp.float32)), axis=-1
                        )                                # (b, s, h)
                        scale = jnp.maximum(amax, 1e-8) / 127.0
                        xq = jnp.round(
                            x4.astype(jnp.float32) / scale[..., None]
                        ).astype(jnp.int8)
                        return xq, jnp.swapaxes(scale, 1, 2)  # (b, h, s)

                    k_store, ks_new = _quantize(k)
                    v_store, vs_new = _quantize(v)
                    key_scale.value = lax.dynamic_update_slice(
                        key_scale.value, ks_new, (0, 0, cur)
                    )
                    value_scale.value = lax.dynamic_update_slice(
                        value_scale.value, vs_new, (0, 0, cur)
                    )
                else:
                    k_store, v_store = k, v
                kc = lax.dynamic_update_slice(
                    cached_key.value,
                    k_store.reshape(flat_kv[0], s, -1).astype(cache_dtype),
                    (0, cur, 0),
                )
                vc = lax.dynamic_update_slice(
                    cached_value.value,
                    v_store.reshape(flat_kv[0], s, -1).astype(cache_dtype),
                    (0, cur, 0),
                )
                cached_key.value = kc
                cached_value.value = vc
                cache_index.value = cur + s
                if (s == 1 and h_ == self.num_heads
                        and _heads_per_pack(h_, hd_) is not None):
                    # token step: packed kernel on the flat cache —
                    # no reshape, O(cur) cache reads (int8: scales ride
                    # as separate small operands)
                    out = decode_attention_packed(
                        q.reshape(flat_kv[0], 1, -1), kc, vc, cur,
                        attn_start, n_heads=h_,
                        k_scale=key_scale.value if quant else None,
                        v_scale=value_scale.value if quant else None,
                    ).reshape(flat_kv[0], 1, h_, hd_)
                else:
                    # prefill (s = prompt length) or unpackable head
                    # shapes: reshape the cache once and take the masked
                    # XLA path (amortized over the whole generation)
                    k4 = kc.reshape(flat_kv[0], max_len, h_, hd_)
                    v4 = vc.reshape(flat_kv[0], max_len, h_, hd_)
                    if quant:
                        # dequantize for the XLA path (one prefill pass
                        # per generation — amortized)
                        ks_t = jnp.swapaxes(key_scale.value, 1, 2)
                        vs_t = jnp.swapaxes(value_scale.value, 1, 2)
                        k4 = (k4.astype(jnp.float32)
                              * ks_t[..., None]).astype(q.dtype)
                        v4 = (v4.astype(jnp.float32)
                              * vs_t[..., None]).astype(q.dtype)
                    pos_q = cur + jnp.arange(s)
                    mask = jnp.arange(max_len)[None, :] <= pos_q[:, None]
                    if self.window is not None:
                        mask &= jnp.arange(max_len)[None, :] \
                            > pos_q[:, None] - self.window
                    if attn_start is not None:
                        # left-padded prompts (inference.py variable-
                        # length batching): key positions before each
                        # sequence's first real token never get attention
                        mask = mask[None] & (
                            jnp.arange(max_len)[None, None, :]
                            >= attn_start[:, None, None]
                        )
                        mask = mask[:, None]  # (b, 1, sq, sk)
                    out = attention_with_mask(
                        q, *self._widen_kv(k4, v4), mask)
        elif self.window is not None:
            from ddp_practice_tpu.ops.attention import attention_with_mask

            if not self.causal:
                raise ValueError("a window is a causal layer's")
            at = jnp.arange(s)
            out = attention_with_mask(
                q, *self._widen_kv(k, v), (at[None, :] <= at[:, None])
                & (at[None, :] > at[:, None] - self.window))
        else:
            out = dot_product_attention(
                q, *self._widen_kv(k, v), causal=self.causal,
                seq_axis=self.seq_axis,
                sp_impl=self.sp_impl, impl=impl,
            )
        return self._out_proj(out, gate, d)

    def _paged_decode(self, q, k, v, page_table, kv_lengths, attn_start,
                      real_lengths=None):
        """Paged KV-cache decode step / prefill (serve/kv_pages.py).

        The "cache" collection leaves are a POOL of fixed-size blocks
        (num_blocks, block_size, h*hd) shared by every slot; `page_table`
        (b, max_blocks_per_slot) int32 maps each slot's block list and
        `kv_lengths` (b,) int32 is each slot's write position — slot-LOCAL
        coordinates starting at 0, so RoPE rotates each slot at its own
        offset and there is no shared cursor to run out.

        s == 1 (decode step): the incoming token's K/V scatters into pool
        block `page_table[b, pos // block_size]` row `pos % block_size`;
        attention gathers through the same table
        (ops/decode_attention.paged_decode_attention) and masks
        [attn_start[b], pos[b]] in slot-local positions.

        s > 1 (paged PREFILL, PR 6): the s tokens occupy positions
        `kv_lengths[b] + [0, s)` — the prefix-cache admission path, where
        a prompt whose first `kv_lengths` positions are already resident
        (shared radix-cache blocks) prefills only its SUFFIX, attending
        the cached prefix through the page table. Writes scatter per
        position; attention gathers the slot's span once and masks
        causally per query row (amortized over the whole admission, the
        same trade the flat prefill makes). The SAME s > 1 path serves
        speculative-decoding verify windows (serve/spec.py): k drafted
        tokens scored in one forward at positions kv_lengths + [0, k),
        each attending the committed context plus the drafts before it —
        no extra model surface, the verify window IS a short paged
        prefill.

        kv_cache_dtype="int8" composes (PR 6): the pool carries
        per-block (num_blocks, h, block_size) fp32 scale pages
        (`cached_key_scale`/`cached_value_scale`, make_paged_cache) and
        the quantized kernel walks them through the same page table.
        """
        from ddp_practice_tpu.ops.attention import attention_with_mask
        from ddp_practice_tpu.ops.decode_attention import (
            gather_pages,
            paged_decode_attention,
        )

        if kv_lengths is None:
            raise ValueError(
                "paged decode needs kv_lengths (per-slot write positions)"
            )
        b_, s_, h_, hd_ = k.shape   # h_: KV heads (the pool's width)
        if self.is_initializing():
            raise ValueError(
                "paged cache pools are allocated by serve/kv_pages.py "
                "make_paged_cache, not by model.init"
            )
        quant = self.kv_cache_dtype == "int8"
        cache_dtype = (
            jnp.int8 if quant
            else (self.kv_cache_dtype if self.kv_cache_dtype is not None
                  else k.dtype)
        )
        cached_key = self.variable(
            "cache", "cached_key", jnp.zeros, (b_, s_, h_ * hd_), cache_dtype
        )
        cached_value = self.variable(
            "cache", "cached_value", jnp.zeros, (b_, s_, h_ * hd_),
            cache_dtype,
        )
        key_scale = value_scale = None
        if quant:
            key_scale = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (b_, h_, s_), jnp.float32,
            )
            value_scale = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (b_, h_, s_), jnp.float32,
            )
        # declared for tree parity with the flat cache (make_paged_cache
        # mirrors make_cache's structure); a block pool has no global
        # clock, so the scalar stays untouched
        self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        block_size = cached_key.value.shape[1]
        pool_dtype = cached_key.value.dtype
        pos0 = jnp.asarray(kv_lengths, jnp.int32)
        # (b, s) slot-local positions of the incoming tokens
        positions = pos0[:, None] + jnp.arange(s_, dtype=jnp.int32)[None, :]
        if self.rope:
            # no rotation at all is as slot-local as a rotary one (a model
            # whose other layers carry position); a learned absolute table
            # is refused where it lives (models/lm.py)
            q, k = self._rotate(q, k, positions)
        if quant:
            def _quantize(x4):
                # per-(batch, token, head) symmetric int8, same recipe
                # as the flat int8 cache above
                amax = jnp.max(jnp.abs(x4.astype(jnp.float32)), axis=-1)
                scale = jnp.maximum(amax, 1e-8) / 127.0    # (b, s, h)
                xq = jnp.round(
                    x4.astype(jnp.float32) / scale[..., None]
                ).astype(jnp.int8)
                return xq, scale

            k_store, ks_new = _quantize(k)
            v_store, vs_new = _quantize(v)
        else:
            k_store, v_store = k, v
        # clamp keeps a retired slot (page row 0, length pinned) writing
        # inside the table; active slots never reach the clamp — the
        # engine pre-allocates blocks for every position it dispatches
        blk_col = jnp.minimum(positions // block_size,
                              page_table.shape[1] - 1)
        blk = jnp.take_along_axis(page_table, blk_col, axis=1)  # (b, s)
        off = positions % block_size
        kc = cached_key.value.at[blk, off].set(
            k_store.reshape(b_, s_, -1).astype(pool_dtype)
        )
        vc = cached_value.value.at[blk, off].set(
            v_store.reshape(b_, s_, -1).astype(pool_dtype)
        )
        cached_key.value = kc
        cached_value.value = vc
        ks_pool = vs_pool = None
        if quant:
            # scale pages: advanced indices (b, s) on axes 0/2 straddle
            # the head slice, so the indexed result is (b, s, h) — set
            # with the per-(batch, token, head) scales directly
            ks_pool = key_scale.value.at[blk, :, off].set(ks_new)
            vs_pool = value_scale.value.at[blk, :, off].set(vs_new)
            key_scale.value = ks_pool
            value_scale.value = vs_pool
        if self.window is not None:
            from ddp_practice_tpu.ops.window_attention import window_start

            stats = self.variable("cache", "window_stats", jnp.zeros,
                                  (b_, 2), jnp.int32)
        if s_ == 1:
            first, walk = attn_start, {}
            if self.window is not None:
                # the same walk from a later start, through the window
                # group's table; what it read beside a whole walk's pages
                first = window_start(pos0, attn_start, self.window)
                whole = jnp.zeros_like(pos0) if attn_start is None \
                    else attn_start
                last = pos0 // block_size
                stats.value = stats.value + jnp.stack(
                    [last - first // block_size + 1,
                     last - whole // block_size + 1], axis=1)
                walk = {"name": "window_walk"}
            out = paged_decode_attention(
                q.reshape(b_, 1, -1), kc, vc, page_table, pos0, first,
                n_heads=self.num_heads, n_kv_heads=h_,
                k_scale=ks_pool, v_scale=vs_pool, **walk,
            )
            return out.reshape(b_, 1, self.num_heads, hd_)
        if self.paged_prefill == "kernel":
            from ddp_practice_tpu.ops.window_attention import (
                NO_WINDOW,
                window_prefill,
            )

            if quant:
                raise ValueError(
                    "paged_prefill='kernel' reads bf16 or float32 pages")
            group = self.num_heads // h_
            outs = [window_prefill(   # a chunk is one sequence's (b = 1)
                q[i].reshape(s_, h_, group, hd_), kc, vc, page_table[i],
                pos0[i], start=0 if attn_start is None else attn_start[i],
                window=self.window or NO_WINDOW,
                real=None if real_lengths is None else real_lengths[i],
            ).reshape(s_, self.num_heads, hd_) for i in range(b_)]
            return jnp.stack(outs).astype(q.dtype)
        # paged prefill: gather the slot's span once (dequantizing int8
        # pools through their scale pages) and mask causally per query
        # row in slot-local coordinates
        k4 = gather_pages(kc, page_table, h_, ks_pool)
        v4 = gather_pages(vc, page_table, h_, vs_pool)
        span = k4.shape[1]
        kpos = jnp.arange(span, dtype=jnp.int32)
        valid = kpos[None, None, :] <= positions[:, :, None]  # (b, s, span)
        if attn_start is not None:
            valid &= kpos[None, None, :] >= attn_start[:, None, None]
        if self.window is not None:
            valid &= kpos[None, None, :] > positions[:, :, None] - self.window
        cd = pool_dtype if not quant else q.dtype
        k4, v4 = self._widen_kv(k4, v4)
        out = attention_with_mask(
            q.astype(cd), k4.astype(cd), v4.astype(cd), valid[:, None]
        )
        return out.reshape(b_, s_, self.num_heads, hd_).astype(q.dtype)

    def _out_proj(self, out, gate=None, width=None):
        """Shared output projection over (b, s, h, hd) attention output —
        one definition for the fused-QKV and sliced/decode paths (they
        share the 'out' parameters), onto `width` features (heads x
        head size where none is given: the model's width unless
        `head_dim` is set). `gate` (b, s, h, hd): out * sigmoid(gate)
        first."""
        if gate is not None:
            out = out * nn.sigmoid(gate)
        d = width or out.shape[-2] * out.shape[-1]
        return nn.DenseGeneral(
            d,
            axis=(-2, -1),
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            use_bias=self.use_bias,
            name="out",
        )(out)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None
    sp_impl: str = "ring"
    attn_impl: str = "auto"         # see SelfAttention.attn_impl
    causal: bool = False
    rope: bool = False
    # pass-through to SelfAttention: None | jnp.dtype | "int8"
    kv_cache_dtype: object = None
    # residual-branch dropout (after the attention projection and inside
    # the MLP). Deliberately NOT on the attention probabilities: that
    # variant cannot compose with the flash/ring kernels, which never
    # materialize the probability matrix.
    dropout_rate: float = 0.0
    # swap the dense MLP for a routed expert MLP (ops/moe.py) — the LM
    # MoE composition (models/lm.py moe_every); ViT's dedicated MoE
    # blocks live in models/vit_moe.py
    use_moe: bool = False
    num_experts: int = 8
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_bias_rate: float = 0.02
    # tokens per routing group (0 = whole sequence); see ops/moe.py
    moe_group_size: int = 0
    moe_group_stride: bool = True
    # routing scheme: "topk" (tokens choose) | "expert_choice" (experts
    # choose — zero padding/drops; ops/moe.py MoEMlp.router)
    moe_router: str = "topk"
    # run the whole layer as ONE Pallas kernel per direction
    # (ops/fused_encoder.py): the HBM-bound small-d regime's fix
    # (BENCHMARKS.md ViT-Tiny analysis). Short-sequence blocks whose
    # weights fit VMEM only; the default backward is the hand-derived
    # Pallas kernel, pinned against unfused autodiff at 2e-4 tolerance
    # in tests/test_fused_encoder.py (bwd_impl="reference" gives the
    # bit-exact unfused gradients instead). Tri-state:
    #   "auto" (the model default) — fuse when the block is plain
    #     (no decode/rope/SP/MoE/dropout/attn override), the shape is
    #     kernel-feasible (fused_shape_supported), and the program runs
    #     compiled on a single TPU chip. Silent per-op fallback
    #     otherwise — users get the fast path without flags (round-4
    #     verdict: the documented vit_tiny command trained at 16.9% MFU
    #     while the fused kernel sat opt-in at 38.4%).
    #   True — force; unsupported configs raise (the pre-round-5
    #     behavior, what the numerics tests pin).
    #   False — always the per-op pipeline.
    fused: object = False  # bool | "auto"

    @nn.compact
    def __call__(self, x, decode: bool = False, train: bool = False, *,
                 attn_start=None, page_table=None, kv_lengths=None):
        # decode/train are positional-friendly: the LM's remat path wraps
        # this module in nn.remat(static_argnums=(2, 3)), and jax.checkpoint
        # only accepts non-array arguments at static positions. attn_start
        # / page_table / kv_lengths (arrays) are decode-only, where remat
        # never applies.
        fused = self.fused
        if fused == "auto":
            fused = not self.is_initializing() and self._auto_fuse(
                x, decode
            )
        if fused and not self.is_initializing():
            if not self._plain_block(decode):
                raise ValueError(
                    "fused encoder layer supports plain blocks only — "
                    "bidirectional or causal (round 4) — with no decode/"
                    "rope/seq-parallel/MoE/dropout/attn_impl override; "
                    "those paths keep the per-op pipeline"
                )
            from ddp_practice_tpu.ops.fused_encoder import (
                fused_encoder_layer,
            )
            from ddp_practice_tpu.parallel.ring import get_current_mesh

            mesh = get_current_mesh()
            if mesh is not None and mesh.devices.size > 1:
                # said here, at trace time, not by the TPU partitioner
                # ("Mosaic kernels cannot be automatically partitioned")
                raise ValueError(
                    f"fused=True on a {mesh.devices.size}-device mesh: the "
                    "fused encoder-layer kernels are single-chip (they "
                    "keep a layer's whole weights in one chip's VMEM and "
                    "have no shard_map island) — use fused='auto'/False "
                    "there, or a one-device mesh"
                )

            return fused_encoder_layer(
                x, self.variables["params"],
                num_heads=self.num_heads,
                compute_dtype=self.dtype,
                causal=self.causal,
            )
        return self._unfused(x, decode=decode, train=train,
                             attn_start=attn_start, page_table=page_table,
                             kv_lengths=kv_lengths)

    def _plain_block(self, decode) -> bool:
        """The ONE definition of 'plain block' — what the fused kernels
        can express. Shared by the fused=True loud gate and the "auto"
        fallback so they cannot drift apart."""
        return not (
            decode or self.rope or self.seq_axis is not None
            or self.use_moe or self.dropout_rate > 0.0
            or self.attn_impl not in ("xla", "auto")
        )

    def _auto_fuse(self, x, decode) -> bool:
        """Resolve fused="auto" at trace time: plain block + feasible
        shape + compiled single-chip TPU execution.

        The device gate is deliberate: CPU runs the kernel in interpret
        mode (orders of magnitude slower than per-op XLA — auto must
        never pick it), and compiled Pallas under a multi-chip GSPMD
        partition is not validated on hardware here, so implicit
        selection stays out of that regime; multi-chip users who have
        verified it force fused=True / --fused on."""
        if not self._plain_block(decode):
            return False
        from ddp_practice_tpu.parallel.ring import single_chip_tpu

        if not single_chip_tpu():
            return False
        from ddp_practice_tpu.ops.fused_encoder import fused_shape_supported

        return fused_shape_supported(
            seq_len=x.shape[1], d=x.shape[2], mlp_dim=self.mlp_dim,
            num_heads=self.num_heads, compute_dtype=self.dtype,
        )

    def _unfused(self, x, *, decode, train, attn_start,
                 page_table=None, kv_lengths=None):
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype, name="ln1")(x)
        y = SelfAttention(
            self.num_heads,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            seq_axis=self.seq_axis,
            sp_impl=self.sp_impl,
            attn_impl=self.attn_impl,
            causal=self.causal,
            rope=self.rope,
            kv_cache_dtype=self.kv_cache_dtype,
            name="attn",
        )(y, decode=decode, attn_start=attn_start, page_table=page_table,
          kv_lengths=kv_lengths)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        x = x + y
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=self.param_dtype, name="ln2")(x)
        if self.use_moe:
            from ddp_practice_tpu.ops.moe import MoEMlp

            y = MoEMlp(
                num_experts=self.num_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.capacity_factor,
                aux_loss_weight=self.moe_aux_weight,
                bias_update_rate=self.moe_bias_rate,
                group_size=self.moe_group_size,
                group_stride=self.moe_group_stride,
                router=self.moe_router,
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="moe",
            )(y, decode=decode)
            # residual-branch dropout for the routed MLP — the dense
            # MlpBlock applies its own internally; without this the MoE
            # blocks would silently train unregularized under --dropout
            y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        else:
            y = MlpBlock(
                self.mlp_dim, dtype=self.dtype, param_dtype=self.param_dtype,
                dropout_rate=self.dropout_rate, name="mlp",
            )(y, train=train)
        return x + y


class ViT(nn.Module):
    num_classes: int = 10
    patch_size: int = 4
    hidden_dim: int = 192
    depth: int = 12
    num_heads: int = 3
    mlp_dim: int = 768
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    seq_axis: Optional[str] = None
    sp_impl: str = "ring"
    attn_impl: str = "auto"         # see SelfAttention.attn_impl
    dropout_rate: float = 0.0       # residual-branch dropout in every block
    # one-Pallas-kernel layers (small-d fix); "auto" picks them whenever
    # the EncoderBlock's constraints hold (see EncoderBlock.fused)
    fused: object = "auto"          # bool | "auto"
    axis_name: Optional[str] = None  # accepted for registry uniformity (no BN)

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
            x = EncoderBlock(
                self.num_heads,
                self.mlp_dim,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                seq_axis=self.seq_axis,
                sp_impl=self.sp_impl,
                attn_impl=self.attn_impl,
                dropout_rate=self.dropout_rate,
                fused=self.fused,
                name=f"block{i}",
            )(x, train=train)
        return ViTHead(
            num_classes=self.num_classes,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="classifier",
        )(x)


def ViTTiny(**kw):
    kw.setdefault("hidden_dim", 192)
    kw.setdefault("depth", 12)
    kw.setdefault("num_heads", 3)
    kw.setdefault("mlp_dim", 768)
    return ViT(**kw)


def ViTBase(**kw):
    kw.setdefault("hidden_dim", 768)
    kw.setdefault("depth", 12)
    kw.setdefault("num_heads", 12)
    kw.setdefault("mlp_dim", 3072)
    return ViT(**kw)
