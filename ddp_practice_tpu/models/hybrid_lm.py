"""A decoder of mixed layers: state-space mixers, expert layers, dense
gated MLPs and grouped-query attention, one residual sub-layer a letter of
a pattern string.

    x = x + f_i(RMSNorm(x))            i over `pattern`
    'M'  Mamba-2 mixer                 (ops/ssm.py)
    'S'  Mamba-1 (selective scan) mixer: a decay for every channel and
         state, a low-rank dt, RMSNorm on dt, B and C (Jamba's)
    'G'  Gated DeltaNet mixer: the gated delta rule on a (key_dim,
         value_dim) matrix state a value head   (ops/gdn.py)
    'E'  LatentMoE, a chip's share of the experts held  (ops/moe.py)
    'Q'  GatedMoE with a softmax router and a shared expert behind a
         sigmoid gate of its own, a chip's share held   (ops/moe.py)
    'D'  dense SwiGLU MLP of width `mlp_dim`            (ops/moe.py GatedMLP)
    '*'  causal attention, `kv_heads` <= `num_heads`, no positional
         embedding: the recurrent layers carry position
    'A'  gated attention: heads of `head_dim` whatever the width, a norm
         on every q and k head, rotary on a head's first `rope_dim`
         lanes, the output times sigmoid(gate), the gate the query
         projection's second half (`pos_emb="rope"`)
    logits = RMSNorm(x) W_head         over `vocab_size` rows; with
                                       `tie_embeddings` W_head is the
                                       embedding itself

Three layouts in the registry. Nemotron-H (`create_model("nemotron_h",
...)`): one mixer a layer from 'M', 'E', '*', untied head. Jamba
(`create_model("jamba", ...)`): a layer is two sub-layers, a mixer ('S' or
'*') then 'D', so 28 layers are 56 letters, and the head is tied. Qwen3-Next
(`create_model("qwen3_next", ...)`): a mixer ('G', every fourth 'A') then
'Q', every norm the zero-centred `(1 + w)` one (`norm_plus_one`), untied
head. The widths are options, so the tests run all three small and the
benchmark at the published sizes (perf/configs/nemotron3_super_ep4.json,
jamba2_3b.json, qwen3next_80b_ep4.json).

Decode mode keeps TWO kinds of cache in the "cache" collection: attention
layers the K/V leaves `SelfAttention` declares (flat, or pages under
`PagedEngine`), state-space layers a fixed-size state a sequence under the
SAME two leaf names whichever mixer: `ssm_state` in float32 (Mamba-2
(b, heads, head_dim, state); Mamba-1 (b, state, channels / 128, 128), the
layout `ops/ssm.py sel_step` reads; Gated DeltaNet (b, value heads,
key_dim, value_dim)) and `conv_state`, the conv's last
`conv_kernel - 1` inputs. A call with one token a sequence advances the
state by the recurrence; a call with more runs the scan FROM the
stored state (zeros for a fresh sequence) and leaves the final state.
Left padding (`attn_start`) moves neither: a padded position has dt = 0
and a zero conv input. Pages cannot re-derive a state, so what needs a
sequence's past at an arbitrary position (a paged call of several tokens:
prefix reuse, chunked prefill, speculative verify) is refused here and at
engine construction.
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddp_practice_tpu.models.vit import SelfAttention
from ddp_practice_tpu.ops import gdn, ssm
from ddp_practice_tpu.ops.moe import GatedMLP, GatedMoE, LatentMoE


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # > 1: the last dim is normalised in that many equal groups
    groups: int = 1
    # zero-centred: the learned `weight` starts at 0 and scales by 1 + w
    plus_one: bool = False

    @nn.compact
    def __call__(self, x):
        if self.plus_one:
            scale = 1.0 + self.param(
                "weight", nn.initializers.zeros, (x.shape[-1],),
                self.param_dtype).astype(jnp.float32)
        else:
            scale = self.param("scale", nn.initializers.ones,
                               (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        shaped = xf.reshape(*x.shape[:-1], self.groups, -1)
        shaped = shaped * jax.lax.rsqrt(
            jnp.mean(jnp.square(shaped), axis=-1, keepdims=True) + self.eps)
        return (shaped.reshape(x.shape) * scale.astype(jnp.float32)
                ).astype(self.dtype)


def _state_leaves(module, state0, tail0, several_paged: bool):
    """The two cache leaves every state-space mixer declares, under the
    names `serve/kv_pages.py STATE_LEAVES` pools a slot."""
    if several_paged:
        raise ValueError(
            "a recurrent layer cannot take several tokens at slot-"
            "local positions through pages: its state holds only "
            "the sequence's end (prefix reuse, chunked prefill and "
            "speculative verify need state snapshots)")
    return (module.variable("cache", "ssm_state", lambda: state0),
            module.variable("cache", "conv_state", lambda: tail0))


class Mamba2Mixer(nn.Module):
    num_heads: int
    head_dim: int
    state_size: int
    groups: int
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 paged: bool = False):
        b, s, d = x.shape
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.groups)
        inner, gn = h * p, g * n
        conv_dim = inner + 2 * gn
        vec = lambda name, shape: self.param(
            name, nn.initializers.normal(0.02), shape, self.param_dtype)
        proj = nn.Dense(2 * inner + 2 * gn + h, use_bias=False,
                        dtype=self.dtype, param_dtype=self.param_dtype,
                        name="in_proj")(x)
        z, xbc, dt = jnp.split(proj, [inner, inner + conv_dim], axis=-1)
        conv_w = vec("conv_kernel", (self.conv_kernel, conv_dim))
        conv_b = vec("conv_bias", (conv_dim,))
        a = -jnp.exp(vec("A_log", (h,)).astype(jnp.float32))
        d_skip = vec("D", (h,))
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + vec("dt_bias", (h,)).astype(jnp.float32))
        if attn_start is not None and s > 1:
            # a call of several tokens is a padded row from its position 0
            # (the engines' prefill); a single token is always real
            real = jnp.arange(s)[None, :] >= attn_start[:, None]   # (b, s)
            dt = jnp.where(real[..., None], dt, 0.0)
            xbc = jnp.where(real[..., None], xbc, 0)
        state0 = jnp.zeros((b, h, p, n), jnp.float32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, conv_dim), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(
                self, state0, tail0, paged and s > 1)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        xbc, tail = ssm.causal_conv(xbc, tail0, conv_w, conv_b)
        xbc = nn.silu(xbc)
        xs, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
        xs = xs.reshape(b, s, h, p)
        bm, cm = bm.reshape(b, s, g, n), cm.reshape(b, s, g, n)
        if decode and s == 1 and not self.is_initializing():
            y, state = ssm.ssm_step(xs[:, 0], dt[:, 0], a, bm[:, 0],
                                    cm[:, 0], d_skip, state0)
            y = y[:, None]
        else:
            y, state = ssm.ssm_scan(xs, dt, a, bm, cm, d_skip, state0,
                                    chunk=self.chunk_size)
        if decode and not self.is_initializing():
            ssm_state.value = state
            conv_state.value = tail.astype(conv_state.value.dtype)
        y = y.reshape(b, s, inner).astype(self.dtype) * nn.silu(z)
        y = RMSNorm(self.norm_eps, self.dtype, self.param_dtype,
                    groups=g, name="norm")(y)
        return nn.Dense(d, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="out_proj")(y)


class Mamba1Mixer(nn.Module):
    """[u, z] = x W_in; u = silu(conv(u) + b); [r, B, C] = u W_x, each
    RMSNormed; dt = softplus(r W_dt + b_dt); the selective scan over
    (channel, state) with A = -exp(A_log); out = (y * silu(z)) W_out."""

    inner: int
    state_size: int
    dt_rank: int
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 paged: bool = False):
        b, s, d = x.shape
        c, n, r = self.inner, self.state_size, self.dt_rank
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        vec = lambda name, shape: self.param(
            name, nn.initializers.normal(0.02), shape, self.param_dtype)
        norm = lambda name: RMSNorm(self.norm_eps, name=name, **kw)
        u, z = jnp.split(
            nn.Dense(2 * c, use_bias=False, name="in_proj", **kw)(x), 2,
            axis=-1)
        conv_w = vec("conv_kernel", (self.conv_kernel, c))
        conv_b = vec("conv_bias", (c,))
        a = -jnp.exp(vec("A_log", (c, n)).astype(jnp.float32))
        d_skip = vec("D", (c,))
        real = None
        if attn_start is not None and s > 1:
            # a call of several tokens is a padded row from its position 0
            # (the engines' prefill); a single token is always real
            real = (jnp.arange(s)[None, :] >= attn_start[:, None])[..., None]
            u = jnp.where(real, u, 0)
        state0 = jnp.zeros(ssm.sel_state_shape(b, c, n), jnp.float32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, c), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(
                self, state0, tail0, paged and s > 1)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        u, tail = ssm.causal_conv(u, tail0, conv_w, conv_b)
        u = nn.silu(u)
        low, bm, cm = jnp.split(
            nn.Dense(r + 2 * n, use_bias=False, name="x_proj", **kw)(u),
            [r, r + n], axis=-1)
        bm, cm = norm("b_norm")(bm), norm("c_norm")(cm)
        dt = jax.nn.softplus(nn.Dense(c, name="dt_proj", **kw)(
            norm("dt_norm")(low)).astype(jnp.float32))
        if real is not None:
            dt = jnp.where(real, dt, 0.0)
        if decode and s == 1 and not self.is_initializing():
            y, state = ssm.sel_step(u[:, 0], dt[:, 0], a, bm[:, 0],
                                    cm[:, 0], d_skip, state0)
            y = y[:, None]
        else:
            y, state = ssm.sel_scan(u, dt, a, bm, cm, d_skip, state0)
        if decode and not self.is_initializing():
            ssm_state.value = state
            conv_state.value = tail.astype(conv_state.value.dtype)
        y = y.astype(self.dtype) * nn.silu(z)
        return nn.Dense(d, use_bias=False, name="out_proj", **kw)(y)


class GatedDeltaMixer(nn.Module):
    """[q|k|v|z] = x W_in, [b|a] = x W_ba; silu(conv([q|k|v])), no bias;
    q, k L2-normalised a head, q / sqrt(key_dim); beta = sigmoid(b),
    g = -exp(A_log) softplus(a + dt_bias); the gated delta rule (ops/gdn.py)
    on a (key_dim, value_dim) state a value head, a key head serving
    value_heads / key_heads of them; out = (RMSNorm_head(o) * silu(z)) W_out
    with the norm and the gate in float32."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 paged: bool = False):
        b, s, d = x.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        keys, values = hk * dk, hv * dv
        conv_dim = 2 * keys + values
        f32 = jnp.float32
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        vec = lambda name, shape: self.param(
            name, nn.initializers.normal(0.02), shape, self.param_dtype)
        qkv, z = jnp.split(
            nn.Dense(conv_dim + values, use_bias=False, name="in_proj",
                     **kw)(x), [conv_dim], axis=-1)
        beta, a = jnp.split(
            nn.Dense(2 * hv, use_bias=False, name="ba_proj", **kw)(x)
            .astype(f32), 2, axis=-1)
        conv_w = vec("conv_kernel", (self.conv_kernel, conv_dim))
        beta = nn.sigmoid(beta)
        g = -jnp.exp(vec("A_log", (hv,)).astype(f32)) * jax.nn.softplus(
            a + vec("dt_bias", (hv,)).astype(f32))
        if attn_start is not None and s > 1:
            # a call of several tokens is a padded row from its position 0
            # (the engines' prefill); a single token is always real
            real = (jnp.arange(s)[None, :] >= attn_start[:, None])[..., None]
            beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
            qkv = jnp.where(real, qkv, 0)
        state0 = jnp.zeros((b, hv, dk, dv), f32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, conv_dim), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(
                self, state0, tail0, paged and s > 1)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        qkv, tail = ssm.causal_conv(qkv, tail0, conv_w, None)
        q, k, v = jnp.split(nn.silu(qkv).astype(f32), [keys, 2 * keys],
                            axis=-1)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        q = unit(q.reshape(b, s, hk, dk)) * dk ** -0.5
        k = unit(k.reshape(b, s, hk, dk))
        v = v.reshape(b, s, hv, dv)
        if decode and s == 1 and not self.is_initializing():
            o, state = gdn.gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state0)
            o = o[:, None]
        else:
            o, state = gdn.gdn_scan(q, k, v, g, beta, state0)
        if decode and not self.is_initializing():
            ssm_state.value = state
            conv_state.value = tail.astype(conv_state.value.dtype)
        o = RMSNorm(self.norm_eps, f32, self.param_dtype, name="norm")(o)
        o = o * nn.silu(z.astype(f32).reshape(b, s, hv, dv))
        return nn.Dense(d, use_bias=False, name="out_proj", **kw)(
            o.reshape(b, s, values).astype(self.dtype))


class HybridLM(nn.Module):
    pattern: str = "MEM*EME"
    vocab_size: int = 256
    hidden_dim: int = 64
    max_len: int = 262144
    # 'M'
    mamba_heads: int = 8
    mamba_head_dim: int = 8
    ssm_state: int = 16
    ssm_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 128
    # 'S'
    mamba_inner: int = 128
    dt_rank: int = 8
    # 'G'
    gdn_key_heads: int = 2
    gdn_value_heads: int = 4
    gdn_key_dim: int = 16
    gdn_value_dim: int = 16
    # 'D'
    mlp_dim: int = 128
    # '*', 'A'
    num_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    # 'A'
    rope_dim: int = 8
    rope_theta: float = 10000.0
    # 'E', 'Q' ('Q' has no latent)
    num_experts: int = 16
    top_k: int = 3
    latent_dim: int = 32
    expert_dim: int = 48
    shared_dim: int = 96
    experts_held: int = 4
    expert_offset: int = 0
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    # every RMSNorm of the residual stream and of 'A' scales by 1 + w
    norm_plus_one: bool = False
    tie_embeddings: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # what the serving engines ask a model: how positions enter (here the
    # recurrent layers carry them) and whether a sequence has state that
    # pages cannot hold
    pos_emb: str = "none"
    recurrent: bool = True
    axis_name: Optional[str] = None  # registry uniformity

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 attn_start=None, page_table=None, kv_lengths=None):
        """tokens (batch, seq) int32 -> logits (batch, seq, vocab_size) in
        the compute dtype. `decode`, `attn_start`, `page_table` and
        `kv_lengths` as in models/lm.py TransformerLM."""
        del train
        if set(self.pattern) - set("MSGEQD*A") or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: want a string of 'M', 'S', 'G', "
                "'E', 'Q', 'D', '*', 'A'")
        if "*" in self.pattern \
                and self.hidden_dim != self.num_heads * self.head_dim:
            raise ValueError(
                "'*' takes its head size from the width: "
                f"hidden_dim {self.hidden_dim} != num_heads "
                f"{self.num_heads} x head_dim {self.head_dim}")
        if "A" in self.pattern and self.pos_emb != "rope":
            raise ValueError("'A' rotates q and k: want pos_emb='rope'")
        if (page_table is not None or attn_start is not None) and not decode:
            raise ValueError("page_table / attn_start are decode features")
        if tokens.shape[1] > self.max_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_len {self.max_len}")
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        embed = nn.Embed(self.vocab_size, self.hidden_dim, name="tok_embed",
                         **kw)
        x = embed(tokens)
        norm = functools.partial(RMSNorm, self.norm_eps,
                                 plus_one=self.norm_plus_one, **kw)
        for i, kind in enumerate(self.pattern):
            y = norm(name=f"norm{i}")(x)
            if kind == "M":
                y = Mamba2Mixer(
                    self.mamba_heads, self.mamba_head_dim, self.ssm_state,
                    self.ssm_groups, self.conv_kernel, self.chunk_size,
                    self.norm_eps, name=f"mamba{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  paged=page_table is not None)
            elif kind == "S":
                y = Mamba1Mixer(
                    self.mamba_inner, self.ssm_state, self.dt_rank,
                    self.conv_kernel, self.norm_eps, name=f"mamba{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  paged=page_table is not None)
            elif kind == "G":
                y = GatedDeltaMixer(
                    self.gdn_key_heads, self.gdn_value_heads,
                    self.gdn_key_dim, self.gdn_value_dim, self.conv_kernel,
                    self.norm_eps, name=f"mamba{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  paged=page_table is not None)
            elif kind == "D":
                y = GatedMLP(self.mlp_dim, name=f"mlp{i}", **kw)(y)
            elif kind == "Q":
                y = GatedMoE(
                    self.num_experts, self.top_k, self.expert_dim,
                    self.shared_dim, self.experts_held, self.expert_offset,
                    self.routed_scaling, router="softmax", shared_gate=True,
                    name=f"moe{i}", **kw,
                )(y, decode=decode)
            elif kind == "E":
                y = LatentMoE(
                    self.num_experts, self.top_k, self.latent_dim,
                    self.expert_dim, self.shared_dim, self.experts_held,
                    self.expert_offset, self.routed_scaling,
                    name=f"moe{i}", **kw,
                )(y, decode=decode)
            elif kind == "A":
                y = SelfAttention(
                    self.num_heads, causal=True, rope=True,
                    kv_heads=self.kv_heads, use_bias=False,
                    head_dim=self.head_dim, qk_norm=norm,
                    rope_dim=self.rope_dim, rope_theta=self.rope_theta,
                    out_gate=True, name=f"attn{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  page_table=page_table, kv_lengths=kv_lengths)
            else:
                y = SelfAttention(
                    self.num_heads, causal=True, rope=False,
                    kv_heads=self.kv_heads, use_bias=False,
                    name=f"attn{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  page_table=page_table, kv_lengths=kv_lengths)
            x = x + y
        x = norm(name="norm_f")(x)
        if self.tie_embeddings:
            return embed.attend(x)
        return nn.Dense(self.vocab_size, use_bias=False, name="lm_head",
                        **kw)(x)
