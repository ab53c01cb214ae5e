"""A decoder of mixed layers: state-space mixers, expert layers, dense
gated MLPs and grouped-query attention, one residual sub-layer a letter of
a pattern string.

    x = x + f_i(RMSNorm(x))            i over `pattern`
    'M'  Mamba-2 mixer                 (ops/ssm.py)
    'S'  Mamba-1 (selective scan) mixer: a decay for every channel and
         state, a low-rank dt, RMSNorm on dt, B and C (Jamba's)
    'G'  Gated DeltaNet mixer: the gated delta rule on a (key_dim,
         value_dim) matrix state a value head   (ops/gdn.py)
    'K'  Kimi Delta Attention mixer: the delta rule with a decay a KEY
         LANE under a bounded (safe) gate, one key head a value head, one
         output gate scalar a head   (ops/kda.py; `KimiDeltaMixer`)
    'L'  lightning (linear) attention: the Mamba-2 recurrence with dt = 1
         and one constant decay a head, on rotated, normed q and k
         (ops/ssm.py `ssm_step` / `ssm_scan`; `LightningMixer`)
    'E'  LatentMoE, a chip's share of the experts held  (ops/moe.py)
    'Q'  GatedMoE with a softmax router and a shared expert behind a
         sigmoid gate of its own, a chip's share held   (ops/moe.py)
    'R'  GatedMoE with ReGLU experts, a softmax router that reads the
         normed input of the MIXER before it (not its own), no shared
         expert                                         (ops/moe.py)
    'U'  GatedMoE with a sigmoid router that keeps `topk_group` of
         `n_group` groups of experts before it picks, a selection bias, an
         ungated shared expert                          (ops/moe.py)
    'D'  dense SwiGLU MLP of width `mlp_dim`            (ops/moe.py GatedMLP)
    '*'  causal attention, `kv_heads` <= `num_heads`, no positional
         embedding: the recurrent layers carry position, or nothing does
         (heads of `head_dim` where that is not width / heads)
    'W'  window attention: '*' with rotary on the whole head and a
         sliding window of `window` keys, the query's own among them; its
         pages are the window GROUP's (`cache_spec`)
    'A'  gated attention: heads of `head_dim` whatever the width, a norm
         on every q and k head, rotary on a head's first `rope_dim`
         lanes, the output times sigmoid(gate), the gate the query
         projection's second half (`pos_emb="rope"`)
    'B'  block-sparse attention: head norms, no rotary, an output gate;
         past `sparse.dense_len` visible tokens a query attends `topk`
         pages picked through compressed keys (ops/sparse_attention.py;
         `SparseAttention`)
    'T'  multi-head latent attention: ONE cached row `[c | k_rope]` a
         token, rotary on the `rope_dim` rope lanes, absorbed for a decode
         step (`paged_decode_mla`), one output gate scalar a head
         (`LatentAttention`, the class `MLALM` runs: models/mla_lm.py)
    logits = RMSNorm(x) W_head         over `vocab_size` rows; with
                                       `tie_embeddings` W_head is the
                                       embedding itself

Six layouts in the registry. Nemotron-H (`create_model("nemotron_h",
...)`): one mixer a layer from 'M', 'E', '*', untied head. Jamba
(`create_model("jamba", ...)`): a layer is two sub-layers, a mixer ('S' or
'*') then 'D', so 28 layers are 56 letters, and the head is tied. Qwen3-Next
(`create_model("qwen3_next", ...)`): a mixer ('G', every fourth 'A') then
'Q', every norm the zero-centred `(1 + w)` one (`norm_plus_one`), untied
head. MiniCPM-SALA (`create_model("minicpm_sala", ...)`): a mixer ('L',
every fourth 'B') then 'D', with muP scalars on the stream: `embed_scale`
times the embedding, `residual_scale` times every branch, `head_scale`
times the head's input (each 1 in the other layouts). SmallThinker
(`create_model("smallthinker", ...)`): a mixer ('W', every fourth '*' with
no positional embedding at all) then 'R', no recurrent state
(`recurrent=False`), a prompt's chunks through the kernel `window_prefill`
(`attn_prefill="kernel"`), untied head. Ling-3.0-flash
(`create_model("ling3", ...)`, the pattern built from `layers`,
`layer_group_size` and `first_dense`): a mixer ('K', every
`layer_group_size`-th 'T') then a feed-forward ('D' for the first
`first_dense` layers, 'U' after), a recurrent state AND latent pages in one
cache, untied head. The widths are
options, so the tests run all six small and the benchmark at the published
sizes (perf/configs/nemotron3_super_ep4.json, jamba2_3b.json,
qwen3next_80b_ep4.json, minicpm_sala_9b_pp4.json, smallthinker_21b_pp7.json,
ling3_flash_ep4.json).

Decode mode keeps TWO kinds of cache in the "cache" collection: attention
layers the K/V leaves `SelfAttention` declares or the one latent leaf of
`LatentAttention` (flat, or pages under
`PagedEngine`), state-space layers a fixed-size state a sequence under the
SAME two leaf names whichever mixer: `ssm_state` in float32 (Mamba-2
(b, heads, head_dim, state); Mamba-1 (b, state, channels / 128, 128), the
layout `ops/ssm.py sel_step` reads; Gated DeltaNet and Kimi Delta Attention
(b, value heads, key_dim, value_dim)) and `conv_state`, the conv's last
`conv_kernel - 1` inputs ('L' has no conv and declares `ssm_state` alone,
(b, heads, value, key)). A call with one token a sequence advances the
state by the recurrence; a call with more runs the scan FROM the
stored state (zeros for a fresh sequence) and leaves the final state.
Padding moves neither: a padded position has dt = 0 and a zero conv input.
A flat call of several tokens is LEFT-padded (`attn_start`); a paged one
continues the slot's END (a prompt's next chunk, at slot-local positions
`kv_lengths + [0, s)`) and is RIGHT-padded past `real_lengths` (0 for the
one token of a slot that is not decoding: its state stays). Pages
cannot re-derive a state, so what needs a sequence's state at a position
that is NOT its end (prefix reuse, speculative verify, a fork) is refused
at engine construction (serve/engine.py): the mixers cannot tell.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ddp_practice_tpu.models.vit import SelfAttention
from ddp_practice_tpu.ops import gdn, kda, sparse_attention as sparse_ops, ssm
from ddp_practice_tpu.ops.attention import _attention, attention_with_mask
from ddp_practice_tpu.ops.decode_attention import paged_decode_mla
from ddp_practice_tpu.ops.moe import GatedMLP, GatedMoE, LatentMoE
from ddp_practice_tpu.ops.rope import apply_rope


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


def _state_leaves(module, state0, tail0):
    """The two cache leaves every state-space mixer declares, under the
    names `serve/kv_pages.py STATE_LEAVES` pools a slot."""
    return (module.variable("cache", "ssm_state", lambda: state0),
            module.variable("cache", "conv_state", lambda: tail0))


def _real_rows(s: int, attn_start, real_lengths, paged: bool):
    """(b, s, 1) bool, the positions of a call that are not padding, or
    None where all are: a flat call of several tokens is a left-padded row
    from its position 0 (the engines' prefill), a paged call right-padded
    past `real_lengths`: a chunk's tail, or the one token of a slot that is
    not decoding (`real_lengths` 0: a slot between two chunks of its prompt
    keeps its state through the others' decode steps)."""
    real = None
    if s > 1 and attn_start is not None and not paged:
        real = jnp.arange(s)[None, :] >= attn_start[:, None]
    if real_lengths is not None:
        inside = jnp.arange(s)[None, :] < real_lengths[:, None]
        real = inside if real is None else real & inside
    return None if real is None else real[..., None]


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
                 paged: bool = False, real_lengths=None):
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
        real = _real_rows(s, attn_start, real_lengths, paged)
        if real is not None:
            dt = jnp.where(real, dt, 0.0)
            xbc = jnp.where(real, xbc, 0)
        state0 = jnp.zeros((b, h, p, n), jnp.float32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, conv_dim), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(self, state0, tail0)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        xbc, tail = ssm.causal_conv(xbc, tail0, conv_w, conv_b,
                                    real_lengths)
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
                 paged: bool = False, real_lengths=None):
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
        real = _real_rows(s, attn_start, real_lengths, paged)
        if real is not None:
            u = jnp.where(real, u, 0)
        state0 = jnp.zeros(ssm.sel_state_shape(b, c, n), jnp.float32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, c), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(self, state0, tail0)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        u, tail = ssm.causal_conv(u, tail0, conv_w, conv_b,
                                  real_lengths)
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
                 paged: bool = False, real_lengths=None):
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
        real = _real_rows(s, attn_start, real_lengths, paged)
        if real is not None:
            beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
            qkv = jnp.where(real, qkv, 0)
        state0 = jnp.zeros((b, hv, dk, dv), f32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, conv_dim), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(self, state0, tail0)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        qkv, tail = ssm.causal_conv(qkv, tail0, conv_w, None,
                                    real_lengths)
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


class KimiDeltaMixer(nn.Module):
    """Kimi Delta Attention. [q|k|v] = silu(conv(x W_in)), no bias; q, k
    L2-normalised a head, q / sqrt(key_dim); beta = sigmoid(x W_b) a head;
    the decay a KEY LANE, g = lower_bound * sigmoid(exp(A_log[h]) *
    (x W_f + dt_bias)) in float32, so lower_bound < g < 0 (the safe gate;
    ops/kda.py's chunked form relies on |lower_bound| <= 5); the delta rule
    with that decay on a (key_dim, value_dim) state a head (ops/kda.py);
    out = (RMSNorm_head(o) * sigmoid(x W_z)[h]) W_out, one gate scalar a
    head, the norm and the gate in float32."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    lower_bound: float = -5.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 paged: bool = False, real_lengths=None):
        b, s, d = x.shape
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        keys, values = h * dk, h * dv
        conv_dim = 2 * keys + values
        f32 = jnp.float32
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        dense = functools.partial(nn.Dense, use_bias=False, **kw)
        vec = lambda name, shape: self.param(
            name, nn.initializers.normal(0.02), shape, self.param_dtype)
        qkv = dense(conv_dim, name="in_proj")(x)
        a = dense(keys, name="f_proj")(x).astype(f32) \
            + vec("dt_bias", (keys,)).astype(f32)
        rate = jnp.exp(vec("A_log", (h,)).astype(f32))
        g = self.lower_bound * nn.sigmoid(
            rate[:, None] * a.reshape(b, s, h, dk))
        beta = nn.sigmoid(dense(h, name="b_proj")(x).astype(f32))
        gate = nn.sigmoid(dense(h, name="z_proj")(x).astype(f32))
        conv_w = vec("conv_kernel", (self.conv_kernel, conv_dim))
        real = _real_rows(s, attn_start, real_lengths, paged)
        if real is not None:
            beta = jnp.where(real, beta, 0.0)
            g = jnp.where(real[..., None], g, 0.0)
            qkv = jnp.where(real, qkv, 0)
        state0 = jnp.zeros((b, h, dk, dv), f32)
        tail0 = jnp.zeros((b, self.conv_kernel - 1, conv_dim), self.dtype)
        if decode:
            ssm_state, conv_state = _state_leaves(self, state0, tail0)
            if not self.is_initializing():
                state0, tail0 = ssm_state.value, conv_state.value
        qkv, tail = ssm.causal_conv(qkv, tail0, conv_w, None, real_lengths)
        q, k, v = jnp.split(nn.silu(qkv).astype(f32), [keys, 2 * keys],
                            axis=-1)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        q = unit(q.reshape(b, s, h, dk)) * dk ** -0.5
        k = unit(k.reshape(b, s, h, dk))
        v = v.reshape(b, s, h, dv)
        if decode and s == 1 and not self.is_initializing():
            o, state = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0], state0)
            o = o[:, None]
        else:
            o, state = kda.kda_scan(q, k, v, g, beta, state0)
        if decode and not self.is_initializing():
            ssm_state.value = state
            conv_state.value = tail.astype(conv_state.value.dtype)
        o = RMSNorm(self.norm_eps, f32, self.param_dtype, name="norm")(o)
        o = o * gate[..., None]
        return dense(d, name="out_proj")(
            o.reshape(b, s, values).astype(self.dtype))


class LightningMixer(nn.Module):
    """Lightning (linear) attention. [q|k|v|gate] = x W_in; q, k RMSNormed a
    head, rotated whole (half-split pairs) at the token's position,
    q / sqrt(head_dim); a head's (value, key) float32 state
    S_t = lambda_h S_{t-1} + v_t k_t^T, o_t = S_t q_t, which is the Mamba-2
    recurrence with dt = 1, x = v, B = k, C = q, no skip and a group a head
    (ops/ssm.py); out = (RMSNorm_head(o) * sigmoid(gate)) W_out. The decay is
    a constant: lambda_h = exp(-s_h), s_h = 2^(-8 (h + 1) / heads) *
    (1 - layer / (layers - 1) + 1e-5), `layer` of `layers` the model's own
    (published) numbering."""

    num_heads: int
    head_dim: int
    layer: int = 0
    layers: int = 2
    rope_theta: float = 10000.0
    chunk_size: int = 128
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, positions, decode: bool = False,
                 attn_start=None, paged: bool = False, real_lengths=None):
        b, s, d = x.shape
        h, p = self.num_heads, self.head_dim
        f32 = jnp.float32
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(self.norm_eps, name=name, **kw)
        q, k, v, gate = jnp.split(
            nn.Dense(4 * h * p, use_bias=False, name="in_proj", **kw)(x), 4,
            axis=-1)
        heads = lambda t: t.reshape(b, s, h, p)
        q = apply_rope(norm("q_norm")(heads(q)), positions,
                       theta=self.rope_theta) * p ** -0.5
        k = apply_rope(norm("k_norm")(heads(k)), positions,
                       theta=self.rope_theta)
        slope = 2.0 ** (-8.0 * (jnp.arange(h, dtype=f32) + 1.0) / h) \
            * (1.0 - self.layer / max(self.layers - 1, 1) + 1e-5)
        real = _real_rows(s, attn_start, real_lengths, paged)
        dt = jnp.ones((b, s, h), f32) if real is None \
            else jnp.broadcast_to(real.astype(f32), (b, s, h))
        state0 = jnp.zeros((b, h, p, p), f32)
        if decode:
            ssm_state = self.variable("cache", "ssm_state", lambda: state0)
            if not self.is_initializing():
                state0 = ssm_state.value
        no_skip = jnp.zeros((h,), f32)
        if decode and s == 1 and not self.is_initializing():
            o, state = ssm.ssm_step(heads(v)[:, 0], dt[:, 0], -slope,
                                    k[:, 0], q[:, 0], no_skip, state0)
            o = o[:, None]
        else:
            o, state = ssm.ssm_scan(heads(v), dt, -slope, k, q, no_skip,
                                    state0, chunk=self.chunk_size)
        if decode and not self.is_initializing():
            ssm_state.value = state
        o = RMSNorm(self.norm_eps, f32, self.param_dtype, name="norm")(o)
        o = o.reshape(b, s, h * p) * nn.sigmoid(gate.astype(f32))
        return nn.Dense(d, use_bias=False, name="out_proj", **kw)(
            o.astype(self.dtype))


class SparseAttention(nn.Module):
    """Block-sparse grouped-query attention (ops/sparse_attention.py has the
    equations): [query | gate] = x W_q a head, k, v = x W_kv, q and k
    RMSNormed a head, no rotary; dense causal attention up to
    `spec.dense_len` visible tokens, `spec.topk` picked blocks past it;
    out = (o * sigmoid(gate)) W_o. A class beside `SelfAttention`, not a mode
    of it: it shares the projections' shapes and nothing of the cache (a
    third leaf, the compressed keys `cached_index`, `spec.rows` rows a
    page; per-slot counts `sparse_stats`) or of the attention cores.

    Decode mode: a flat cache (`inference.make_cache`, the engines' scratch
    prefill) runs the plain jax.numpy form over the whole span; pages
    (`page_table`) run `sparse_select` + `sparse_walk` for one token a slot
    and `sparse_prefill` for a chunk, whose rows past `real_lengths` are
    padding. Blocks are counted in slot-local POSITIONS, so they are the
    sequence's own only where it starts at position 0 (a paged, right-padded
    admission: `PagedEngine` with `prefill_chunk`); a left-padded row is
    exact up to `dense_len` and picks among shifted blocks past it."""

    num_heads: int
    kv_heads: int
    head_dim: int
    spec: sparse_ops.SparseSpec
    qk_norm: Callable
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def _windows(self, span, rows):
        """(b, L, lanes) keys from position 0 -> (b, rows, lanes): the
        compressed rows 0..rows-1 (zeros where a window passes L)."""
        sp = self.spec
        at = sp.stride * jnp.arange(rows)[:, None] + jnp.arange(sp.kernel)
        span = jnp.pad(span, ((0, 0), (0, max(
            sp.stride * (rows - 1) + sp.kernel - span.shape[1], 0)), (0, 0)))
        return sparse_ops.compress(span[:, at])

    @nn.compact
    def __call__(self, x, *, decode: bool = False, attn_start=None,
                 page_table=None, kv_lengths=None, real_lengths=None):
        b, s, d = x.shape
        h, kvh, hd, sp = (self.num_heads, self.kv_heads, self.head_dim,
                          self.spec.check())
        dense = functools.partial(
            nn.DenseGeneral, dtype=self.dtype, param_dtype=self.param_dtype,
            use_bias=False)
        qg = dense((h, 2 * hd), name="q")(x)
        q, gate = qg[..., :hd], qg[..., hd:]
        kv = dense((2, kvh, hd), name="kv")(x)
        q = self.qk_norm(name="q_norm")(q)
        k, v = self.qk_norm(name="k_norm")(kv[:, :, 0]), kv[:, :, 1]
        start = jnp.zeros((b,), jnp.int32) if attn_start is None \
            else attn_start.astype(jnp.int32)
        flat = lambda t: t.reshape(b, s, kvh * hd)
        if not decode or (page_table is None and self.is_initializing()):
            if decode:   # the flat cache's leaves, shaped by this call
                self._leaves(b, s, k.dtype)
            blocks = -(-s // sp.block)
            index = self._windows(flat(k), blocks * sp.rows)
            out, _ = sparse_ops.sparse_attention_reference(
                q, k, v, index.reshape(b, -1, kvh, hd),
                jnp.broadcast_to(jnp.arange(s), (b, s)), start, sp)
        elif page_table is None:
            out = self._flat_decode(q, flat(k), flat(v), start)
        else:
            out = self._through_pages(q, flat(k), flat(v), start,
                                      page_table, kv_lengths, real_lengths)
        out = out * nn.sigmoid(gate)
        return dense(d, axis=(-2, -1), name="out")(out)

    def _leaves(self, b, s, dtype):
        wide = self.kv_heads * self.head_dim
        leaf = lambda name, rows: self.variable(
            "cache", name, jnp.zeros, (b, rows, wide), dtype)
        return (leaf("cached_key", s), leaf("cached_value", s),
                leaf("cached_index", s // self.spec.stride),
                self.variable("cache", "cache_index",
                              lambda: jnp.zeros((), jnp.int32)),
                self.variable("cache", "sparse_stats", jnp.zeros, (b, 2),
                              jnp.int32))

    def _flat_decode(self, q, k, v, start):
        """The s tokens at the flat cache's cursor: written, the compressed
        rows made again from the whole span, the plain form over it."""
        b, s, h, hd = q.shape
        sp, kvh = self.spec, self.kv_heads
        ck, cv, ci, cursor, _ = self._leaves(b, s, k.dtype)
        cur, span = cursor.value, ck.value.shape[1]
        if span % sp.block:
            raise ValueError(
                f"a flat cache of {span} positions is not whole blocks of "
                f"{sp.block}")
        ck.value = lax.dynamic_update_slice(
            ck.value, k.astype(ck.value.dtype), (0, cur, 0))
        cv.value = lax.dynamic_update_slice(
            cv.value, v.astype(cv.value.dtype), (0, cur, 0))
        ci.value = self._windows(ck.value, ci.value.shape[1])
        cursor.value = cur + s
        out, _ = sparse_ops.sparse_attention_reference(
            q, ck.value.reshape(b, span, kvh, hd),
            cv.value.reshape(b, span, kvh, hd),
            ci.value.reshape(b, -1, kvh, hd),
            jnp.broadcast_to(cur + jnp.arange(s), (b, s)), start, sp)
        return out

    def _through_pages(self, q, k, v, start, page_table, kv_lengths,
                       real_lengths):
        """Pools of pages through `page_table`, the s tokens at slot-local
        positions `kv_lengths + [0, s)`."""
        b, s, h, hd = q.shape
        sp, kvh = self.spec, self.kv_heads
        ck, cv, ci, _, stats = self._leaves(b, s, k.dtype)
        bs, mb = ck.value.shape[1], page_table.shape[1]
        if bs != sp.block:
            raise ValueError(
                f"the engine's page ({bs}) is the selection's block "
                f"({sp.block})")
        pos0 = jnp.asarray(kv_lengths, jnp.int32)
        positions = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)

        def page_of(col):
            # the clamp keeps a retired slot (page row 0, length pinned)
            # inside the table; a live slot never reaches it
            return jnp.take_along_axis(
                page_table, jnp.clip(col, 0, mb - 1), axis=1)

        blk, off = page_of(positions // bs), positions % bs
        keys = ck.value.at[blk, off].set(k.astype(ck.value.dtype))
        values = cv.value.at[blk, off].set(v.astype(cv.value.dtype))
        ck.value, cv.value = keys, values
        # the compressed rows whose window ends among these tokens, their
        # keys read back through the table (a window reaches behind a chunk)
        real = jnp.full((b,), s, jnp.int32) if real_lengths is None \
            else jnp.asarray(real_lengths, jnp.int32)
        j, due = sparse_ops.due_rows(sp, pos0, pos0 + real,
                                     -(-s // sp.stride) + 1)
        at = sp.stride * j[..., None] + jnp.arange(sp.kernel)  # (b, r, ker)
        flat_at = at.reshape(b, -1)
        window = keys[page_of(flat_at // bs), flat_at % bs].reshape(
            *at.shape, kvh * hd)
        row_page = jnp.where(due, page_of(j // sp.rows), 0)
        index = ci.value.at[row_page, jnp.where(due, j % sp.rows, 0)].set(
            sparse_ops.compress(window))
        ci.value = index
        if s == 1:
            with jax.named_scope("sparse_select"):
                pages, tokens, held = sparse_ops.sparse_select(
                    q[:, 0], index, page_table, pos0, start, spec=sp,
                    kv_heads=kvh)
            walked = jnp.sum(tokens // bs + 1, axis=1)
            stats.value = stats.value + jnp.stack(
                [walked, kvh * held], axis=1).astype(jnp.int32)
            out = sparse_ops.sparse_walk(
                q[:, 0], keys, values, pages, tokens,
                jnp.broadcast_to((start % bs)[:, None], tokens.shape))
            return out[:, None]
        outs = []
        for i in range(b):   # a chunk is one sequence's (the engine's b = 1)
            rows = jnp.take(index, page_table[i], axis=0).reshape(
                -1, kvh, hd)
            qi = q[i].reshape(s, kvh, h // kvh, hd)
            with jax.named_scope("sparse_select"):
                picked = sparse_ops.prefill_selection(
                    qi, rows, positions[i], start[i], sp)
            outs.append(sparse_ops.sparse_prefill(
                qi, keys, values, picked, page_table[i], pos0[i],
                block=bs).reshape(s, h, hd))
        return jnp.stack(outs)


_LANES = 128
# Cached positions a block of the several-token paged path expands and scores
# at a time (`LatentAttention._span_attention`).
_SPAN_TOKENS = 1024


class LatentAttention(nn.Module):
    """Multi-head latent attention: one cached row `[RMSNorm(c) | k_rope]` a
    token and layer, un-absorbed over several tokens, absorbed (the kernel
    `paged_decode_mla`) for one token a slot through pages; the equations
    and the two paths are in models/mla_lm.py's docstring, whose `MLALM`
    runs this class in every layer as `HybridLM` runs it for a 'T'."""

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
    # "head": every head's output times sigmoid(x W_z)[h], one scalar a head
    out_gate: Optional[str] = None

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
        if self.out_gate == "head":
            out = out * nn.sigmoid(
                nn.Dense(h, name="gate", **kw)(x))[..., None]
        elif self.out_gate is not None:
            raise ValueError(f"out_gate {self.out_gate!r}: want 'head'")
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
    # 'K' (its heads are `gdn_value_heads` of `gdn_key_dim` /
    # `gdn_value_dim`): the safe gate's lower bound
    kda_lower_bound: float = -5.0
    # 'T' (its heads are `num_heads`, its rotary lanes `rope_dim`)
    nope_dim: int = 16
    v_dim: int = 16
    attn_latent_dim: int = 32
    # 'L' (its heads are `num_heads` of `head_dim`): the layer numbers its
    # decays follow, one a letter 'L' in pattern order, of `decay_layers`
    lightning_layers: tuple = ()
    decay_layers: int = 2
    # 'B' (heads as 'A'): the selection's sizes, ops/sparse_attention.py
    sparse: sparse_ops.SparseSpec = sparse_ops.SparseSpec()
    # 'D'
    mlp_dim: int = 128
    # '*', 'A', 'W'
    num_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    # 'W': keys a query attends, its own among them
    window: int = 0
    # '*', 'W': a paged chunk's attention, "xla" or "kernel"
    # (models/vit.py SelfAttention.paged_prefill)
    attn_prefill: str = "xla"
    # 'A'
    rope_dim: int = 8
    rope_theta: float = 10000.0
    # 'E', 'Q', 'U', 'R' ('E' alone has a latent, 'R' no shared expert)
    num_experts: int = 16
    top_k: int = 3
    latent_dim: int = 32
    expert_dim: int = 48
    shared_dim: int = 96
    experts_held: int = 4
    expert_offset: int = 0
    routed_scaling: float = 1.0
    # 'U': the router keeps `topk_group` of `n_group` groups of experts
    n_group: int = 1
    topk_group: int = 1
    norm_eps: float = 1e-5
    # every RMSNorm of the residual stream and of 'A' scales by 1 + w
    norm_plus_one: bool = False
    tie_embeddings: bool = False
    # muP scalars on the stream: the embedding's output, every residual
    # branch and the head's input times these
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    head_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # what the serving engines ask a model: how positions enter (here the
    # recurrent layers carry them) and whether a sequence has state that
    # pages cannot hold
    pos_emb: str = "none"
    recurrent: bool = True
    axis_name: Optional[str] = None  # registry uniformity

    def cache_spec(self) -> dict:
        """The page group of each layer, as the fields of serve/kv_pages.py
        `CacheSpec`: the 'W' layers' pages are the window group's."""
        return {"window": self.window, "window_layers": tuple(
            f"attn{i}" for i, kind in enumerate(self.pattern)
            if kind == "W")}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 attn_start=None, page_table=None, kv_lengths=None,
                 real_lengths=None):
        """tokens (batch, seq) int32 -> logits (batch, seq, vocab_size) in
        the compute dtype. `decode`, `attn_start`, `page_table` and
        `kv_lengths` as in models/lm.py TransformerLM (`page_table` a dict
        of tables by page group where `cache_spec` names more than the
        global one: {"global": ..., "window": ...}); `real_lengths`
        (batch,): the tokens of a paged call that are real, the rest right
        padding the recurrent layers do not advance over; a call of several
        tokens then returns the logits of each sequence's LAST REAL position
        alone, (batch, 1, vocab_size): what a prompt's chunk is asked for
        (the head over a 2,048-token chunk's every row is 1.2 TFLOP at
        73,448 rows)."""
        del train
        if set(self.pattern) - set("MSGKLEQURD*AWBT") or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: want a string of 'M', 'S', 'G', "
                "'K', 'L', 'E', 'Q', 'U', 'R', 'D', '*', 'A', 'W', 'B', 'T'")
        if set("ALWT") & set(self.pattern) and self.pos_emb != "rope":
            raise ValueError(
                "'A', 'L', 'W' and 'T' rotate q and k: want pos_emb='rope'")
        if "W" in self.pattern and self.window < 1:
            raise ValueError(f"'W' attends a window of keys: {self.window}")
        if self.pattern[0] == "R":
            raise ValueError("'R' routes on the input of the mixer before it")
        if self.pattern.count("L") != len(self.lightning_layers):
            raise ValueError(
                f"lightning_layers {self.lightning_layers} numbers the 'L' "
                f"layers of {self.pattern!r}, one each")
        if (page_table is not None or attn_start is not None) and not decode:
            raise ValueError("page_table / attn_start are decode features")
        if tokens.shape[1] > self.max_len:
            raise ValueError(
                f"sequence {tokens.shape[1]} exceeds max_len {self.max_len}")
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        embed = nn.Embed(self.vocab_size, self.hidden_dim, name="tok_embed",
                         **kw)
        x = embed(tokens)
        if self.embed_scale != 1.0:
            x = x * self.embed_scale
        norm = functools.partial(RMSNorm, self.norm_eps,
                                 plus_one=self.norm_plus_one, **kw)
        paged = page_table is not None
        tables = page_table if isinstance(page_table, dict) \
            else {"global": page_table, "window": page_table}
        # '*' and 'W': heads of `head_dim` where the width does not give it
        own_head = {} if self.hidden_dim == self.num_heads * self.head_dim \
            else {"head_dim": self.head_dim}
        recur = dict(decode=decode, attn_start=attn_start, paged=paged,
                     real_lengths=real_lengths)
        if "L" in self.pattern:
            # 'L' rotates at the token's position: slot-local under pages,
            # else from a cursor of the model's own in the flat cache
            s = tokens.shape[1]
            if paged:
                positions = jnp.asarray(kv_lengths, jnp.int32)[:, None] \
                    + jnp.arange(s, dtype=jnp.int32)
            else:
                positions = jnp.arange(s, dtype=jnp.int32)
                if decode:
                    cursor = self.variable(
                        "cache", "cache_index",
                        lambda: jnp.zeros((), jnp.int32))
                    if not self.is_initializing():
                        positions = cursor.value + positions
                        cursor.value = cursor.value + s
        lightning = iter(self.lightning_layers)
        for i, kind in enumerate(self.pattern):
            y = norm(name=f"norm{i}")(x)
            if kind not in "EQURD":
                mixer_in = y   # what a router placed before the mixer reads
            if kind == "L":
                y = LightningMixer(
                    self.num_heads, self.head_dim, next(lightning),
                    self.decay_layers, self.rope_theta, self.chunk_size,
                    self.norm_eps, name=f"mamba{i}", **kw,
                )(y, positions=positions, **recur)
            elif kind == "B":
                y = SparseAttention(
                    self.num_heads, self.kv_heads, self.head_dim,
                    self.sparse, norm, name=f"attn{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  page_table=tables["global"], kv_lengths=kv_lengths,
                  real_lengths=real_lengths)
            elif kind == "M":
                y = Mamba2Mixer(
                    self.mamba_heads, self.mamba_head_dim, self.ssm_state,
                    self.ssm_groups, self.conv_kernel, self.chunk_size,
                    self.norm_eps, name=f"mamba{i}", **kw,
                )(y, **recur)
            elif kind == "S":
                y = Mamba1Mixer(
                    self.mamba_inner, self.ssm_state, self.dt_rank,
                    self.conv_kernel, self.norm_eps, name=f"mamba{i}", **kw,
                )(y, **recur)
            elif kind == "G":
                y = GatedDeltaMixer(
                    self.gdn_key_heads, self.gdn_value_heads,
                    self.gdn_key_dim, self.gdn_value_dim, self.conv_kernel,
                    self.norm_eps, name=f"mamba{i}", **kw,
                )(y, **recur)
            elif kind == "K":
                y = KimiDeltaMixer(
                    self.gdn_value_heads, self.gdn_key_dim,
                    self.gdn_value_dim, self.conv_kernel,
                    self.kda_lower_bound, self.norm_eps, name=f"mamba{i}",
                    **kw,
                )(y, **recur)
            elif kind == "T":
                y = LatentAttention(
                    self.num_heads, self.nope_dim, self.rope_dim, self.v_dim,
                    self.attn_latent_dim, self.rope_theta,
                    norm_eps=self.norm_eps, out_gate="head",
                    name=f"attn{i}", **kw,
                )(y, decode=decode, attn_start=attn_start,
                  page_table=tables["global"], kv_lengths=kv_lengths)
            elif kind == "D":
                y = GatedMLP(self.mlp_dim, name=f"mlp{i}", **kw)(y)
            elif kind == "U":
                y = GatedMoE(
                    self.num_experts, self.top_k, self.expert_dim,
                    self.shared_dim, self.experts_held, self.expert_offset,
                    self.routed_scaling, n_group=self.n_group,
                    topk_group=self.topk_group, name=f"moe{i}", **kw,
                )(y, decode=decode)
            elif kind == "Q":
                y = GatedMoE(
                    self.num_experts, self.top_k, self.expert_dim,
                    self.shared_dim, self.experts_held, self.expert_offset,
                    self.routed_scaling, router="softmax", shared_gate=True,
                    name=f"moe{i}", **kw,
                )(y, decode=decode)
            elif kind == "R":
                y = GatedMoE(
                    self.num_experts, self.top_k, self.expert_dim, 0,
                    self.experts_held, self.expert_offset,
                    self.routed_scaling, router="softmax",
                    activation="relu", name=f"moe{i}", **kw,
                )(y, decode=decode, router_input=mixer_in)
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
                  page_table=tables["global"], kv_lengths=kv_lengths)
            else:   # '*', or 'W': rotary, a window, the window group's pages
                win = kind == "W"
                y = SelfAttention(
                    self.num_heads, causal=True, rope=win,
                    kv_heads=self.kv_heads, use_bias=False,
                    rope_theta=self.rope_theta,
                    window=self.window if win else None,
                    paged_prefill=self.attn_prefill, name=f"attn{i}",
                    **own_head, **kw,
                )(y, decode=decode, attn_start=attn_start,
                  page_table=tables["window" if win else "global"],
                  kv_lengths=kv_lengths,
                  real_lengths=real_lengths if paged else None)
            if self.residual_scale != 1.0:
                y = y * self.residual_scale
            x = x + y
        if real_lengths is not None and paged and x.shape[1] > 1:
            last = jnp.clip(real_lengths - 1, 0, x.shape[1] - 1)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        x = norm(name="norm_f")(x)
        if self.head_scale != 1.0:
            x = x * self.head_scale
        if self.tie_embeddings:
            return embed.attend(x)
        return nn.Dense(self.vocab_size, use_bias=False, name="lm_head",
                        **kw)(x)
