"""Plain reference for `perf/configs/jamba2_3b.json`: the Jamba layer
equations in float32 `jax.numpy`, precision "highest".

Layer i is two residual sub-layers, `x + mixer_i(RMSNorm(x))` then
`x + W_down(silu(W_gate RMSNorm'(x)) * W_up RMSNorm'(x))`; the mixer is
causal attention where `i % attn_layer_period == attn_layer_offset` and a
Mamba-1 mixer elsewhere; a final RMSNorm and the head tied to the embedding.
No kernel, no cache, no batching, nothing imported from the program: the
recurrence is a sequential `lax.scan` over positions on a (channels, state)
tensor, attention a dense masked softmax over 20 query heads that share one
KV head. Parameters come as the flax tree the program lays out (sub-layer
2i is layer i's mixer, 2i + 1 its MLP), filled by the benchmark's weights;
a weight is upcast where it is used, so the float32 copy of the model never
stands whole.

    [u, z]    = x W_in
    u         = silu(causal_conv(u) + b_conv)
    [r, B, C] = u W_x;   r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)
    dt        = softplus(r W_dt + b_dt);   A = -exp(A_log)
    h_t[c,n]  = exp(dt_t[c] A[c,n]) h_{t-1}[c,n] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n h_t[c,n] C_t[n] + D[c] u_t[c]
    out       = (y * silu(z)) W_out

No positional embedding in attention (the family applies none).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks

F32 = jnp.float32


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def mamba(x, p, cfg: dict, quant=None):
    """Mamba-1 mixer, one position at a time. x (b, s, d) float32."""
    b, s, _ = x.shape
    c = cfg["mamba_expand"] * cfg["hidden_size"]
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    u, z = jnp.split(
        blocks.mm("bsd,de->bse", x, p["in_proj"]["kernel"], quant), 2, -1)
    # causal depthwise conv over time, zeros before position 0
    w, bias = p["conv_kernel"].astype(F32), p["conv_bias"].astype(F32)
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, i:i + s] * w[i] for i in range(k)) + bias)
    low, bm, cm = jnp.split(
        blocks.mm("bsc,ce->bse", u, p["x_proj"]["kernel"], quant),
        [r, r + n], axis=-1)
    low = rms_norm(low, p["dt_norm"]["scale"], eps)
    bm = rms_norm(bm, p["b_norm"]["scale"], eps)
    cm = rms_norm(cm, p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(
        blocks.mm("bsr,rc->bsc", low, p["dt_proj"]["kernel"], quant)
        + p["dt_proj"]["bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))                      # (c, n)

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t, precision=blocks.HIGHEST)

    _, ys = jax.lax.scan(
        step, jnp.zeros((b, c, n), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (u, dt, bm, cm)))
    y = jnp.moveaxis(ys, 0, 1) + u * p["D"].astype(F32)
    return blocks.mm("bsc,cd->bsd", y * jax.nn.silu(z),
                     p["out_proj"]["kernel"], quant)


def attention(x, p, cfg: dict, quant=None):
    """Causal attention, every query head on the KV head of its group, no
    positional embedding, no bias."""
    s = x.shape[1]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = blocks.mm("bsd,dhk->bshk", x, p["q"]["kernel"], quant)
    kv = blocks.mm("bsd,dckv->bsckv", x, p["kv"]["kernel"], quant)
    k = jnp.repeat(kv[:, :, 0], heads // kvh, axis=2)
    v = jnp.repeat(kv[:, :, 1], heads // kvh, axis=2)
    scores = blocks.mm("bqhk,bshk->bhqs", q, k, quant) \
        / jnp.sqrt(float(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = blocks.mm("bhqs,bshk->bqhk", probs, v, quant)
    return blocks.mm("bqhk,hkd->bqd", out, p["out"]["kernel"], quant)


def mlp(x, p, quant=None):
    gate = blocks.mm("bsd,df->bsf", x, p["gate"]["kernel"], quant)
    up = blocks.mm("bsd,df->bsf", x, p["up"]["kernel"], quant)
    return blocks.mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                     p["down"]["kernel"], quant)


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32."""
    del remat
    eps = cfg["rms_norm_eps"]
    emb = params["tok_embed"]["embedding"]
    x = emb.astype(F32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        y = rms_norm(x, params[f"norm{2 * i}"]["scale"], eps)
        if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
            x = x + attention(y, params[f"attn{2 * i}"], cfg, quant)
        else:
            x = x + mamba(y, params[f"mamba{2 * i}"], cfg, quant)
        y = rms_norm(x, params[f"norm{2 * i + 1}"]["scale"], eps)
        x = x + mlp(y, params[f"mlp{2 * i + 1}"], quant)
    x = rms_norm(x, params["norm_f"]["scale"], eps)
    return blocks.mm("bsd,vd->bsv", x, emb, quant)


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
