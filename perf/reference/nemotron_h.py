"""Plain reference for `perf/configs/nemotron3_super_ep4.json`: the Nemotron-H
layer equations in float32 `jax.numpy`, precision "highest".

Every layer is `x + mixer(RMSNorm(x))`, one mixer a layer by the pattern
string (`M` Mamba-2, `E` LatentMoE, `*` attention), a final RMSNorm and the
untied head. No kernel, no cache, no batching, nothing imported from the
program: the recurrence is a sequential `lax.scan` over positions (not the
chunked form the program's prefill uses), the experts a loop over the held
ones in blocks (so one float32 expert layer, 2.8 GB, is never whole in
memory), attention a dense masked softmax. Parameters come as the flax tree
the program lays out, filled by the benchmark's weights.

Departures from the published model, as the configuration file states them:
no multi-token-prediction module; no positional embedding in attention (the
family applies none); only experts `expert_offset` .. `+ n_routed_experts_held`
add to an expert layer's output (a chip's share: the router scores and picks
over all `n_routed_experts`); `vocab_size` rows of the head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks

F32 = jnp.float32


def rms_norm(x, scale, eps: float, groups: int = 1):
    shaped = x.reshape(*x.shape[:-1], groups, -1)
    shaped = shaped * jax.lax.rsqrt(
        jnp.mean(jnp.square(shaped), axis=-1, keepdims=True) + eps)
    return shaped.reshape(x.shape) * scale.astype(F32)


def mamba(x, p, cfg: dict, quant=None):
    """Mamba-2 mixer, one position at a time. x (b, s, d) float32."""
    b, s, _ = x.shape
    h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner, gn = h * hp, g * n
    proj = blocks.mm("bsd,de->bse", x, p["in_proj"]["kernel"], quant)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * gn], axis=-1)
    # causal depthwise conv over time, zeros before position 0
    w, bias = p["conv_kernel"].astype(F32), p["conv_bias"].astype(F32)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + s] * w[i] for i in range(k)) + bias
    xbc = jax.nn.silu(xbc)
    xs, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
    xs = xs.reshape(b, s, h, hp)
    bm = jnp.repeat(bm.reshape(b, s, g, n), h // g, axis=2)   # a head's B
    cm = jnp.repeat(cm.reshape(b, s, g, n), h // g, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))       # (b, s, h)
    a = -jnp.exp(p["A_log"].astype(F32))

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=blocks.HIGHEST)

    _, ys = jax.lax.scan(
        step, jnp.zeros((b, h, hp, n), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, bm, cm)))
    y = jnp.moveaxis(ys, 0, 1) + xs * p["D"].astype(F32)[:, None]
    y = y.reshape(b, s, inner) * jax.nn.silu(z)
    y = rms_norm(y, p["norm"]["scale"], cfg["layer_norm_epsilon"], groups=g)
    return blocks.mm("bse,ed->bsd", y, p["out_proj"]["kernel"], quant)


def route(x, p, cfg: dict, quant=None):
    """(tokens, n_routed_experts) combine weights, zero where not picked."""
    s = jax.nn.sigmoid(blocks.mm("nd,de->ne", x, p["router"]["kernel"],
                                 quant))
    sel = s + p["e_score_correction_bias"].astype(F32)
    _, picks = jax.lax.top_k(sel, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picks, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(picks, s.shape[-1], dtype=F32)
                   * w[..., None], axis=1)


def shared_expert(x, p, quant=None):
    hid = blocks.mm("nd,df->nf", x, p["shared_in"]["kernel"], quant)
    return blocks.mm("nf,fd->nd", jnp.square(jax.nn.relu(hid)),
                     p["shared_out"]["kernel"], quant)


def routed_experts(x, p, cfg: dict, quant=None, block: int = 16):
    """What the held experts add: (sum over held picks of w_e f_e(u)) W_up,
    the held experts taken `block` at a time."""
    held, off = cfg["n_routed_experts_held"], cfg["expert_offset"]
    gates = route(x, p, cfg, quant)[:, off:off + held]            # (n, held)
    u = blocks.mm("nd,dl->nl", x, p["down"]["kernel"], quant)
    block = min(block, held)
    while held % block:
        block -= 1
    nb = held // block

    def one(acc, inp):
        w1, w2, gate = inp           # (block, l, f), (block, f, l), (block, n)
        hid = jnp.square(jax.nn.relu(blocks.mm("nl,elf->enf", u, w1, quant)))
        out = blocks.mm("enf,efl->enl", hid, w2, quant)
        return acc + jnp.einsum("en,enl->nl", gate, out,
                                precision=blocks.HIGHEST), None

    lat = u.shape[-1]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["expert_w1"].reshape(nb, block, lat, -1),
        p["expert_w2"].reshape(nb, block, -1, lat),
        gates.T.reshape(nb, block, -1)))
    return blocks.mm("nl,ld->nd", acc, p["up"]["kernel"], quant)


def moe(x, p, cfg: dict, quant=None):
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    return (routed_experts(xf, p, cfg, quant)
            + shared_expert(xf, p, quant)).reshape(b, s, d)


def attention(x, p, cfg: dict, quant=None):
    """Causal grouped-query attention, no positional embedding, no bias."""
    s = x.shape[1]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = blocks.mm("bsd,dhk->bshk", x, p["q"]["kernel"], quant)
    kv = blocks.mm("bsd,dckv->bsckv", x, p["kv"]["kernel"], quant)
    k = jnp.repeat(kv[:, :, 0], heads // kvh, axis=2)  # head i: KV i // group
    v = jnp.repeat(kv[:, :, 1], heads // kvh, axis=2)
    scores = blocks.mm("bqhk,bshk->bhqs", q, k, quant) \
        / jnp.sqrt(float(cfg["head_dim"]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = blocks.mm("bhqs,bshk->bqhk", probs, v, quant)
    return blocks.mm("bqhk,hkd->bqd", out, p["out"]["kernel"], quant)


MIXERS = {"M": ("mamba", mamba), "E": ("moe", moe), "*": ("attn", attention)}


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32."""
    del remat
    eps = cfg["layer_norm_epsilon"]
    x = params["tok_embed"]["embedding"].astype(F32)[tokens]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        name, mixer = MIXERS[kind]
        y = rms_norm(x, params[f"norm{i}"]["scale"], eps)
        x = x + mixer(y, params[f"{name}{i}"], cfg, quant)
    x = rms_norm(x, params["norm_f"]["scale"], eps)
    return blocks.mm("bsd,dv->bsv", x, params["lm_head"]["kernel"], quant)


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
