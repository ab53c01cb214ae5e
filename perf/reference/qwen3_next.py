"""Plain reference for `perf/configs/qwen3next_80b_ep4.json`: the Qwen3-Next
layer equations in float32 `jax.numpy`, precision "highest".

Layer i of `layers_run` is two residual sub-layers, `x + mixer_i(N(x))` then
`x + experts_i(N(x))`, N the zero-centred RMSNorm `x / rms(x) * (1 + w)`; the
mixer is gated attention where `(i + 1) % full_attention_interval == 0` and a
Gated DeltaNet mixer elsewhere; a final N and the untied head. No kernel, no
cache, no batching, nothing imported from the program: the recurrence is a
sequential `lax.scan` over POSITIONS on a (key_dim, value_dim) state a value
head (what the program's chunked scan is checked against), attention a
masked softmax over the whole row of keys, a block of queries at a time,
16 query heads on 2 KV heads. Parameters come as the flax tree the program
lays out (sub-layer 2i is layer i's mixer, 2i + 1 its expert layer), filled
by the benchmark's weights; a weight is upcast where it is used, so the
float32 copy of the model never stands whole.

    Gated DeltaNet
    [q|k|v|z] = x W_in;  [b|a] = x W_ba;  [q|k|v] = silu(causal_conv(.))
    q, k      = l2norm(q), l2norm(k) a head;  q = q / sqrt(key_dim)
    beta      = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    S_t       = exp(g_t) S_{t-1};  d = beta_t (v_t - S_t^T k_t)
    S_t       = S_t + k_t d^T;     o_t = S_t^T q_t        a value head, its
                                   key head h // (value heads / key heads)
    out       = (rmsnorm_head(o) w * silu(z)) W_out

    gated attention
    [query | gate] = x W_q a head;  k, v = x W_kv;  q, k = N(q), N(k) a head
    rotary (pairs i, i + r/2) on the first r = partial_rotary_factor x
    head_dim lanes;  out = (softmax(q k / sqrt(head_dim)) v * sigmoid(gate)) W_o

    experts
    p = softmax(x W_r) over all num_experts; the num_experts_per_tok largest,
    renormalised to sum 1;  sum over the picks HELD here
    [expert_offset, + num_experts_held) of w_e W_down(silu(W_gate x) * W_up x)
    + sigmoid(x w_s) * shared(x)

Departures from the published model, as the configuration file states them:
`layers_run` of the layers, a share of the experts and of the vocabulary, no
multi-token-prediction module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks

F32 = jnp.float32


def norm(x, p, eps: float):
    """Zero-centred RMSNorm over the last dim."""
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * (1.0 + p["weight"].astype(F32))


def gated_delta_net(x, p, cfg: dict, quant=None):
    """The Gated DeltaNet mixer, one position at a time. x (b, s, d)."""
    b, s, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kc = cfg["linear_conv_kernel_dim"]
    keys, values = hk * dk, hv * dv
    proj = blocks.mm("bsd,de->bse", x, p["in_proj"]["kernel"], quant)
    qkv, z = proj[..., :2 * keys + values], proj[..., 2 * keys + values:]
    beta, a = jnp.split(
        blocks.mm("bsd,de->bse", x, p["ba_proj"]["kernel"], quant), 2, -1)
    # causal depthwise conv over time, zeros before position 0, no bias
    w = p["conv_kernel"].astype(F32)
    padded = jnp.pad(qkv, ((0, 0), (kc - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + s] * w[i] for i in range(kc)))
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    rep = hv // hk
    q = jnp.repeat(unit(qkv[..., :keys].reshape(b, s, hk, dk)), rep, 2) \
        * dk ** -0.5
    k = jnp.repeat(unit(qkv[..., keys:2 * keys].reshape(b, s, hk, dk)),
                   rep, 2)
    v = qkv[..., 2 * keys:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(beta)
    g = -jnp.exp(p["A_log"].astype(F32)) \
        * jax.nn.softplus(a + p["dt_bias"].astype(F32))

    def step(state, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t,
                          precision=blocks.HIGHEST)
        d = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=blocks.HIGHEST)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, hv, dk, dv), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                               # (b, s, hv, dv)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"]) \
        * p["norm"]["scale"].astype(F32)
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv))
    return blocks.mm("bse,ed->bsd", o.reshape(b, s, values),
                     p["out_proj"]["kernel"], quant)


def gated_attention(x, p, cfg: dict, quant=None, block: int = 512):
    """Causal grouped-query attention with head norms, rotary on part of a
    head and a sigmoid output gate; a block of queries at a time."""
    b, s, _ = x.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    r = int(cfg["partial_rotary_factor"] * hd)
    pos = jnp.arange(s)
    qg = blocks.mm("bsd,dhk->bshk", x, p["q"]["kernel"], quant)
    q, gate = qg[..., :hd], qg[..., hd:]
    kv = blocks.mm("bsd,dckv->bsckv", x, p["kv"]["kernel"], quant)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = norm(q, p["q_norm"], eps), norm(k, p["k_norm"], eps)
    turn = lambda t: jnp.concatenate(
        [blocks.rope(t[..., :r], pos, float(cfg["rope_theta"])),
         t[..., r:]], axis=-1)
    q, k = turn(q), turn(k)
    k = jnp.repeat(k, heads // kvh, axis=2)    # head i reads KV i // group
    v = jnp.repeat(v, heads // kvh, axis=2)
    block = min(block, s)
    while s % block:
        block -= 1

    def rows(_, inp):
        qb, qpos = inp                                # (b, block, h, hd)
        scores = blocks.mm("bqhk,bshk->bhqs", qb, k, quant) \
            / jnp.sqrt(float(hd))
        seen = pos[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, blocks.mm("bhqs,bshk->bqhk", probs, v, quant)

    _, out = jax.lax.scan(rows, None, (
        jnp.moveaxis(q.reshape(b, s // block, block, heads, hd), 1, 0),
        pos.reshape(s // block, block)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads, hd)
    return blocks.mm("bqhk,hkd->bqd", out * jax.nn.sigmoid(gate),
                     p["out"]["kernel"], quant)


def swiglu(x, p, quant=None):
    hid = jax.nn.silu(blocks.mm("nd,df->nf", x, p["gate"]["kernel"], quant)) \
        * blocks.mm("nd,df->nf", x, p["up"]["kernel"], quant)
    return blocks.mm("nf,fd->nd", hid, p["down"]["kernel"], quant)


def route(x, p, cfg: dict, quant=None):
    """(picks (n, k) int over ALL experts, weights (n, k) float32)."""
    probs = jax.nn.softmax(
        blocks.mm("nd,de->ne", x, p["router"]["kernel"], quant), axis=-1)
    w, picks = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    return picks, w / jnp.sum(w, axis=-1, keepdims=True)


def routed_experts(x, p, cfg: dict, quant=None, rows: int = 512):
    """What the held experts add: sum over a token's picks that land in
    [expert_offset, + num_experts_held) of w_e expert_e(x). The (token,
    pick) pairs are sorted by held expert (the others last, and dropped);
    window j of `rows` sorted pairs belongs to ONE expert, and
    ceil(n k / rows) + held windows cover any routing."""
    n, held, off = x.shape[0], cfg["num_experts_held"], cfg["expert_offset"]
    picks, w = route(x, p, cfg, quant)
    k = picks.shape[1]
    local = picks - off
    flat = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    token, gate = order // k, w.reshape(-1)[order]
    counts = jnp.bincount(flat, length=held + 1)[:held]
    first = jnp.cumsum(counts) - counts
    windows = -(-counts // rows)
    w_end = jnp.cumsum(windows)

    def one(acc, j):
        e = jnp.minimum(jnp.searchsorted(w_end, j, side="right"), held - 1)
        lo = first[e] + (j - (w_end[e] - windows[e])) * rows
        idx = lo + jnp.arange(rows)
        live = (idx < first[e] + counts[e]) & (j < w_end[-1])
        idx = jnp.minimum(idx, n * k - 1)
        tok = token[idx]
        xi = x[tok]
        hid = jax.nn.silu(blocks.mm("rd,df->rf", xi, p["expert_gate"][e],
                                    quant)) \
            * blocks.mm("rd,df->rf", xi, p["expert_up"][e], quant)
        out = blocks.mm("rf,fd->rd", hid, p["expert_down"][e], quant)
        return acc.at[tok].add(
            jnp.where(live, gate[idx], 0.0)[:, None] * out), None

    n_windows = -(-(n * k) // rows) + held
    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_windows))
    return acc


def experts(x, p, cfg: dict, quant=None):
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    shared = swiglu(xf, p["shared"], quant) * jax.nn.sigmoid(
        blocks.mm("nd,de->ne", xf, p["shared_expert_gate"]["kernel"], quant))
    return (routed_experts(xf, p, cfg, quant, rows=min(512, b * s))
            + shared).reshape(b, s, d)


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32."""
    del remat
    eps = cfg["rms_norm_eps"]
    x = params["tok_embed"]["embedding"].astype(F32)[tokens]
    for i in range(cfg["layers_run"]):
        y = norm(x, params[f"norm{2 * i}"], eps)
        if (i + 1) % cfg["full_attention_interval"] == 0:
            x = x + gated_attention(y, params[f"attn{2 * i}"], cfg, quant)
        else:
            x = x + gated_delta_net(y, params[f"mamba{2 * i}"], cfg, quant)
        y = norm(x, params[f"norm{2 * i + 1}"], eps)
        x = x + experts(y, params[f"moe{2 * i + 1}"], cfg, quant)
    x = norm(x, params["norm_f"], eps)
    return blocks.mm("bsd,dv->bsv", x, params["lm_head"]["kernel"], quant)


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
