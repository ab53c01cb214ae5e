"""Plain reference for `perf/configs/ling3_flash_ep4.json`: the language model
of Ling-3.0-flash-VL in float32 `jax.numpy`, precision "highest".

Layer i of `layers_run`: `h = x + mixer_i(N(x))`, `x' = h + ffn_i(N(h))`, N
the RMSNorm `x / rms(x) * w` (eps `rms_norm_eps`); a final N and the untied
head. The mixer is latent attention where `(i + 1) % layer_group_size == 0`
and Kimi Delta Attention elsewhere; the feed-forward a dense SwiGLU for
`i < first_k_dense_replace` and the expert layer after. No kernel, no cache,
no batching, nothing imported from the program: the recurrence is a
sequential `lax.scan` over POSITIONS on a (key_dim, value_dim) state a head
(the defining form: what the program's chunked scan and its decode step are
checked against), attention UN-absorbed (K and V expanded from the latent
for every position) with the causal softmax a block of queries at a time.
Parameters come as the flax tree the program lays out (sub-layer 2i is
layer i's mixer, 2i + 1 its feed-forward), filled by the benchmark's weights;
a weight is upcast where it is used.

    Kimi Delta Attention (arXiv:2510.26692)
    [q|k|v] = silu(causal_conv4(x W_in));  q, k = l2norm a head;  q / sqrt(dk)
    g      = kda_lower_bound * sigmoid(exp(A_log[h]) * (x W_f + dt_bias))
    beta   = sigmoid(x W_b) a head
    S_t    = diag(exp(g_t)) S_{t-1};  d = beta_t (v_t - S_t^T k_t)
    S_t    = S_t + k_t d^T;           o_t = S_t^T q_t
    out    = (rmsnorm_head(o) w * sigmoid(x W_z)[h]) W_out

    latent attention
    q = x W_q -> a head [q_nope | q_rope];  [c | k_rope] = x W_kv_a
    c = N(c);  q_rope, k_rope = RoPE(., pos), pairs (2i, 2i + 1), one k_rope
    for all heads;  [k_nope_h | v_h] = c W_kv_b
    score = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope), causal
    softmax;  out = concat_h(p v_h * sigmoid(x W_z)[h]) W_o

    experts
    s = sigmoid(x W_r) over all num_experts;  b = s + e_score_correction_bias
    a group of num_experts / n_group consecutive experts scores the sum of
    its two largest b; the topk_group best groups are kept; picks = the
    num_experts_per_tok largest b among their experts
    w = s_picks / sum s_picks * routed_scaling_factor
    sum over the picks HELD here [expert_offset, + num_experts_held) of
    w_e W_down(silu(W_gate x) * W_up x)  +  the shared expert, ungated

Departures from the published model, as the configuration file states them:
`layers_run` of the layers, a share of the experts and of the vocabulary; no
vision tower, no multi-token-prediction module; the swiglu limit lists are 0
in every layer run and are not read.

`state_reset` is the second control's switch (the first is `quant`): the
recurrent state and the conv's memory are zeroed at every position that is a
multiple of it and, with `reset_until`, lies before that position (a
prompt's length: its chunk boundaries alone, none at or after the first
served token), which is what a program that lost a slot's state between two
chunks of its prompt would compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks

F32 = jnp.float32


def norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def kimi_delta_attention(x, p, cfg: dict, quant=None, state_reset=None,
                         reset_until=None):
    """The KDA mixer of one sequence, one position at a time. x (s, d)."""
    s = x.shape[0]
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    dv, kc = dk, cfg["short_conv_kernel_size"]
    keys = h * dk
    qkv = blocks.mm("sd,de->se", x, p["in_proj"]["kernel"], quant)
    a = blocks.mm("sd,de->se", x, p["f_proj"]["kernel"], quant) \
        + p["dt_bias"].astype(F32)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"].astype(F32))[:, None] * a.reshape(s, h, dk))
    beta = jax.nn.sigmoid(
        blocks.mm("sd,dh->sh", x, p["b_proj"]["kernel"], quant))
    gate = jax.nn.sigmoid(
        blocks.mm("sd,dh->sh", x, p["z_proj"]["kernel"], quant))
    # causal depthwise conv over time, zeros before position 0 (and before
    # every reset), no bias
    w = p["conv_kernel"].astype(F32)
    pos = jnp.arange(s)
    if state_reset is None:
        since = pos
    else:  # positions since the last reset (`reset_until` may be traced)
        last = pos // state_reset * state_reset
        if reset_until is not None:
            last = jnp.minimum(
                last, (reset_until - 1) // state_reset * state_reset)
        since = pos - last
    padded = jnp.pad(qkv, ((kc - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        jnp.where((since >= kc - 1 - i)[:, None], padded[i:i + s], 0.0)
        * w[i] for i in range(kc)))
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = unit(qkv[:, :keys].reshape(s, h, dk)) * dk ** -0.5
    k = unit(qkv[:, keys:2 * keys].reshape(s, h, dk))
    v = qkv[:, 2 * keys:].reshape(s, h, dv)
    fresh = jnp.zeros((s,), bool) if state_reset is None else since == 0

    def step(state, inp):
        q_t, k_t, v_t, g_t, beta_t, fresh_t = inp
        state = jnp.where(fresh_t, 0.0, state) * jnp.exp(g_t)[..., None]
        read = jnp.einsum("hkv,hk->hv", state, k_t, precision=blocks.HIGHEST)
        d = beta_t[:, None] * (v_t - read)
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=blocks.HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), F32),
                        (q, k, v, g, beta, fresh))
    o = norm(o, p["norm"]["scale"], cfg["rms_norm_eps"]) * gate[..., None]
    return blocks.mm("se,ed->sd", o.reshape(s, h * dv),
                     p["out_proj"]["kernel"], quant)


def rope_pairs(x, positions, theta: float):
    """Rotate (s, h, d) by position, pairs (2i, 2i + 1), frequencies
    theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(x, p, cfg: dict, quant=None, block: int = 512):
    """Un-absorbed latent attention of one sequence with a gate a head.
    x (s, d) float32."""
    s = x.shape[0]
    lat, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    theta = float(cfg["rope_theta"])
    pos = jnp.arange(s)
    q = blocks.mm("sd,dhe->she", x, p["q"]["kernel"], quant)
    kv_a = blocks.mm("sd,de->se", x, p["kv_a"]["kernel"], quant)
    c = norm(kv_a[:, :lat], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_rope = rope_pairs(kv_a[:, None, lat:], pos, theta)        # (s, 1, r)
    kv = blocks.mm("sl,lhe->she", c, p["kv_b"], quant)
    heads = kv.shape[1]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads,
                                                   k_rope.shape[-1]))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], pos, theta)], -1)
    block = min(block, s)
    while s % block:
        block -= 1

    def rows(_, inp):
        qb, qpos = inp                                   # (block, h, e)
        scores = blocks.mm("qhe,khe->hqk", qb, k, quant) \
            / jnp.sqrt(float(q.shape[-1]))
        seen = pos[None, :] <= qpos[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return None, blocks.mm("hqk,khv->qhv", probs, v, quant)

    _, out = jax.lax.scan(rows, None, (
        q.reshape(s // block, block, *q.shape[1:]),
        pos.reshape(s // block, block)))
    gate = jax.nn.sigmoid(
        blocks.mm("sd,dh->sh", x, p["gate"]["kernel"], quant))
    return blocks.mm("shv,hvd->sd",
                     out.reshape(s, heads, -1) * gate[..., None],
                     p["out"]["kernel"], quant)


def swiglu(x, p, quant=None):
    hid = jax.nn.silu(blocks.mm("nd,df->nf", x, p["gate"]["kernel"], quant)) \
        * blocks.mm("nd,df->nf", x, p["up"]["kernel"], quant)
    return blocks.mm("nf,fd->nd", hid, p["down"]["kernel"], quant)


def route(x, p, cfg: dict, quant=None):
    """(picks (n, k) int over ALL experts, weights (n, k) float32) of the
    group-limited sigmoid router."""
    s = jax.nn.sigmoid(
        blocks.mm("nd,de->ne", x, p["router"]["kernel"], quant))
    b = s + p["e_score_correction_bias"].astype(F32)
    n, e = b.shape
    groups = cfg["n_group"]
    by_group = b.reshape(n, groups, e // groups)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    kept = jax.lax.top_k(score, cfg["topk_group"])[1]           # (n, kept)
    keep = jnp.any(jnp.arange(groups)[None, :, None] == kept[:, None, :], -1)
    b = jnp.where(keep[..., None], by_group, -jnp.inf).reshape(n, e)
    picks = jax.lax.top_k(b, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, picks, axis=-1)
    return picks, w / jnp.sum(w, axis=-1, keepdims=True) \
        * cfg["routed_scaling_factor"]


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
    return routed_experts(x, p, cfg, quant, rows=min(512, x.shape[0])) \
        + swiglu(x, p["shared"], quant)


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False,
            at=None, state_reset=None, reset_until=None):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32, or with
    `at` = (first, count) the logits of positions [first, first + count)
    alone, (b, count, vocab_size); `first` may be traced."""
    del remat
    eps, group = cfg["rms_norm_eps"], cfg["layer_group_size"]

    def one(seq):
        x = params["tok_embed"]["embedding"].astype(F32)[seq]
        for i in range(cfg["layers_run"]):
            y = norm(x, params[f"norm{2 * i}"]["scale"], eps)
            if (i + 1) % group == 0:
                x = x + latent_attention(y, params[f"attn{2 * i}"], cfg,
                                         quant)
            else:
                x = x + kimi_delta_attention(y, params[f"mamba{2 * i}"], cfg,
                                             quant, state_reset, reset_until)
            y = norm(x, params[f"norm{2 * i + 1}"]["scale"], eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + swiglu(y, params[f"mlp{2 * i + 1}"], quant)
            else:
                x = x + experts(y, params[f"moe{2 * i + 1}"], cfg, quant)
        if at is not None:
            x = jax.lax.dynamic_slice_in_dim(x, at[0], at[1], axis=0)
        x = norm(x, params["norm_f"]["scale"], eps)
        return blocks.mm("sd,dv->sv", x, params["lm_head"]["kernel"], quant)

    return jnp.stack([one(seq) for seq in tokens])


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
