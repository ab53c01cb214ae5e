"""Plain reference for `perf/configs/kanana2_30b_pp8.json`: the DeepSeek-V3
layer equations in float32 `jax.numpy`, precision "highest".

Every layer is `x = x + attn(RMSNorm(x)); x = x + ffn(RMSNorm(x))`, a final
RMSNorm and the untied head. No kernel, no cache, no batching, nothing
imported from the program. Attention is UN-absorbed: K and V are expanded
from the latent for every position, and the causal softmax is taken a block
of queries at a time (an 8,960-token request's scores are 10 GB whole). The
experts are computed for the tokens that picked them only: the picks are
sorted by expert and walked in windows of rows, each window through its own
expert's three matrices (every expert for every token would be twenty times
the work, 10 PFLOP a layer at 8,960 tokens). Parameters come as the flax tree
the program lays out, filled by the benchmark's weights.

    q = h W_q -> a head [q_nope | q_rope];  [c | k_rope] = h W_kv_a
    c = RMSNorm(c);  q_rope, k_rope = RoPE(., pos), pairs (2i, 2i+1) as
    `rope_interleave` says, one k_rope for all heads
    [k_nope_h | v_h] = c W_kv_b;  score = (q_nope.k_nope + q_rope.k_rope)
    / sqrt(nope + rope), causal softmax, out = concat_h(p v_h) W_o
    layer < first_k_dense_replace:  W_down(silu(W_gate x) * W_up x)
    else: s = sigmoid(x W_r), picks = top_k(s + e_score_correction_bias),
          w = s_picks / sum s_picks * routed_scaling_factor,
          sum_picks w_e expert_e(x) + the shared expert

Departures from the published model, as the configuration file states them:
`layers_run` of `num_hidden_layers` layers. The config has no
multi-token-prediction module, query compression (`q_lora_rank` null), rope
scaling or expert groups (`n_group` 1), and none is run.
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


def rope_pairs(x, positions, theta: float, interleave: bool):
    """Rotate (s, h, d) by position: pairs (2i, 2i + 1) when `interleave`,
    (i, i + d/2) otherwise; frequencies theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, cfg: dict, quant=None, block: int = 512):
    """Un-absorbed latent attention of one sequence. x (s, d) float32."""
    s = x.shape[0]
    lat, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope = dict(theta=float(cfg["rope_theta"]),
                interleave=bool(cfg["rope_interleave"]))
    pos = jnp.arange(s)
    q = blocks.mm("sd,dhe->she", x, p["q"]["kernel"], quant)
    kv_a = blocks.mm("sd,de->se", x, p["kv_a"]["kernel"], quant)
    c = rms_norm(kv_a[:, :lat], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_rope = rope_pairs(kv_a[:, None, lat:], pos, **rope)       # (s, 1, r)
    kv = blocks.mm("sl,lhe->she", c, p["kv_b"], quant)
    heads = kv.shape[1]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads,
                                                   k_rope.shape[-1]))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], pos, **rope)], -1)
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
    return blocks.mm("shv,hvd->sd", out.reshape(s, heads, -1),
                     p["out"]["kernel"], quant)


def swiglu(x, p, quant=None):
    hid = jax.nn.silu(blocks.mm("nd,df->nf", x, p["gate"]["kernel"], quant)) \
        * blocks.mm("nd,df->nf", x, p["up"]["kernel"], quant)
    return blocks.mm("nf,fd->nd", hid, p["down"]["kernel"], quant)


def route(x, p, cfg: dict, quant=None):
    """(picks (n, k) int, weights (n, k) float32) of the sigmoid router."""
    s = jax.nn.sigmoid(blocks.mm("nd,de->ne", x, p["router"]["kernel"],
                                 quant))
    _, picks = jax.lax.top_k(s + p["e_score_correction_bias"].astype(F32),
                             cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, picks, axis=-1)
    return picks, w / jnp.sum(w, axis=-1, keepdims=True) \
        * cfg["routed_scaling_factor"]


def routed_experts(x, p, cfg: dict, quant=None, rows: int = 512):
    """sum over a token's picks of w_e expert_e(x). The (token, pick)
    pairs are sorted by expert; window j of `rows` sorted pairs belongs to
    ONE expert (an expert's pairs start a new window), and
    ceil(n k / rows) + experts windows cover any routing."""
    n, experts = x.shape[0], cfg["n_routed_experts"]
    picks, w = route(x, p, cfg, quant)
    k = picks.shape[1]
    flat = picks.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    token, gate = order // k, w.reshape(-1)[order]
    counts = jnp.bincount(flat, length=experts)
    first = jnp.cumsum(counts) - counts
    windows = -(-counts // rows)
    w_end = jnp.cumsum(windows)

    def one(acc, j):
        e = jnp.minimum(jnp.searchsorted(w_end, j, side="right"),
                        experts - 1)
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

    n_windows = -(-(n * k) // rows) + experts
    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_windows))
    return acc


def moe(x, p, cfg: dict, quant=None, rows: int = 512):
    return routed_experts(x, p, cfg, quant, rows) \
        + swiglu(x, p["shared"], quant)


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32."""
    del remat
    eps = cfg["rms_norm_eps"]

    def one(seq):
        x = params["tok_embed"]["embedding"].astype(F32)[seq]
        for i in range(cfg["layers_run"]):
            y = rms_norm(x, params[f"norm_attn{i}"]["scale"], eps)
            x = x + attention(y, params[f"attn{i}"], cfg, quant)
            y = rms_norm(x, params[f"norm_ffn{i}"]["scale"], eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + swiglu(y, params[f"mlp{i}"], quant)
            else:
                x = x + moe(y, params[f"moe{i}"], cfg, quant,
                            rows=min(512, seq.shape[0]))
        x = rms_norm(x, params["norm_f"]["scale"], eps)
        return blocks.mm("sd,dv->sv", x, params["lm_head"]["kernel"], quant)

    return jnp.stack([one(seq) for seq in tokens])


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
