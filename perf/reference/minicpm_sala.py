"""Plain reference for `perf/configs/minicpm_sala_9b_pp4.json`: the
MiniCPM-SALA layer equations in float32 `jax.numpy`, precision "highest".

    x = scale_emb * embed(tokens)
    layer i of `layers_published`:  x = x + c * mixer_i(N(x))
                                    x = x + c * mlp(N(x))
    c = scale_depth / sqrt(num_hidden_layers)   (the PUBLISHED depth)
    logits = (N(x) * dim_model_base / hidden_size) W_head
    N: RMSNorm, float32, eps rms_norm_eps;  mlp(y) = W_down(silu(W_gate y) * W_up y)

    lightning-attn
    [q|k|v|gate] = y W_in;  q, k = N_h(q), N_h(k) a head;  rotary on the
    whole head (pairs i, i + 64);  q = q / sqrt(128)
    S_t = lambda_h S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t        a head
    lambda_h = exp(-2^(-8 (h + 1) / 32) * (1 - l / 31 + 1e-5)),  l published
    out = (N_h(o) * sigmoid(gate)) W_out

    minicpm4 (InfLLM-v2)
    [query | gate] = y W_q a head;  k, v = y W_kv;  q, k = N_h(q), N_h(k);
    no rotary. A query at position t with n = t + 1 visible tokens:
      n <= dense_len: causal softmax(q k / sqrt(128)) over all of them
      else, a KV head at a time: K~_j = mean(k[16j : 16j + 32]) for
      16j + 32 <= n;  a_{h,j} = softmax_j(q_h K~_j / sqrt(128));
      r_j = sum of a_{h,j} over the group's 16 heads;
      b_m = max(r_j : 4m - 1 <= j <= 4m + 3);  +inf for block 0 and the
      blocks of the last `window` tokens;  the 64 highest b_m (lowest index
      first among equals);  causal softmax over the picked blocks' tokens
    out = (o * sigmoid(gate)) W_o

No kernel, no cache, no batching, nothing imported from the program: the
recurrence is a sequential `lax.scan` over POSITIONS (what the program's
chunked scan and step kernel are checked against); the sparse layer reads as
above, a block of queries at a time over the whole row of keys. Parameters
come as the flax tree the program lays out (sub-layer 2i is layer i's mixer,
2i + 1 its MLP), filled by the benchmark's weights; a weight is upcast where
it is used and everything position-wise runs `ROWS` positions at a time, so
that a request of 34,304 tokens fits beside the bf16 weights. `forward(...,
at=(first, count))` gives the logits of `count` positions from `first` alone
(a whole request's are 10 GB).

Departures from the published model, as the configuration file states them:
`layers_run` of the layers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perf.reference import blocks

F32 = jnp.float32
# positions a position-wise piece takes at a time
ROWS = 2048
# queries a step of the sparse layer takes against the whole row of keys
QUERIES = 128


def norm(x, p, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def by_rows(fn, x, rows: int = ROWS):
    """fn over (b, s, ...) in pieces of `rows` positions (s a multiple, or
    under it)."""
    s = x.shape[1]
    if s <= rows or s % rows:
        return fn(x)
    pieces = jnp.moveaxis(
        x.reshape(x.shape[0], s // rows, rows, *x.shape[2:]), 1, 0)
    out = jax.lax.map(fn, pieces)
    out = jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1).reshape(
        a.shape[1], s, *a.shape[3:]), out)
    return out


def mixer_kinds(cfg: dict) -> list:
    return [cfg["mixer_types"][i] for i in cfg["layers_published"]]


def lightning(x, p, cfg: dict, layer: int, quant=None):
    """Lightning attention, one position at a time. x (b, s, d)."""
    b, s, _ = x.shape
    h, hd, eps = cfg["lightning_nh"], cfg["lightning_head_dim"], \
        cfg["rms_norm_eps"]

    def project(y):
        z = blocks.mm("bsd,de->bse", y, p["in_proj"]["kernel"], quant)
        q, k, v, gate = jnp.split(z, 4, axis=-1)
        heads = lambda t: t.reshape(*t.shape[:2], h, hd)
        return (norm(heads(q), p["q_norm"], eps),
                norm(heads(k), p["k_norm"], eps), heads(v), gate)

    q, k, v, gate = by_rows(project, x)
    pos = jnp.arange(s)
    theta = float(cfg["rope_theta"])
    q = blocks.rope(q, pos, theta) * hd ** -0.5
    k = blocks.rope(k, pos, theta)
    slope = 2.0 ** (-8.0 * (jnp.arange(h, dtype=F32) + 1.0) / h) \
        * (1.0 - layer / (cfg["num_hidden_layers"] - 1) + 1e-5)
    decay = jnp.exp(-slope)[None, :, None, None]

    def step(state, inp):
        q_t, k_t, v_t = inp                                 # (b, h, hd)
        state = decay * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=blocks.HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, hd, hd), F32),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    o = norm(jnp.moveaxis(o, 0, 1), p["norm"], eps).reshape(b, s, h * hd)
    o = o * jax.nn.sigmoid(gate)
    return by_rows(lambda t: blocks.mm(
        "bse,ed->bsd", t, p["out_proj"]["kernel"], quant), o)


def block_picks(q, cmp, pos, sp: dict):
    """The blocks each query picks. q (r, kvh, g, hd) at positions pos (r,);
    cmp (J, kvh, hd) compressed keys. Returns (r, kvh, blocks) bool."""
    hd = q.shape[-1]
    j = jnp.arange(cmp.shape[0])
    n = pos[:, None] + 1
    valid = (sp["stride"] * j + sp["kernel"] <= n)[:, None, None, :]
    logits = jnp.einsum("rngd,jnd->rngj", q, cmp,
                        precision=blocks.HIGHEST) / jnp.sqrt(float(hd))
    a = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    r = jnp.where(valid[:, :, 0], jnp.sum(jnp.where(valid, a, 0.0), axis=2),
                  -jnp.inf)                                 # (r, kvh, J)
    per = sp["block"] // sp["stride"]
    m = jnp.arange(cmp.shape[0] // per)
    r = jnp.pad(r, ((0, 0), (0, 0), (1, 0)), constant_values=-jnp.inf)
    score = jnp.max(jnp.stack(
        [r[..., i:i + per * len(m):per] for i in range(per + 1)]), axis=0)
    near = jnp.maximum(pos - sp["window"] + 1, 0) // sp["block"]
    forced = (m[None] <= pos[:, None] // sp["block"]) & (
        (m[None] < sp["init_blocks"]) | (m[None] >= near[:, None]))
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    picks = jax.lax.top_k(score, min(sp["topk"], len(m)))[1]
    return jnp.any(picks[..., None] == m, axis=-2)


def sparse_attention(x, p, cfg: dict, quant=None):
    """InfLLM-v2 attention, a block of queries at a time. x (b, s, d)."""
    b, s, _ = x.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, sp = cfg["head_dim"], cfg["rms_norm_eps"], cfg["sparse"]
    group = heads // kvh

    def project(y):
        qg = blocks.mm("bsd,dhk->bshk", y, p["q"]["kernel"], quant)
        kv = blocks.mm("bsd,dckv->bsckv", y, p["kv"]["kernel"], quant)
        return (norm(qg[..., :hd], p["q_norm"], eps), qg[..., hd:],
                norm(kv[:, :, 0], p["k_norm"], eps), kv[:, :, 1])

    q, gate, k, v = by_rows(project, x)
    n_blocks = -(-s // sp["block"])
    rows = n_blocks * (sp["block"] // sp["stride"])
    at = sp["stride"] * jnp.arange(rows)[:, None] + jnp.arange(sp["kernel"])
    padded = jnp.pad(k, ((0, 0), (0, max(int(
        sp["stride"] * (rows - 1) + sp["kernel"] - s), 0)), (0, 0), (0, 0)))
    cmp = jnp.mean(padded[:, at], axis=2)                  # (b, J, kvh, hd)
    step = min(QUERIES, s)
    while s % step:
        step -= 1
    span = jnp.arange(s)

    def one(args):
        qb, pos = args                                 # (b, step, h, hd)
        qg = qb.reshape(b, step, kvh, group, hd)
        picked = jax.vmap(lambda qi, ci: block_picks(qi, ci, pos, sp))(
            qg, cmp)                                   # (b, step, kvh, M)
        of_block = jnp.minimum(span // sp["block"], n_blocks - 1)
        dense = (pos + 1 <= sp["dense_len"])[None, :, None, None]
        seen = (span[None, :] <= pos[:, None])[None, :, None, :] & (
            dense | jnp.take(picked, of_block, axis=-1))
        scores = blocks.mm("bqngd,bsnd->bqngs", qg, k, quant) \
            / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(
            jnp.where(seen[:, :, :, None, :], scores, -jnp.inf), axis=-1)
        return blocks.mm("bqngs,bsnd->bqngd", probs, v, quant).reshape(
            b, step, heads, hd)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // step, step, heads, hd), 1, 0),
        span.reshape(s // step, step)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads, hd)
    out = out * jax.nn.sigmoid(gate)
    return by_rows(lambda t: blocks.mm(
        "bqhk,hkd->bqd", t, p["out"]["kernel"], quant), out)


def mlp(x, p, quant=None):
    def one(y):
        hid = jax.nn.silu(
            blocks.mm("bsd,df->bsf", y, p["gate"]["kernel"], quant)) \
            * blocks.mm("bsd,df->bsf", y, p["up"]["kernel"], quant)
        return blocks.mm("bsf,fd->bsd", hid, p["down"]["kernel"], quant)

    return by_rows(one, x)


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False,
            at=None):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32, or with
    `at` = (first, count) the logits of positions [first, first + count)
    alone, (b, count, vocab_size); `first` may be traced."""
    del remat
    eps = cfg["rms_norm_eps"]
    c = cfg["scale_depth"] / cfg["num_hidden_layers"] ** 0.5
    x = cfg["scale_emb"] * params["tok_embed"]["embedding"].astype(F32)[
        tokens]
    for i, (kind, layer) in enumerate(
            zip(mixer_kinds(cfg), cfg["layers_published"])):
        y = by_rows(lambda t, i=i: norm(t, params[f"norm{2 * i}"], eps), x)
        if kind == "minicpm4":
            y = sparse_attention(y, params[f"attn{2 * i}"], cfg, quant)
        else:
            y = lightning(y, params[f"mamba{2 * i}"], cfg, layer, quant)
        x = x + c * y
        y = by_rows(lambda t, i=i: norm(t, params[f"norm{2 * i + 1}"], eps),
                    x)
        x = x + c * mlp(y, params[f"mlp{2 * i + 1}"], quant)
    if at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, at[0], at[1], axis=1)
    x = norm(x, params["norm_f"], eps) \
        * (cfg["dim_model_base"] / cfg["hidden_size"])
    return blocks.mm("bsd,dv->bsv", x, params["lm_head"]["kernel"], quant)


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
