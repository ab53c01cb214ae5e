"""Plain reference for `perf/configs/smallthinker_21b_pp7.json`: the
SmallThinker layer equations in float32 `jax.numpy`, precision "highest".

    layer i of `layers_published`:
    a      = N_1(x)
    q,k,v  = a Wq, a Wk, a Wv                  (28 | 4 | 4 heads of 128)
    window layer (`sliding_window_layout[i]` 1): q, k rotated, whole head,
        pairs (j, j + 64), base `rope_theta`; keys s with t - w < s <= t
    global layer (0, and there `rope_layout[i]` is 0 too): no positional
        embedding at all; keys s <= t
    x      = x + softmax(q k^T / sqrt(128) + mask) v Wo
    l      = a Wr                              (64 logits: the router reads
                                                a, the ATTENTION's input)
    picks  = top_6(softmax(l));  w = softmax(l)[picks] / their sum
    m      = N_2(x)
    x      = x + sum_{e in picks} w_e Wd_e(relu(Wg_e m) * Wu_e m)
    logits = N_f(x) W_head
    N: RMSNorm, float32, eps rms_norm_eps; no bias anywhere

No kernel, no cache, no pages, nothing imported from the program: the window
is a mask over the whole row of keys, a block of queries at a time; the
experts are a loop over ALL of them, each run on every token and weighted by
what the token's picks give it (zero for the 58 it did not pick), so a pick
is never a gather. Parameters come as the flax tree the program lays out
(sub-layer 2i is layer i's attention, 2i + 1 its experts), filled by the
benchmark's weights; a weight is upcast where it is used and everything
position-wise runs `ROWS` positions at a time. `forward(..., at=(first,
count))` gives the logits of `count` positions from `first` alone (a whole
request's are 9 GB at 151,936 rows).

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
# queries a step of the attention takes against the whole row of keys
QUERIES = 128


def norm(x, p, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * p["scale"].astype(F32)


def by_rows(fn, x, rows: int = ROWS):
    """fn over (b, s, ...), or over a tuple of such arrays, in pieces of
    `rows` positions (s a multiple, or under it)."""
    s = jax.tree.leaves(x)[0].shape[1]
    if s <= rows or s % rows:
        return fn(x)
    pieces = jax.tree.map(lambda a: jnp.moveaxis(
        a.reshape(a.shape[0], s // rows, rows, *a.shape[2:]), 1, 0), x)
    out = jax.lax.map(fn, pieces)
    return jax.tree.map(lambda a: jnp.moveaxis(a, 0, 1).reshape(
        a.shape[1], s, *a.shape[3:]), out)


def attention(a, p, cfg: dict, window, quant=None):
    """a (b, s, d) normed; `window` None for a global layer (no rotary, every
    key before the query) or the window's size (rotary, the `window` latest
    keys, the query's own among them)."""
    b, s, _ = a.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, group = cfg["head_dim"], heads // kvh
    span = jnp.arange(s)

    def project(y):
        q = blocks.mm("bsd,dhk->bshk", y, p["q"]["kernel"], quant)
        kv = blocks.mm("bsd,dckv->bsckv", y, p["kv"]["kernel"], quant)
        return q, kv[:, :, 0], kv[:, :, 1]

    q, k, v = by_rows(project, a)
    if window is not None:
        theta = float(cfg["rope_theta"])
        q, k = blocks.rope(q, span, theta), blocks.rope(k, span, theta)
    step = min(QUERIES, s)
    while s % step:
        step -= 1

    def one(args):
        qb, pos = args                                 # (b, step, h, hd)
        qg = qb.reshape(b, step, kvh, group, hd)
        seen = span[None, :] <= pos[:, None]
        if window is not None:
            seen &= span[None, :] > pos[:, None] - window
        scores = blocks.mm("bqngd,bsnd->bqngs", qg, k, quant) \
            / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(
            jnp.where(seen[None, :, None, None, :], scores, -jnp.inf),
            axis=-1)
        return blocks.mm("bqngs,bsnd->bqngd", probs, v, quant).reshape(
            b, step, heads, hd)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // step, step, heads, hd), 1, 0),
        span.reshape(s // step, step)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, heads, hd)
    return by_rows(lambda t: blocks.mm(
        "bqhk,hkd->bqd", t, p["out"]["kernel"], quant), out)


def route(a, p, cfg: dict, quant=None):
    """(b, s, E) float32: a token's weight on every expert, zero off its
    picks: softmax over all, the top `moe_num_active_primary_experts`,
    renormalised over them."""
    probs = jax.nn.softmax(
        blocks.mm("bsd,de->bse", a, p["router"]["kernel"], quant), axis=-1)
    w, picks = jax.lax.top_k(probs, cfg["moe_num_active_primary_experts"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    hot = jax.nn.one_hot(picks, probs.shape[-1], dtype=F32)   # (b, s, k, E)
    return jnp.einsum("bsk,bske->bse", w, hot)


def experts(m, weights, p, quant=None):
    """sum_e weights[..., e] Wd_e(relu(Wg_e m) * Wu_e m): every expert on
    every token, one expert at a time."""
    def rows(args):
        y, w = args                                    # (b, r, d), (b, r, E)

        def one(acc, e):
            hid = jax.nn.relu(
                blocks.mm("bsd,df->bsf", y, p["expert_gate"][e], quant)) \
                * blocks.mm("bsd,df->bsf", y, p["expert_up"][e], quant)
            out = blocks.mm("bsf,fd->bsd", hid, p["expert_down"][e], quant)
            return acc + jnp.take(w, e, axis=-1)[..., None] * out, None

        return jax.lax.scan(one, jnp.zeros_like(y),
                            jnp.arange(p["expert_gate"].shape[0]))[0]

    return by_rows(rows, (m, weights))


def forward(params, tokens, cfg: dict, quant=None, remat: bool = False,
            at=None):
    """tokens (b, s) int32 -> logits (b, s, vocab_size) float32, or with
    `at` = (first, count) the logits of positions [first, first + count)
    alone, (b, count, vocab_size); `first` may be traced."""
    del remat
    eps = cfg["rms_norm_eps"]
    x = params["tok_embed"]["embedding"].astype(F32)[tokens]
    for i, layer in enumerate(cfg["layers_published"]):
        windowed = bool(cfg["sliding_window_layout"][layer])
        a = by_rows(lambda t, i=i: norm(t, params[f"norm{2 * i}"], eps), x)
        x = x + attention(
            a, params[f"attn{2 * i}"], cfg,
            cfg["sliding_window_size"] if windowed else None, quant)
        moe = params[f"moe{2 * i + 1}"]
        weights = by_rows(lambda t, moe=moe: route(t, moe, cfg, quant), a)
        m = by_rows(lambda t, i=i: norm(t, params[f"norm{2 * i + 1}"], eps),
                    x)
        x = x + experts(m, weights, moe, quant)
    if at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, at[0], at[1], axis=1)
    x = norm(x, params["norm_f"], eps)
    return blocks.mm("bsd,dv->bsv", x, params["lm_head"]["kernel"], quant)


def loss(params, batch, cfg: dict, quant=None):
    """Next-token loss of (b, s+1) token windows: position t predicts t+1."""
    tokens = batch["tokens"]
    logits = forward(params, tokens[:, :-1], cfg, quant)
    return blocks.softmax_xent(logits, tokens[:, 1:])
