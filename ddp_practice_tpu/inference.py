"""Autoregressive inference: KV-cache prefill/decode + sampling.

The reference is a training-only demo — it saves a checkpoint and stops
(`origin_main.py:113`); there is no inference path anywhere in it. A
framework with a decoder LM family (models/lm.py) needs one, so this
module adds generation designed for the XLA compilation model:

- the ENTIRE generation — prompt prefill plus `max_new_tokens` decode
  steps — is one jittable pure function with static shapes: the K/V cache
  is pre-allocated in HBM at `prompt_len + max_new_tokens`, prefill writes
  the prompt's keys/values with one batched call (s = prompt length), and
  decoding is a `lax.scan` of single-token steps (s = 1);
- data-dependent stopping (EOS) is a done-mask folded through the scan,
  not a dynamic loop exit — sampled-after-done positions emit `pad_id`;
- sampling (greedy / temperature / top-k / nucleus top-p) happens
  on-device with an explicit PRNG key chain — logits arrive in the policy
  compute dtype (bf16 under the bf16 policy, models/lm.py) and
  `sample_logits` upcasts to fp32 before filtering — so a given
  (params, prompt, key) triple is reproducible across hosts and backends.

The cache lives in a flax "cache" variable collection (see
models/vit.py SelfAttention `decode=True`): each block holds
(b, total_len, heads, head_dim) key/value buffers plus a write cursor,
and the model tracks one top-level position cursor for the positional
embedding. `model.apply(..., mutable=["cache"])` threads it functionally
through the scan carry.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def cast_params_for_streaming(params: Any) -> Any:
    """fp32 leaves -> bf16 for inference-time param streaming.

    Training keeps fp32 master params, but decode re-reads the whole tree
    every token step, so streaming them as bf16 halves the HBM traffic.
    Under the bf16 compute policy the cast is BIT-IDENTICAL to applying
    the fp32 tree (every layer casts its kernel to the compute dtype
    before use — pinned in tests/test_generate.py); under an fp32 policy
    it changes numerics (weights round to bf16) and is not applied by
    default anywhere.
    """
    return jax.tree.map(
        lambda l: l.astype(jnp.bfloat16)
        if l.dtype == jnp.float32 else l,
        params,
    )


def make_cache(model, batch: int, total_len: int) -> Any:
    """Zero-initialized KV cache for `batch` sequences of `total_len`.

    Shapes come from `jax.eval_shape` over a decode-mode init — no FLOPs,
    no params materialized. Safe to call inside a jitted function (it is,
    in `make_generate_fn`).
    """
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch, total_len), jnp.int32),
            decode=True,
        )
    )["cache"]
    return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)


def decode_apply(
    model,
    params,
    cache: Any,
    tokens: jnp.ndarray,
    *,
    attn_start=None,
    batch_stats: Any = None,
    page_table=None,
    kv_lengths=None,
    real_lengths=None,
) -> tuple:
    """One decode-mode model application: `(new_cache, logits)`.

    The single primitive both inference paths are built from — a prompt
    prefill is `decode_apply` with `tokens` spanning the prompt, a decode
    step is `decode_apply` with one token per sequence — so the one-shot
    generator below and the continuous-batching engine (serve/engine.py)
    share the exact apply (and therefore the exact logits): the cache
    collection threads through functionally, the write cursor advances by
    `tokens.shape[1]`, and `attn_start` masks left padding per sequence.

    `page_table` + `kv_lengths` switch the cache to the PAGED layout
    (serve/kv_pages.py): `cache` holds block pools instead of per-row
    buffers, each sequence writes/attends at its own slot-local position
    (kv_lengths), and there is no shared cursor — `attn_start` then masks
    in slot-local coordinates. `tokens` with s > 1 is a paged PREFILL:
    the s tokens land at positions kv_lengths[b] + [0, s), attending any
    already-resident prefix through the table (the prefix-cache
    admission path, serve/engine.py PagedEngine._prefix_prefill). An
    int8-cache model pools per-block scale pages alongside
    (models/vit.py). `real_lengths` (b,), for a model with recurrent state
    (models/hybrid_lm.py) alone: how many of a paged prefill's tokens are
    real, the rest right padding its scans must not advance over.
    """
    variables = {"params": params, "cache": cache}
    if batch_stats is not None:
        variables["batch_stats"] = batch_stats
    kwargs = {}
    if page_table is not None:
        kwargs = {"page_table": page_table, "kv_lengths": kv_lengths}
        if real_lengths is not None:
            kwargs["real_lengths"] = real_lengths
    logits, mut = model.apply(
        variables,
        tokens,
        decode=True,
        mutable=["cache"],
        attn_start=attn_start,
        **kwargs,
    )
    return mut["cache"], logits


def sample_logits(
    logits: jnp.ndarray,
    key: Optional[jax.Array],
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
) -> jnp.ndarray:
    """Sample token ids (b,) from logits (b, vocab); any float dtype —
    upcast to fp32 here before temperature/filter math.

    temperature=0 is greedy argmax (no key needed). top_k keeps the k
    highest logits (clamped to the vocab size — asking for more than the
    vocab has is a no-op filter, not a lax.top_k shape error); top_p keeps
    the smallest prefix of the sorted distribution whose cumulative
    probability reaches p (the most likely token always survives). Both
    filters compose: k first, then p.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / temperature
    neg = jnp.asarray(-1e30, logits.dtype)
    top_k = min(top_k, logits.shape[-1])
    if top_k > 0:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # exclusive cumulative prob: position i survives while the mass
        # BEFORE it is < p, so the argmax (mass 0 before it) always does
        cum = jnp.cumsum(probs, axis=-1) - probs
        keep = cum < top_p
        # threshold = smallest surviving logit
        thresh = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < thresh, neg, logits)
    return jax.random.categorical(key, logits, axis=-1)


def sample_logits_batch(
    logits: jnp.ndarray,
    keys: jnp.ndarray,
    *,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
) -> jnp.ndarray:
    """Per-ROW sampling over logits (b, vocab): temperature / top_k /
    top_p are traced (b,) arrays, not compile-time constants — the
    per-slot sampling path (serve/engine.py `per_slot_sampling`), where
    one jitted decode program serves a batch mixing greedy and sampled
    requests with arbitrary per-request params and never recompiles
    when they change.

    Row semantics match `sample_logits` exactly (pinned in
    tests/test_per_slot_sampling.py): temperature <= 0 is greedy
    argmax, top_k keeps the k highest logits (k <= 0 = off; ties at
    the kth value survive, as with lax.top_k), top_p keeps the
    smallest sorted prefix whose EXCLUSIVE cumulative probability is
    below p (p <= 0 = off); the filters compose k-then-p. The only
    difference is mechanism: a static k can call lax.top_k, a traced
    per-row k cannot, so the threshold comes from a descending sort —
    the same kth-largest VALUE either way. `keys` is (b, 2) uint32 raw
    key data, one independent chain per row; greedy rows ignore their
    draw (the chain still advances uniformly, so a request's stream
    never depends on its batchmates' params).
    """
    v = logits.shape[-1]
    logits32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits32, axis=-1)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    is_greedy = temperature <= 0.0
    safe_t = jnp.where(is_greedy, 1.0, temperature)
    scaled = logits32 / safe_t[:, None]
    neg = jnp.asarray(-1e30, jnp.float32)
    k = jnp.clip(top_k, 0, v)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        desc, jnp.maximum(k - 1, 0)[:, None], axis=-1
    )
    scaled = jnp.where((k[:, None] > 0) & (scaled < kth), neg, scaled)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs
    keep = cum < top_p[:, None]
    thresh = jnp.min(
        jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
    )
    scaled = jnp.where(
        (top_p[:, None] > 0.0) & (scaled < thresh), neg, scaled
    )
    # one categorical per row under its own key, called at the same
    # (1, vocab) shape as the per-request path so the drawn bits match
    # sample_logits bit-for-bit under the same sub-key
    sampled = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row[None], axis=-1)[0]
    )(keys, scaled)
    return jnp.where(is_greedy, greedy, sampled.astype(greedy.dtype))


def make_generate_fn(
    model,
    *,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    batch_stats: Any = None,
) -> Callable[[Any, jnp.ndarray, Optional[jax.Array]], jnp.ndarray]:
    """Build `gen(params, prompt, key) -> tokens` for a decode-capable model.

    `prompt` is (b, prompt_len) int32 (uniform length per batch — byte-level
    prompts pad naturally by construction); the result is
    (b, prompt_len + max_new_tokens) with the prompt copied through. Wrap
    the returned function in `jax.jit` (the generate CLI and tests do); all
    sampling parameters are closed over as compile-time constants.

    `batch_stats`: the checkpoint's non-param state, REQUIRED for MoE
    models to route like they trained — the router's aux-free selection
    bias lives there (ops/moe.py); without it selection falls back to the
    raw gates. The tiny (E,)-sized leaves close over as jit constants.
    """

    def gen(params, prompt, key=None, prompt_lens=None):
        b, prompt_len = prompt.shape
        if prompt_len == 0:
            raise ValueError("prompt must contain at least one token")
        total = prompt_len + max_new_tokens
        if total > model.max_len:
            raise ValueError(
                f"prompt {prompt_len} + max_new_tokens {max_new_tokens} "
                f"exceeds model max_len {model.max_len}"
            )
        if temperature != 0.0 and key is None:
            raise ValueError("sampling (temperature != 0) needs a PRNG key")
        # variable-length batching: prompts arrive LEFT-padded (real tokens
        # right-aligned, pad_left_prompts builds this layout), so every
        # sequence's last prompt token sits at the same index and the
        # decode scan needs no per-sequence cursors; attn_start masks the
        # left padding out of every attention. RoPE-only (models/lm.py).
        attn_start = None
        if prompt_lens is not None:
            # lengths are traced under jit, so out-of-range values can't
            # raise here; clamp to [1, prompt_len] instead — a negative
            # start would silently attend the padding, a start past the
            # last prompt slot would leave query rows with no valid keys
            lens = jnp.clip(
                jnp.asarray(prompt_lens, jnp.int32), 1, prompt_len
            )
            attn_start = (prompt_len - lens).astype(jnp.int32)
        cache, logits = decode_apply(
            model, params, make_cache(model, b, total), prompt,
            attn_start=attn_start, batch_stats=batch_stats,
        )
        carry_key = key if key is not None else jax.random.PRNGKey(0)
        done = jnp.zeros((b,), bool)

        def step(carry, _):
            cache, last_logits, k, done = carry
            k, sub = jax.random.split(k)
            tok = sample_logits(
                last_logits, sub,
                temperature=temperature, top_k=top_k, top_p=top_p,
            ).astype(jnp.int32)
            tok = jnp.where(done, jnp.asarray(pad_id, jnp.int32), tok)
            if eos_id is not None:
                done = done | (tok == eos_id)
            cache, logits = decode_apply(
                model, params, cache, tok[:, None],
                attn_start=attn_start, batch_stats=batch_stats,
            )
            return (cache, logits[:, -1], k, done), tok

        (_, _, _, _), toks = lax.scan(
            step,
            (cache, logits[:, -1], carry_key, done),
            None,
            length=max_new_tokens,
        )
        return jnp.concatenate([prompt, toks.T], axis=1)

    return gen


def pad_left_prompts(prompts, pad_id: int = 0):
    """Batch variable-length token lists as a LEFT-padded array.

    Returns (tokens (b, max_len) int32, lengths (b,) int32) for
    `gen(params, tokens, key, prompt_lens=lengths)` — real tokens are
    right-aligned so all sequences share the decode cursor, and the
    returned lengths drive the attention mask over the padding.
    """
    lens = np.asarray([len(p) for p in prompts], np.int32)
    if (lens == 0).any():
        raise ValueError("every prompt must contain at least one token")
    width = int(lens.max())
    out = np.full((len(prompts), width), pad_id, np.int32)
    for i, p in enumerate(prompts):
        out[i, width - len(p):] = np.asarray(p, np.int32)
    return jnp.asarray(out), jnp.asarray(lens)


def encode_bytes(text: str) -> np.ndarray:
    """str -> (1, len) int32 byte tokens (the byte-level LM vocabulary)."""
    raw = np.frombuffer(text.encode("utf-8"), dtype=np.uint8)
    return raw.astype(np.int32)[None, :]


def decode_bytes(tokens) -> str:
    """(len,) byte tokens -> str (invalid UTF-8 replaced, not raised)."""
    arr = np.asarray(tokens).astype(np.uint8)
    return arr.tobytes().decode("utf-8", errors="replace")
