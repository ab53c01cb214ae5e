"""`drivers/serve_by_leaf_admits.py` under the one order of `lib/dealt.py`,
for a cell whose requests are too long for `drivers/serve.py`'s check and
whose sparse attention layers count what they read:

- `reference_gaps` reads the reference's logits at the SERVED positions alone
  (`forward(..., at=(first, count))`: a request of 34,304 tokens would hold
  10 GB of float32 logits taken whole), over the request right-padded to a
  multiple of `check.pad_rows`; `reference_checks` compares their maximum and
  their 99th percentile (a near-tied pick of a page flips under rounding as a
  router's pick does: PERF.md section 2);
- `pick_sample` takes three finished requests: the longest, one whose whole
  context stayed under the configuration's `dense_len`, one between;
- `obs["chunks"]`, [start, end, first position, real tokens] of every
  `engine.prefill_step` (monotonic seconds), for the reader that charges
  `sparse_prefill` its real tokens (`obs["admits"]` holds a chunk admission's
  bookkeeping alone); `obs["sparse_bursts"]`, [seconds, pages walked, pages
  held, sparse slots] of every decode burst, from the engine's own
  `last_burst_sparse` (what the decode program counted).

Composed, not copied, and swapped in ONE place each for the time of the run,
as the drivers it wraps do; it goes with them when a `benchmark` PR lets
`drivers/serve.py` check in blocks and `obs["spans"]` carry attributes
(PERF.md section 7).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from perf.drivers import serve, serve_by_leaf_admits
from perf.lib import compare, dealt


def reference_gaps(ctx, params, sample: list, quant=None) -> list:
    """`serve.reference_gaps`, the logits taken at the served rows alone."""
    import jax
    import jax.numpy as jnp

    cfg, check = ctx.config, ctx.traffic["check"]
    ref = importlib.import_module(f"perf.reference.{cfg['reference']}")
    rows, count = check["pad_rows"], check["served_rows"]
    read = jax.jit(lambda p, t, first, picks: compare.token_gaps(
        ref.forward(p, t, cfg, None, at=(first, count))[0], picks))
    best = jax.jit(lambda p, t, first: ref.forward(
        p, t, cfg, quant, at=(first, count))[0].argmax(-1).astype(jnp.int32))
    out = []
    for prompt, served in sample:
        seq = prompt + served
        at = len(prompt) - 1  # served token j was chosen at position at + j
        k = min(len(served), count)
        width = -(-max(len(seq), at + count) // rows) * rows
        tokens = np.zeros((1, width), np.int32)  # right pad: causal
        tokens[0, :len(seq)] = seq
        tokens = jnp.asarray(tokens)
        if quant is None:
            picks = np.zeros(count, np.int32)
            picks[:k] = served[:k]
            picks = jnp.asarray(picks)
        else:
            picks = best(params, tokens, at)
        out.append(np.asarray(read(params, tokens, at, picks),
                              np.float32)[:k])
    return out


def reference_checks(ctx, params, sample: list) -> compare.Checks:
    checks = compare.Checks()
    lim = ctx.traffic["limits"]
    if not sample:
        checks.add("served_requests_sampled", float("inf"), 0.0,
                   "no finished request to compare")
        return checks
    gaps = np.concatenate(reference_gaps(ctx, params, sample))
    checks.add("served_token_logit_gap_max", float(gaps.max()),
               lim["served_token_gap"],
               f"{len(gaps)} served tokens of {len(sample)} requests, "
               f"contexts {sorted(len(p) + len(s) for p, s in sample)}")
    checks.add("served_token_logit_gap_p99",
               float(np.percentile(gaps, 99.0)),
               lim["served_token_gap_p99"],
               f"{int((gaps > 0).sum())} off the reference's best")
    return checks


def three_contexts(dense_len: int):
    """A `pick_sample`: the longest finished request, one that never left
    the dense regime, one between (each where there is one)."""

    def pick(ok: list, by_rid: dict, seed: int, n: int, buckets: list):
        del n, buckets
        if not ok:
            return []
        rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x5A3]))
        order = [ok[i] for i in rng.permutation(len(ok))]
        size = lambda c: len(by_rid[c.rid]["prompt"]) + len(c.tokens)
        longest = max(ok, key=size)
        picked = [longest]
        picked += [c for c in order if size(c) <= dense_len
                   and c is not longest][:1]
        picked += [c for c in order if dense_len < size(c)
                   and c is not longest][:1]
        return [(list(by_rid[c.rid]["prompt"]), [int(t) for t in c.tokens])
                for c in picked]

    return pick


def run(ctx) -> dict:
    chunks, bursts, build = [], [], serve.build_engine

    def noting(ctx, tracer=None):
        model, params, engine = build(ctx, tracer)
        chunk, step = engine.prefill_step, engine.step_burst

        def prefill_step(slot):
            t, first = time.monotonic(), int(engine._len[slot])
            done = chunk(slot)
            chunks.append([t, time.monotonic(), first,
                           int(engine._len[slot]) - first])
            return done

        def step_burst():
            out = step()
            if engine.last_burst_sparse is not None:
                bursts.append([time.monotonic(), *engine.last_burst_sparse])
            return out

        engine.prefill_step, engine.step_burst = prefill_step, step_burst
        return model, params, engine

    whole = (serve.reference_gaps, serve.reference_checks, serve.pick_sample)
    serve.build_engine = noting
    serve.reference_gaps, serve.reference_checks = reference_gaps, \
        reference_checks
    serve.pick_sample = three_contexts(ctx.config["sparse"]["dense_len"])
    try:
        with dealt.one_order():
            result = serve_by_leaf_admits.run(ctx)
    finally:
        serve.build_engine = build
        (serve.reference_gaps, serve.reference_checks,
         serve.pick_sample) = whole
    result["obs"]["chunks"], result["obs"]["sparse_bursts"] = chunks, bursts
    if bursts:
        walked, held = (sum(b[i] for b in bursts) for i in (1, 2))
        result["series"]["sparse_pages_walked_share"] = walked / max(held, 1)
    return result
