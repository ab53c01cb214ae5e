"""`drivers/serve_by_leaf_admits.py` under the one order of `lib/dealt.py`,
for a cell whose model holds a recurrent state AND latent pages and whose
prompts are admitted in chunks (`ling3_serve_reason`):

- `reference_gaps` is `serve_long_by_leaf`'s (the reference's logits at the
  served positions alone, read in blocks: a request of 11,264 tokens would
  hold 1.8 GB of float32 logits taken whole); `reference_checks` is
  `serve_window_by_leaf`'s: the gaps' maximum, and their mean over the
  positions where the reference computed in bf16 picks the float32
  reference's best (under random weights a greedy answer of a thousand
  tokens falls into loops, and a near-tie repeated for hundreds of positions
  is every bf16 run's p99: PERF.md section 2);
- `pick_sample` takes three finished requests: the longest, one admitted in
  more than one chunk (its recurrent state carried from chunk to chunk while
  other slots decoded) whose prompt ends within a quarter of a chunk after
  its last boundary, where a state lost there is still remembered when the
  answer starts, one whose prompt fits one chunk;
- the weights are `lib/weights_by_leaf.py`'s with every `dt_bias` leaf
  shifted by the configuration's `dt_bias_shift` (-8: the file's `assumed`
  says why; the generator is not this PR's to edit, so the drawn leaf is
  shifted here, on the device, a leaf at a time);
- `obs["chunks"]`, [start, end, first position, real tokens] of every
  `engine.prefill_step` (monotonic seconds), for the reader that charges
  `kda_terms` + `kda_scan` their real tokens; `obs["expert_bursts"]` and
  `obs["admits"]` as the drivers it wraps leave them.

Composed, not copied, and swapped in ONE place each for the time of the run,
as the drivers it wraps do; it goes with them when a `benchmark` PR lets
`drivers/serve.py` check in blocks, `lib/weights.py` take a leaf's shift and
`obs["spans"]` carry attributes (PERF.md section 7).
"""

from __future__ import annotations

import time

import numpy as np

from perf.drivers import (serve, serve_by_leaf_admits, serve_long_by_leaf,
                          serve_window_by_leaf)
from perf.lib import dealt, weights_by_leaf


def shifted(make, shifts: dict):
    """`make` (a `make_params`) with the leaves named in `shifts` moved by
    that much after the draw."""
    import jax

    def make_params(abstract, seed, **kw):
        def one(path, leaf):
            by = shifts.get(str(getattr(path[-1], "key", path[-1])))
            return leaf if by is None else (leaf + by).astype(leaf.dtype)

        return jax.tree_util.tree_map_with_path(
            one, make(abstract, seed, **kw))

    return make_params


def three_admissions(chunk: int):
    """A `pick_sample`: the longest finished request, one whose prompt took
    more than one chunk of `chunk` tokens and ends under `chunk // 4` tokens
    after its last chunk boundary (else the one that ends nearest after
    it), one whose prompt fits one chunk (each where there is one)."""

    def pick(ok: list, by_rid: dict, seed: int, n: int, buckets: list):
        del n, buckets
        if not ok:
            return []
        rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x5A3]))
        order = [ok[i] for i in rng.permutation(len(ok))]
        plen = lambda c: len(by_rid[c.rid]["prompt"])
        after = lambda c: (plen(c) - 1) % chunk
        longest = max(ok, key=lambda c: plen(c) + len(c.tokens))
        picked = [longest]
        chunked = [c for c in order if plen(c) > chunk and c is not longest]
        picked += ([c for c in chunked if after(c) < chunk // 4]
                   or sorted(chunked, key=after))[:1]
        picked += [c for c in order if plen(c) <= chunk
                   and c is not longest][:1]
        return [(list(by_rid[c.rid]["prompt"]), [int(t) for t in c.tokens])
                for c in picked]

    return pick


def run(ctx) -> dict:
    chunks, build = [], serve.build_engine

    def noting(ctx, tracer=None):
        model, params, engine = build(ctx, tracer)
        chunk = engine.prefill_step

        def prefill_step(slot):
            t, first = time.monotonic(), int(engine._len[slot])
            done = chunk(slot)
            chunks.append([t, time.monotonic(), first,
                           int(engine._len[slot]) - first])
            return done

        engine.prefill_step = prefill_step
        return model, params, engine

    whole = (serve.reference_gaps, serve.reference_checks, serve.pick_sample,
             weights_by_leaf.make_params)
    serve.build_engine = noting
    serve.reference_gaps = serve_long_by_leaf.reference_gaps
    serve.reference_checks = serve_window_by_leaf.reference_checks
    serve.pick_sample = three_admissions(
        ctx.traffic["engine"]["prefill_chunk"])
    weights_by_leaf.make_params = shifted(
        whole[3], {"dt_bias": ctx.config["dt_bias_shift"]})
    try:
        with dealt.one_order():
            result = serve_by_leaf_admits.run(ctx)
    finally:
        serve.build_engine = build
        (serve.reference_gaps, serve.reference_checks, serve.pick_sample,
         weights_by_leaf.make_params) = whole
    result["obs"]["chunks"] = chunks
    return result
