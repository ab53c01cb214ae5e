"""`drivers/serve_long_by_leaf.py`'s blocked reference check and chunk
records for a cell whose attention layers keep a WINDOW of their cache, under
the one order of `lib/dealt.py`:

- `reference_gaps` is `serve_long_by_leaf`'s (the reference's logits at the
  served positions alone, read in blocks); `reference_checks` compares their
  maximum and, in place of their 99th percentile, their MEAN over the
  positions where the reference computed in bf16 picks the float32
  reference's best (`mean_gap_past_bf16`). Under random weights this model's
  greedy answers fall into loops of one to four tokens, so a near-tie of the
  float32 logits repeats for hundreds of served positions of the same
  request: there ANY bf16 arithmetic reads the tie's gap as its p99 (0.205
  on one seed of eighteen, the reference in bf16 to the digit), and the
  reference in e4m3 reads hardly more (0.243); away from those positions a
  sound run reads 0.0006-0.0018 and the e4m3 control 0.018-0.104 (PERF.md
  section 2). One more reference pass a run, after the window;
- `pick_sample` takes three finished requests: the longest, one whose whole
  context stayed inside the window (its window layers never gave a page
  back), one whose context crossed window + chunk (pages went back under it
  in prefill already);
- `obs["chunks"]`, [start, end, first position, real tokens] of every
  `engine.prefill_step`, for the reader that charges `window_prefill` its
  real tokens; `obs["window_bursts"]`, [seconds, pages the window walks read,
  pages whole walks would have] of every decode burst, from the engine's own
  `last_burst_window` (what the decode program counted); `obs["page"]`, the
  page's tokens; `obs["expert_bursts"]` and `obs["admits"]` as the drivers it
  wraps leave them;
- the series' `pages_a_slot_max`, the most pages one slot held in each page
  group after any chunk or burst (the engine's `pages_held(a_slot=True)`),
  and `window_pages_freed`, the pages the window group got back.

Composed, not copied, and swapped in ONE place each for the time of the run,
as the drivers it wraps do; it goes with them when a `benchmark` PR lets
`drivers/serve.py` check in blocks and `obs["spans"]` carry attributes
(PERF.md section 7). A program whose engine has no `last_burst_window` (one
before the window group) leaves `window_bursts` empty.
"""

from __future__ import annotations

import time

import numpy as np

from perf.drivers import serve, serve_by_leaf_admits, serve_long_by_leaf
from perf.lib import compare, dealt


def mean_gap_past_bf16(gaps: np.ndarray, same: np.ndarray) -> float:
    """Mean of `gaps` over the positions where `same`, the gaps of the tokens
    the reference computed in bf16 puts first, is 0: what the tokens compared
    lose to the float32 reference where bf16 arithmetic alone loses nothing.
    No such position: inf."""
    at = same == 0
    return float(gaps[at].mean()) if at.any() else float("inf")


def reference_checks(ctx, params, sample: list) -> compare.Checks:
    checks = compare.Checks()
    lim = ctx.traffic["limits"]
    if not sample:
        checks.add("served_requests_sampled", float("inf"), 0.0,
                   "no finished request to compare")
        return checks
    gaps, same = (np.concatenate(serve_long_by_leaf.reference_gaps(
        ctx, params, sample, quant)) for quant in (None, "bf16"))
    checks.add("served_token_logit_gap_max", float(gaps.max()),
               lim["served_token_gap"],
               f"{len(gaps)} served tokens of {len(sample)} requests, "
               f"contexts {sorted(len(p) + len(s) for p, s in sample)}")
    checks.add("served_token_logit_gap_past_bf16",
               mean_gap_past_bf16(gaps, same),
               lim["served_token_gap_past_bf16"],
               f"mean over {int((same == 0).sum())} positions; "
               f"{int((gaps > 0).sum())} served tokens and "
               f"{int((same > 0).sum())} of the reference's in bf16 off the "
               f"reference's best, p99 {np.percentile(gaps, 99.0):.4g} and "
               f"{np.percentile(same, 99.0):.4g}")
    return checks


def three_contexts(window: int, crossed: int):
    """A `pick_sample`: the longest finished request, one whose context
    stayed inside `window`, one whose context passed `crossed` (each where
    there is one)."""

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
        picked += [c for c in order if size(c) <= window
                   and c is not longest][:1]
        picked += [c for c in order if size(c) > crossed
                   and c is not longest][:1]
        return [(list(by_rid[c.rid]["prompt"]), [int(t) for t in c.tokens])
                for c in picked]

    return pick


def run(ctx) -> dict:
    chunks, bursts, peak, build = [], [], {}, serve.build_engine
    engines = []

    def noting(ctx, tracer=None):
        model, params, engine = build(ctx, tracer)
        chunk, step = engine.prefill_step, engine.step_burst
        engines.append(engine)

        def note_pages():
            if hasattr(engine, "wgroup"):
                for group, n in engine.pages_held(a_slot=True).items():
                    peak[group] = max(peak.get(group, 0), n)

        def prefill_step(slot):
            t, first = time.monotonic(), int(engine._len[slot])
            done = chunk(slot)
            chunks.append([t, time.monotonic(), first,
                           int(engine._len[slot]) - first])
            note_pages()
            return done

        def step_burst():
            out = step()
            seen = getattr(engine, "last_burst_window", None)
            if seen is not None:
                bursts.append([time.monotonic(), *seen])
            note_pages()
            return out

        engine.prefill_step, engine.step_burst = prefill_step, step_burst
        return model, params, engine

    whole = (serve.reference_gaps, serve.reference_checks, serve.pick_sample)
    serve.build_engine = noting
    serve.reference_gaps = serve_long_by_leaf.reference_gaps
    serve.reference_checks = reference_checks
    window = ctx.config["sliding_window_size"]
    serve.pick_sample = three_contexts(
        window, window + ctx.traffic["engine"]["prefill_chunk"])
    try:
        with dealt.one_order():
            result = serve_by_leaf_admits.run(ctx)
    finally:
        serve.build_engine = build
        (serve.reference_gaps, serve.reference_checks,
         serve.pick_sample) = whole
    obs = result["obs"]
    obs["chunks"], obs["window_bursts"] = chunks, bursts
    obs["page"] = ctx.traffic["engine"]["page"]
    if bursts:
        near, whole_walk = (sum(b[i] for b in bursts) for i in (1, 2))
        result["series"]["window_pages_walked_share"] = \
            near / max(whole_walk, 1)
    if peak:
        result["series"]["pages_a_slot_max"] = peak
        result["series"]["window_pages_freed"] = \
            engines[-1].window_pages_freed
    return result
