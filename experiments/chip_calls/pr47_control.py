"""perf/run.py on one seed of ling3_serve_reason, with the check wrapped so
that, after the cell's own numbers, each control goes through the SAME
`serve_window_by_leaf.reference_checks` at the traffic file's limits, on the
same sample, and prints the `correct` it would have got:

- `e4m3`: the tokens that the reference with every matmul operand rounded
  to e4m3 puts first;
- `state_lost`: the tokens of the reference whose recurrent state and conv
  memory are zeroed at every chunk boundary INSIDE the prompt
  (`state_reset` = prefill_chunk, `reset_until` = the prompt's length: none
  at or after the first served token), which is what a program that lost a
  slot's state between two chunks of its prompt would serve.

Each control's gaps stand in for the served tokens' (`reference_gaps` with
`quant` None); the bf16 reading is the run's own. Scratch: never part of the
benchmark."""
import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perf import run as harness  # noqa: E402
from perf.drivers import serve_long_by_leaf, serve_window_by_leaf  # noqa: E402
from perf.lib import compare  # noqa: E402

whole = serve_window_by_leaf.reference_checks
gaps_of = serve_long_by_leaf.reference_gaps
OUT = os.path.join(ROOT, "chiprun_out", os.environ.get("OUT", "ctl"))
os.makedirs(OUT, exist_ok=True)


def state_lost_gaps(ctx, params, sample, every):
    import jax
    import jax.numpy as jnp

    cfg, check = ctx.config, ctx.traffic["check"]
    ref = importlib.import_module(f"perf.reference.{cfg['reference']}")
    rows, count = check["pad_rows"], check["served_rows"]
    read = jax.jit(lambda p, t, first, picks: compare.token_gaps(
        ref.forward(p, t, cfg, None, at=(first, count))[0], picks))
    best = jax.jit(lambda p, t, first, until: ref.forward(
        p, t, cfg, None, at=(first, count), state_reset=every,
        reset_until=until)[0].argmax(-1).astype(jnp.int32))
    out = []
    for prompt, served in sample:
        seq = prompt + served
        at = len(prompt) - 1
        k = min(len(served), count)
        width = -(-max(len(seq), at + count) // rows) * rows
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :len(seq)] = seq
        tokens = jnp.asarray(tokens)
        picks = best(params, tokens, at, len(prompt))
        out.append(np.asarray(read(params, tokens, at, picks),
                              np.float32)[:k])
    return out


def with_controls(ctx, params, sample):
    kept = {}

    def noting(ctx, params, sample, quant=None):
        kept[quant or "f32"] = gaps_of(ctx, params, sample, quant)
        return kept[quant or "f32"]

    serve_long_by_leaf.reference_gaps = noting
    try:
        checks = whole(ctx, params, sample)
    finally:
        serve_long_by_leaf.reference_gaps = gaps_of
    if not sample:
        return checks
    chunk = ctx.traffic["engine"]["prefill_chunk"]
    row = {"seed": ctx.seed, "prompts": [len(p) for p, s in sample],
           "served": [len(s) for p, s in sample],
           "after_last_boundary": [(len(p) - 1) % chunk for p, s in sample]}
    print("CONTROL sample " + json.dumps(row), flush=True)
    controls = {
        "e4m3": lambda: gaps_of(ctx, params, sample, "fp8"),
        "state_lost": lambda: state_lost_gaps(ctx, params, sample, chunk)}
    saved = {k: np.concatenate(v) for k, v in kept.items()}
    for name, make in controls.items():
        gaps = make()
        saved[name] = np.concatenate(gaps)
        serve_long_by_leaf.reference_gaps = \
            lambda c, p, s, quant=None: gaps if quant is None else kept[quant]
        try:
            verdict = whole(ctx, params, sample)
        finally:
            serve_long_by_leaf.reference_gaps = gaps_of
        per = [float(g.max()) for g in gaps]
        print(f"CONTROL {name} correct={verdict.correct} "
              f"max_by_request={per}", flush=True)
        for line in verdict.lines():
            print("CONTROL " + name + " " + line, flush=True)
    np.savez(os.path.join(OUT, f"gaps_{ctx.seed}.npz"), **saved)
    return checks


if __name__ == "__main__":
    serve_window_by_leaf.reference_checks = with_controls
    sys.exit(harness.main(sys.argv[1:]))
