"""`drivers/serve_by_leaf_admits.py` and `drivers/serve_by_leaf_p99.py` at
once: `obs["admits"]` for the reader that counts a prefill's REAL tokens
(`flood_gdn_scan_roofline`), and the 99th percentile of the served tokens'
logit gaps compared beside their maximum (`limits.served_token_gap_p99`), for
a model whose 512-wide softmax router flips near-tied picks under random
weights as kanana's sigmoid one does (PERF.md section 2).

And the one schedule of `lib/dealt.py`: the cell floods, so a window serves
the first third of what it is offered, and an order drawn from `--seed` made
each run serve another sample of the prompts (4-9% of `serve_tok_s` between
the quartiles of six runs, which the driver's check refused). Every seed now
gets the same requests at the same instants in the same order, a
low-discrepancy one, and draws the token ids and the weights.

Composed, not copied: `serve_by_leaf_p99.run` wraps the check and then runs
the driver it names `serve_by_leaf`; for the time of the run that name is
`serve_by_leaf_admits`, whose `run` wraps the engine's `admit` and runs
`serve_by_leaf` itself. It goes with its parts (PERF.md section 7).
"""

from __future__ import annotations

from perf.drivers import serve_by_leaf_admits, serve_by_leaf_p99
from perf.lib import dealt


def run(ctx) -> dict:
    inner = serve_by_leaf_p99.serve_by_leaf
    serve_by_leaf_p99.serve_by_leaf = serve_by_leaf_admits
    try:
        with dealt.one_order():
            return serve_by_leaf_p99.run(ctx)
    finally:
        serve_by_leaf_p99.serve_by_leaf = inner
