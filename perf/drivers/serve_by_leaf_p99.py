"""`drivers/serve_by_leaf.py`, with one number more compared for `correct`:
the 99th percentile of the served tokens' logit gaps, beside their maximum.

Why. A model whose expert routing is chaotic under its random weights (128
sigmoid-routed experts, top-6: the 6th and 7th scores of a token lie 0.07
apart and one changed pick moves the next layer's scores by more) turns any
rounding into a different set of experts for 2-3% of tokens, in bfloat16
already. The MAXIMUM over ~1,800 served tokens is then the largest of some
forty such events, and reads alike whatever the precision (on the chip, PR
30: 1.12-1.86 over 17 sound seeds, 1.79-2.00 for the e4m3 control: they
overlap). How OFTEN it happens is what the precision sets, and the 99th
percentile reads it: 0.67-0.69 sound, 1.33-1.38 e4m3 (PERF.md section 2).
So the traffic file of such a cell gives two limits: `served_token_gap` (the
maximum: a guard against a wrong token, which reads over 3) and
`served_token_gap_p99` (the precision's).

`drivers/serve.py reference_checks` is not this PR's to edit, so the check is
added here, around it, for the time of the run; the gaps are read once more
(a few seconds of the reference). It goes when a `benchmark` PR lets
`reference_checks` compare a quantile (PERF.md section 7).
"""

from __future__ import annotations

import numpy as np

from perf.drivers import serve, serve_by_leaf


def run(ctx) -> dict:
    whole = serve.reference_checks

    def with_p99(ctx, params, sample):
        checks = whole(ctx, params, sample)
        if sample:
            gaps = np.concatenate(serve.reference_gaps(ctx, params, sample))
            checks.add("served_token_logit_gap_p99",
                       float(np.percentile(gaps, 99.0)),
                       ctx.traffic["limits"]["served_token_gap_p99"],
                       f"{len(gaps)} served tokens, "
                       f"{int((gaps > 0).sum())} off the reference's best")
        return checks

    serve.reference_checks = with_p99
    try:
        return serve_by_leaf.run(ctx)
    finally:
        serve.reference_checks = whole
