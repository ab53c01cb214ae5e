"""`drivers/serve_by_leaf.py`, with one key more in `obs`: `admits`,
[start, end, real prompt tokens] of every `engine.admit` call (monotonic
seconds), for the readers that must know how many REAL tokens the prefills
of the traced slice held (`flood_sel_scan_roofline`: a prompt's scan is
charged its real tokens, not its bucket's).

Why a driver. The program says it already: its `prefill` span carries
`prompt_len`, `bucket`, `scan_tokens` and `scan_padded`. But `obs["spans"]`
is [name, t0, t1] without attributes (PERF.md section 7 row 9), and
`drivers/serve.py` is not this PR's to edit, so the count is taken here,
around the engine's own `admit`, for the time of the run. It goes when a
`benchmark` PR lets `obs["spans"]` carry attributes.

The sweep and the calibration reach a cell of this driver as one of
`serve_by_leaf` (`tools/by_leaf.py` knows that name alone): PERF.md section
7 gives the one-line recipe.
"""

from __future__ import annotations

import time

from perf.drivers import serve, serve_by_leaf


def run(ctx) -> dict:
    admits, build = [], serve.build_engine

    def noting(ctx, tracer=None):
        model, params, engine = build(ctx, tracer)
        admit = engine.admit

        def noted(prompt, **kw):
            t = time.monotonic()
            slot = admit(prompt, **kw)
            admits.append([t, time.monotonic(), len(prompt)])
            return slot

        engine.admit = noted
        return model, params, engine

    serve.build_engine = noting
    try:
        result = serve_by_leaf.run(ctx)
    finally:
        serve.build_engine = build
    result["obs"]["admits"] = admits
    return result
