"""`drivers/serve.py` for a configuration too large for ONE draw of all its
weights: the same driver, run as it is, with the weights drawn a leaf
at a time (`lib/weights_by_leaf.py`). The readers get the same `obs`, and one
key more: `expert_bursts`, [seconds, held experts touched] of every decode
burst, from the engine's own `last_burst_experts` (what the decode program
counted: `obs["spans"]` carries no attributes), for the readers that need to
know how many experts a step had to stream.

Every attribute this swaps in files that are not this PR's to edit is swapped
in ONE place, `by_leaf()`, and put back when it closes. It goes when a
`benchmark` PR lets `lib/weights.py` draw by leaf for every cell and
`obs["spans"]` carry attributes (PERF.md section 7).
"""

from __future__ import annotations

import contextlib
import time

from perf.drivers import serve
from perf.lib import weights, weights_by_leaf


@contextlib.contextmanager
def by_leaf(bursts: list | None = None, harness=None):
    """While open: `lib/weights.py make_params` (the driver's
    `build_engine`, the calibration's re-draw) draws by leaf; with `bursts`,
    every engine `build_engine` makes notes into it, after each decode
    burst, the instant and what its expert layers touched; with `harness`
    (`perf/run.py`, for `tools/by_leaf.py`), its `open_cell` hands a traffic
    file of this driver over as the serving one the tools know."""
    whole, build = weights.make_params, serve.build_engine
    open_cell = harness.open_cell if harness is not None else None

    def noting(ctx, tracer=None):
        model, params, engine = build(ctx, tracer)
        step = engine.step_burst

        def step_burst():
            out = step()
            seen = getattr(engine, "last_burst_experts", None)
            if seen is not None:
                bursts.append([time.monotonic(), seen[1]])
            return out

        engine.step_burst = step_burst
        return model, params, engine

    def as_serving(name):
        opened = open_cell(name)
        if isinstance(opened, int):
            return opened
        opened = list(opened)
        if opened[3]["driver"] == "serve_by_leaf":
            opened[3] = dict(opened[3], driver="serve")
        return tuple(opened)

    weights.make_params = weights_by_leaf.make_params
    if bursts is not None:
        serve.build_engine = noting
    if harness is not None:
        harness.open_cell = as_serving
    try:
        yield
    finally:
        weights.make_params, serve.build_engine = whole, build
        if harness is not None:
            harness.open_cell = open_cell


def run(ctx) -> dict:
    bursts = []
    with by_leaf(bursts):
        result = serve.run(ctx)
    result["obs"]["expert_bursts"] = bursts
    if bursts:
        result["series"]["experts_touched_a_burst"] = \
            sum(n for _, n in bursts) / len(bursts)
    return result
