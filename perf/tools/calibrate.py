#!/usr/bin/env python3
"""Read what a cell's `correct` limits are set from, on the chip.

    python3 perf/tools/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 [--seconds 12]

One process, because set-up is long. For each seed it makes the program's
readings at the cell's own size — for a training cell the first three steps
(no window), for a serving cell a short window at the cell's own load — and
the plain reference's, and prints both. For the first `--control-seeds` seeds
it also puts the CONTROL in the program's place: the reference computed in
the next precision down (`quant="fp8"` for a configuration that states
bfloat16), which has to come out as not correct. `PERF.md` records the
largest sound reading and the smallest control reading beside each limit.
Never part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run as harness  # noqa: E402


def train_seed(ctx, control: bool, quants=("fp8", "bf16")) -> dict:
    import jax

    from perf.drivers import train

    trainer, fed, seen = train.start_program(
        ctx, os.path.join(ctx.outdir, "calibrate_metrics.jsonl"))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        trainer.state.params)
    del trainer
    gc.collect()

    def read(got, want):
        g = train.gaps(got, want)
        return {"loss_rel_gap": max(g["loss_rel_gap"]),
                "grad_norm_gap": g["grad_norm_gap"],
                "param_change_gap": g["param_change_gap"]}

    want = train.reference_follows(ctx, fed, abstract)
    out = {"seed": ctx.seed, "program": read(seen, want)}
    if control:
        for quant in quants:
            out[f"control_{quant}"] = read(
                train.reference_follows(ctx, fed, abstract, quant), want)
    return out


def serve_seeds(ctx, seeds: list, n_control: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from ddp_practice_tpu.serve.engine import warm_engine
    from ddp_practice_tpu.serve.scheduler import Scheduler

    from perf.drivers import serve
    from perf.lib import traffic as traffic_lib, weights

    # every request offered is waited for, whatever the cell does
    ctx.traffic = dict(ctx.traffic,
                       drain_limit_s=ctx.traffic["drain_limit_s"] or 60.0)
    family = serve.family_of(ctx.config)
    _, params, engine = serve.build_engine(ctx)
    warm_engine(engine)
    for i, seed in enumerate(seeds):
        ctx.seed = seed
        params = weights.make_params(params, seed, dtype=jnp.bfloat16)
        engine.params = params
        rows = traffic_lib.build_schedule(
            ctx.traffic, seed=seed, duration_s=ctx.seconds,
            vocab=family.vocab(ctx.config))
        sched = Scheduler(engine,
                          max_queue=ctx.traffic["engine"]["max_queue"])
        serve.serve_window(ctx, sched, rows)
        ok = [c for c in sched.completions if c.status == "length"]
        by_rid = {r["rid"]: r for r in rows}
        sample = serve.pick_sample(ok, by_rid, seed,
                                   ctx.traffic["check"]["requests"],
                                   ctx.traffic["engine"]["buckets"])
        gaps = np.concatenate(serve.reference_gaps(ctx, params, sample))
        out = {"seed": seed, "finished": len(ok), "requests": len(sample),
               "tokens": len(gaps),
               "program_gap_max": float(gaps.max()),
               "program_gap_p99": float(np.percentile(gaps, 99)),
               "program_off_argmax": int((gaps > 0).sum())}
        if i < n_control:
            for quant in ("fp8", "bf16"):
                # the tokens the lower precision puts first, position by
                # position, judged by the reference's logits
                g = np.concatenate(
                    serve.reference_gaps(ctx, params, sample, quant))
                out[f"control_{quant}_gap_max"] = float(g.max())
                out[f"control_{quant}_off_argmax"] = int((g > 0).sum())
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=5_000_000_011)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--quants", default="fp8,bf16",
                    help="the control's precisions (training cells)")
    ap.add_argument("--traffic", help="a file of perf/traffic/ to offer in "
                    "place of the cell's own (one that is no cell yet)")
    args = ap.parse_args(argv)
    opened = harness.open_cell(args.workload)
    if isinstance(opened, int):
        return opened
    _, cell, config, traffic, devices, chip_peaks = opened
    if args.traffic:
        with open(os.path.join(ROOT, "perf", "traffic",
                               args.traffic + ".json")) as f:
            traffic = json.load(f)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    ctx = harness.make_ctx(cell, config, traffic, seed=seeds[0],
                           seconds=args.seconds, trace=False,
                           devices=devices, chip_peaks=chip_peaks)
    if traffic["driver"] == "serve":
        serve_seeds(ctx, seeds, args.control_seeds)
        return 0
    for i, seed in enumerate(seeds):
        ctx.seed = seed
        print(json.dumps(train_seed(ctx, i < args.control_seeds,
                                    args.quants.split(","))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
