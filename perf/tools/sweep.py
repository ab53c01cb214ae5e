#!/usr/bin/env python3
"""Find a serving cell's knee: one process, one engine, several rates.

    python3 perf/tools/sweep.py --workload gpt2s_serve_flood \
        --traffic chat_poisson --rates 8,12,16,20,24 --seconds 20 [--seed 1]

For each rate the cell's traffic file is offered at that rate for a short
window and drained; a line of JSON says what the tail did, what share of
requests met the file's `slo`, and whether the backlog grew. Run once, when a
cell is defined (the rate it then fixes goes into its traffic file); never
part of a benchmark run.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
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
    from ddp_practice_tpu.serve.engine import warm_engine
    from ddp_practice_tpu.serve.scheduler import Scheduler

    from perf.drivers import serve
    from perf.lib import stats, traffic as traffic_lib

    # every rate is drained, whatever the cell does
    traffic = dict(traffic, drain_limit_s=traffic["drain_limit_s"] or 60.0)
    ctx = harness.make_ctx(cell, config, traffic, seed=args.seed,
                           seconds=args.seconds, trace=False,
                           devices=devices, chip_peaks=chip_peaks)
    _, _, engine = serve.build_engine(ctx)
    warm_engine(engine)
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = copy.deepcopy(traffic)
        for t in tr["tenants"]:
            t["rate_rps"] = rate / len(tr["tenants"])
        ctx.traffic = tr
        rows = traffic_lib.build_schedule(
            tr, seed=args.seed, duration_s=args.seconds,
            vocab=serve.family_of(config).vocab(config))
        sched = Scheduler(engine, max_queue=tr["engine"]["max_queue"])
        win = serve.serve_window(ctx, sched, rows)
        w0 = win["w0"]
        ok = [c for c in sched.completions if c.status == "length"]
        ttft = [c.ttft * 1e3 for c in ok]
        tpot = [c.tpot * 1e3 for c in ok if c.tpot is not None]
        slo = tr["slo"]
        met = sum(1 for c in ok if c.ttft * 1e3 <= slo["ttft_ms"]
                  and (c.tpot is None or c.tpot * 1e3 <= slo["tpot_ms"]))
        ticks = [(a - w0, q, s) for a, _, s, _, q in win["ticks"]]
        busy = [s for t, _, s in ticks if s and t <= args.seconds]
        q_at = lambda t: ([q for a, q, _ in ticks if a <= t] or [0])[-1]
        toks = sum(len(c.tokens) for c in sched.chunks
                   if w0 <= c.t < w0 + args.seconds)
        print(json.dumps({
            "rate_rps": rate, "offered": len(rows), "finished": len(ok),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "tpot_p50_ms": stats.percentile(tpot, 50),
            "tpot_p95_ms": stats.percentile(tpot, 95),
            "met_share": met / max(len(rows), 1),
            "queue_mid": q_at(0.5 * args.seconds),
            "queue_end": q_at(args.seconds),
            "slots_mean": sum(busy) / max(len(busy), 1),
            "slots_max": max(busy, default=0),
            "tok_s": toks / args.seconds,
            "drain_s": win["end"] - w0 - args.seconds,
        }), flush=True)
        if not sched.idle:  # a rate far past the knee: start clean
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
