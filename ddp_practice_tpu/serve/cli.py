"""`python -m ddp_practice_tpu.cli serve`: serve prompts from a checkpoint.

Loads a trained LM checkpoint (generate.py load_lm), puts every --prompt
through one PagedEngine behind a Scheduler (continuous batching: the
prompts share the decode batch at slot granularity; the block pool is
sized so that every slot can hold its bucketed prompt and
--max_new_tokens at once), prints each
completion with its status and time to first token, then the run's
ServeMetrics as one log line. Measurement is not done here: the
benchmark is perf/run.py (BENCHMARK.json), its results are in PERF.md.
"""

from __future__ import annotations

import argparse
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "ddp_practice_tpu serve",
        description="continuous-batching serving: serve prompts from a "
                    "trained LM checkpoint",
    )
    p.add_argument("--ckpt_dir", required=True,
                   help="serve the --prompt strings from this LM "
                        "checkpoint")
    p.add_argument("--prompt", action="append", default=None,
                   help="repeatable; byte-level prompt(s) to serve")
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--top_p", type=float, default=0.0)
    p.add_argument("--eos_id", type=int, default=None)
    p.add_argument("--max_slots", type=int, default=4)
    p.add_argument("--decode_burst", type=int, default=None,
                   help="decode steps per dispatch (amortizes host "
                        "overhead; releases are burst-granular; "
                        "default: 1)")
    p.add_argument("--trace-out", "--trace_out", dest="trace_out",
                   default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of the request "
                        "lifecycle (queued/prefill/decode-burst spans; "
                        "pid=replica, tid=slot); open in Perfetto, "
                        "validate with tools/check_traces.py")
    p.add_argument("--seed", type=int, default=0)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.generate import load_lm
    from ddp_practice_tpu.inference import decode_bytes, encode_bytes
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler
    from ddp_practice_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    # every serving run names the device it ran on
    print(f"[serve] platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={jax.device_count()}")
    model, params, batch_stats, _ = load_lm(args.ckpt_dir)
    prompts = args.prompt or ["\n"]
    max_prompt = max(len(p.encode("utf-8")) for p in prompts)
    bucket = 8
    while bucket < max_prompt:
        bucket *= 2
    burst = args.decode_burst or 1
    # a slot's span: the bucketed prompt, the tokens asked for, and the
    # last burst's overshoot (the scheduler budgets whole bursts)
    span = bucket + args.max_new_tokens + burst
    engine = PagedEngine(
        model, params,
        EngineConfig(
            max_slots=args.max_slots,
            prompt_buckets=(bucket,),
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id,
            decode_burst=burst,
            max_blocks_per_slot=-(-span // EngineConfig.block_size),
        ),
        batch_stats=batch_stats,
    )
    tracer = None
    if args.trace_out:
        from ddp_practice_tpu.utils.trace import TraceRecorder, label_replica

        tracer = TraceRecorder()
        engine.set_tracer(tracer, 0)
        label_replica(tracer, 0, args.max_slots)
    metrics = ServeMetrics()
    sched = Scheduler(engine, metrics=metrics, tracer=tracer)
    t0 = time.monotonic()
    for i, text in enumerate(prompts):
        toks = encode_bytes(text)[0].tolist()
        sched.submit(Request(
            rid=i, prompt=toks, max_new_tokens=args.max_new_tokens,
            seed=args.seed,
        ))
    completions = sched.run_until_idle()
    elapsed = time.monotonic() - t0
    for c in sorted(completions, key=lambda c: c.rid):
        toks = c.tokens
        if args.eos_id is not None and args.eos_id in toks:
            toks = toks[: toks.index(args.eos_id)]
        print(f"--- request {c.rid} [{c.status}] "
              f"ttft {c.ttft:.3f}s ---" if c.ttft is not None
              else f"--- request {c.rid} [{c.status}] ---")
        print(prompts[c.rid] + decode_bytes(jnp.asarray(toks)))
    metrics.emit(elapsed)
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"wrote trace to {args.trace_out} ({len(tracer)} events)")
    return 0

