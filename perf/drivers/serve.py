"""The serving cells: `PagedEngine` behind `Scheduler`, under open-loop load.

What is the program's: `models.create_model`, `serve.engine.PagedEngine` with
its `warm_engine` recipe, `serve.scheduler.Scheduler` and its `Completion`
records (time to first token from the request's own arrival stamp, mean gap
per request, flight record), the engine's tracer spans. What is the
benchmark's: the weights (made on the device in the served type; no
checkpoint is written or restored), the schedule (`lib/traffic.py`, drawn
before the window), the generator thread, the loop that turns the scheduler,
the clock, the sample of served requests that the plain reference reads after
the window, and every number's arithmetic.

A request's `arrival` is stamped with the instant it was DUE, so a stall
counts against every request it delays. The generator thread only hands
requests over at their due time; the serving loop is one thread, like the
program's own server loop.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import queue
import threading
import time

import numpy as np

from perf.lib import compare, stats, traffic as traffic_lib, weights


def family_of(cfg: dict):
    """`perf/families/<family>.py`: what knows this configuration's keys."""
    return importlib.import_module(f"perf.families.{cfg['family']}")


def build_engine(ctx, tracer=None):
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.config import PrecisionPolicy
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    cfg, eng = ctx.config, ctx.traffic["engine"]
    family = family_of(cfg)
    family.prepare(cfg)
    model = create_model(cfg["program_model"], policy=PrecisionPolicy.bf16(),
                         **family.model_options(cfg))
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    params = weights.make_params(abstract, ctx.seed, dtype=jnp.bfloat16)
    ctx.clock.mark("weights made on the device from the seed (bf16)")
    # the file's short names for four fields; any other field of the
    # program's EngineConfig (prefix_cache, prefill_chunk, ...) by its own
    named = {"buckets", "page", "burst", "max_queue"}
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = set(eng) - named - fields
    if unknown:
        raise ValueError(f"engine keys {sorted(unknown)} are not fields of "
                         "the program's EngineConfig")
    engine = PagedEngine(model, params, EngineConfig(
        prompt_buckets=tuple(eng["buckets"]), block_size=eng["page"],
        decode_burst=eng["burst"], temperature=0.0,
        **{k: v for k, v in eng.items() if k in fields},
    ))
    if tracer is not None:
        engine.set_tracer(tracer)
    return model, params, engine


class Generator(threading.Thread):
    """Hands each request to the serving loop at its due time and notes
    when it really did."""

    def __init__(self, rows: list, t0: float, inbox) -> None:
        super().__init__(daemon=True, name="perf-generator")
        self.rows, self.t0, self.inbox = rows, t0, inbox
        self.stop = threading.Event()
        self.done = threading.Event()

    def run(self) -> None:
        for row in self.rows:
            wait = self.t0 + row["due_s"] - time.monotonic()
            if wait > 0 and self.stop.wait(wait):
                break
            if self.stop.is_set():
                break
            self.inbox.put((row, time.monotonic()))
        self.done.set()


def live_tokens(sched, finished: list, burst: int) -> tuple:
    """(slots that decoded, real tokens they held before the burst) of the
    tick that just ran: the requests still running and those it finished,
    each with its prompt and the tokens it had before this burst's (never
    more than it really held, so least bytes are never counted high)."""
    running = [(len(st.req.prompt), len(st.tokens))
               for st in sched.running.values() if not st.prefilling]
    ended = [(c.flight["prompt_tokens"], len(c.tokens)) for c in finished
             if c.status in ("length", "eos")]
    both = running + ended
    return len(both), sum(p + max(n - burst, 0) for p, n in both)


def serve_window(ctx, sched, rows: list) -> dict:
    """Offer `rows` from now on and turn the scheduler until the window has
    closed and, where the traffic file gives a `drain_limit_s` above 0,
    until every request offered is finished or that many seconds more have
    passed."""
    from ddp_practice_tpu.serve.scheduler import Request

    inbox = queue.SimpleQueue()
    trace_on = ctx.trace
    t_trace0 = ctx.traffic["trace_start_share"] * ctx.seconds
    t_trace1 = t_trace0 + min(ctx.traffic["trace_seconds"],
                              0.5 * ctx.seconds)
    drain = ctx.traffic["drain_limit_s"] > 0
    w0 = time.monotonic()
    gen = Generator(rows, w0, inbox)
    gen.start()
    sent, ticks, traced, tracing = {}, [], None, False
    burst = ctx.traffic["engine"]["burst"]
    limit = w0 + ctx.seconds + ctx.traffic["drain_limit_s"]
    import jax

    def take(row, at) -> None:
        sent[row["rid"]] = at
        sched.submit(Request(
            rid=row["rid"], prompt=row["prompt"],
            max_new_tokens=row["max_new"], seed=row["rid"],
            arrival=w0 + row["due_s"], tenant=row["tenant"]))

    def close_slice() -> tuple:
        marker.__exit__(None, None, None)
        t_b = time.monotonic()
        ctx.stop_trace()
        return t_a, t_b

    while True:
        now = time.monotonic()
        if trace_on and not tracing and traced is None \
                and now - w0 >= t_trace0:
            ctx.start_trace(ctx.trace_dir)
            marker = jax.profiler.TraceAnnotation("perf:traced")
            marker.__enter__()
            tracing, t_a = True, time.monotonic()
        if tracing and now - w0 >= t_trace1:
            tracing, traced = False, close_slice()
        while True:
            try:
                take(*inbox.get_nowait())
            except queue.Empty:
                break
        closed = now - w0 >= ctx.seconds
        if closed and not drain:
            break
        if now > limit:
            break
        if sched.idle:
            if closed and gen.done.is_set() and inbox.empty():
                break
            try:  # nothing to do: sleep until the generator speaks
                take(*inbox.get(timeout=0.02))
            except queue.Empty:
                pass
            continue
        t0, bursts = time.monotonic(), sched.engine.burst_seq
        finished = sched.step()
        if sched.engine.burst_seq > bursts:
            slots, live = live_tokens(sched, finished, burst)
        else:
            slots, live = 0, 0
        ticks.append((t0, time.monotonic(), slots, live,
                      len(sched.queue)))
    if tracing:
        traced = close_slice()
    gen.stop.set()
    gen.join(timeout=5.0)
    return {"w0": w0, "w1": w0 + ctx.seconds, "sent": sent, "ticks": ticks,
            "traced": traced, "end": time.monotonic()}


def run(ctx) -> dict:
    from ddp_practice_tpu.serve.engine import warm_engine
    from ddp_practice_tpu.serve.scheduler import Scheduler

    from perf.lib import xtrace

    cfg, tr = ctx.config, ctx.traffic
    family = family_of(cfg)
    # below the knee a cell drains: every request offered is waited for and
    # counts; above it the backlog is the point and the window just closes
    drain = tr["drain_limit_s"] > 0
    tracer = None
    if ctx.trace:
        from ddp_practice_tpu.utils.trace import TraceRecorder

        tracer = TraceRecorder(max_events=1 << 20)
    model, params, engine = build_engine(ctx, tracer)
    ctx.clock.mark("model, engine and page pool built")
    for i, width in enumerate(engine.buckets):
        warm_engine(engine, widths=[width])
        ctx.clock.mark(f"warm_engine: prefill of bucket {width}"
                       + (" and the decode burst" if i == 0 else ""))
    rows = traffic_lib.build_schedule(
        tr, seed=ctx.seed, duration_s=float(ctx.seconds),
        vocab=family.vocab(cfg))
    offered = traffic_lib.offered_summary(rows, float(ctx.seconds))
    sched = Scheduler(engine, max_queue=tr["engine"]["max_queue"],
                      tracer=tracer)
    ctx.clock.mark("schedule drawn from the seed")
    setup_s = time.monotonic() - ctx.t_start

    win = serve_window(ctx, sched, rows)
    w0, w1 = win["w0"], win["w1"]
    compiles = ctx.compiles.count_between(w0, win["end"])
    peak = ctx.memory_peak()
    spans = ctx.program_spans(tracer)
    done = {c.rid: c for c in sched.completions}
    chunks = [(c.t, len(c.tokens)) for c in sched.chunks]
    by_rid = {r["rid"]: r for r in rows}

    # --------------------------------------------------------- the numbers
    # The rate is read in every run and the tail in every run that drains;
    # the manifest says which is the cell's end-to-end metric.
    ok = [c for c in done.values() if c.status == "length"]
    failed = sum(1 for c in done.values() if c.status != "length")
    tokens_in_window = sum(n for t, n in chunks if w0 <= t < w1)
    metrics = {"setup_s": setup_s,
               "serve_tok_s": tokens_in_window / (w1 - w0)}
    attempted = len(win["sent"])
    if drain:
        # a tail is the tail of ALL requests, so it exists only where all
        # were waited for. Offered and never finished: failed, and its wait
        # so far (due to the end of the drain) stands in the tail
        lost = [r for r in rows if r["rid"] not in done]
        attempted, failed = len(rows), failed + len(lost)
        ttft = [c.ttft * 1e3 for c in ok if c.ttft is not None] \
            + [(win["end"] - w0 - r["due_s"]) * 1e3 for r in lost]
        metrics["ttft_p95_ms"] = stats.percentile(ttft, 95.0)
    requests = [{
        "rid": c.rid, "due_s": by_rid[c.rid]["due_s"],
        "prompt": len(by_rid[c.rid]["prompt"]), "tokens": len(c.tokens),
        "status": c.status, "ttft_ms": None if c.ttft is None
        else c.ttft * 1e3, "tpot_ms": None if c.tpot is None
        else c.tpot * 1e3,
        "wait_ms": (c.flight["queue_s"] + c.flight["stall_s"]) * 1e3,
        "prefill_ms": c.flight["prefill_s"] * 1e3,
        "late_ms": (win["sent"][c.rid] - w0 - by_rid[c.rid]["due_s"]) * 1e3,
        "finish_s": c.finish - w0,
    } for c in sorted(done.values(), key=lambda c: c.rid)]
    ticks = [{"t": a - w0, "dt": b - a, "slots": s, "live": lv, "queue": q}
             for a, b, s, lv, q in win["ticks"]]
    series = {"offered": offered, "requests": requests, "ticks": ticks,
              "tokens_in_window": tokens_in_window,
              "compiles_in_window": ctx.compiles.between(w0, win["end"]),
              "queue_at_middle": _queue_at(ticks, 0.5 * ctx.seconds),
              "queue_at_end": _queue_at(ticks, ctx.seconds),
              "limits_met_share": _attainment(requests, tr)}
    if win["traced"] is not None:
        # closing the profiler stalls the loop for seconds (it serialises
        # the trace); what a traced run says about requests and ticks ends
        # where its traced slice ends
        cut = win["traced"][1] - w0
        requests = [r for r in requests if r["finish_s"] <= cut]
        ticks = [k for k in ticks if k["t"] + k["dt"] <= cut]
    obs = {"kind": "serve", "requests": requests,
           "decode_bytes": family.decode_bytes(cfg),
           "ticks": ticks, "compiles_in_window": compiles, "spans": spans,
           "window": (w0, w1), "traced": win["traced"], "trace": None,
           "chips": ctx.chips, "burst": tr["engine"]["burst"],
           "t_origin": w0}
    if win["traced"] is not None:
        obs["trace"] = xtrace.load(xtrace.find_xplane(ctx.trace_dir))

    # ---------------------------- the reference, after the engine is freed
    sample = pick_sample(ok, by_rid, ctx.seed, tr["check"]["requests"],
                         tr["engine"]["buckets"])
    del engine, sched, done
    gc.collect()
    t_ref = time.monotonic()
    checks = reference_checks(ctx, params, sample)
    series["reference_s"] = time.monotonic() - t_ref
    return {"metrics": metrics, "attempted": attempted, "failed": failed,
            "checks": checks, "obs": obs, "series": series,
            "memory_peak_bytes": peak}


def _queue_at(ticks: list, t: float) -> int:
    before = [k["queue"] for k in ticks if k["t"] <= t]
    return before[-1] if before else 0


def _attainment(requests: list, tr: dict):
    lim = tr.get("slo")
    if not lim or not requests:
        return None
    met = sum(1 for r in requests
              if r["status"] == "length" and r["ttft_ms"] is not None
              and r["ttft_ms"] <= lim["ttft_ms"]
              and (r["tpot_ms"] is None or r["tpot_ms"] <= lim["tpot_ms"]))
    return met / len(requests)


def pick_sample(ok: list, by_rid: dict, seed: int, n: int,
                buckets: list) -> list:
    """[(prompt, served tokens)] of `n` finished requests: the longest, one
    of every prompt bucket that finished any (so no compiled prefill goes
    unread, the rare widest one included), and the rest drawn from the
    seed."""
    if not ok:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5A3]))
    order = [ok[i] for i in rng.permutation(len(ok))]
    plen = lambda c: len(by_rid[c.rid]["prompt"])
    picked = [max(ok, key=lambda c: plen(c) + len(c.tokens))]
    for lo, hi in zip([0] + list(buckets), buckets):
        picked += [c for c in order if lo < plen(c) <= hi
                   and c not in picked][:1]
    picked += [c for c in order if c not in picked][:max(n - len(picked), 0)]
    return [(list(by_rid[c.rid]["prompt"]), [int(t) for t in c.tokens])
            for c in picked]


def reference_gaps(ctx, params, sample: list, quant=None) -> list:
    """One float32 reference forward over each prompt with its served
    tokens; per request, how far each served token's logit lies below the
    reference's best at its position. With `quant` (the control) the tokens
    judged are instead those the reference computed in that lower precision
    puts first at the same positions. Reduced on the device: a request's
    logits are 200 MB at the published vocabulary."""
    import jax
    import jax.numpy as jnp

    cfg = ctx.config
    ref = importlib.import_module(f"perf.reference.{cfg['reference']}")
    width = ctx.traffic["check"]["pad_to"]
    read = jax.jit(lambda p, t, picks: compare.token_gaps(
        ref.forward(p, t, cfg, None)[0], picks))
    first = jax.jit(lambda p, t: ref.forward(p, t, cfg, quant)[0]
                    .argmax(-1).astype(jnp.int32))
    out = []
    for prompt, served in sample:
        seq = (prompt + served)[:width]
        tokens = np.zeros((1, width), np.int32)  # right pad: causal
        tokens[0, :len(seq)] = seq
        tokens = jnp.asarray(tokens)
        at = len(prompt) - 1  # served token j was chosen at position at + j
        k = min(len(served), len(seq) - at)
        if quant is None:
            picks = np.zeros(width, np.int32)
            picks[at:at + k] = served[:k]
            picks = jnp.asarray(picks)
        else:
            picks = first(params, tokens)
        out.append(np.asarray(read(params, tokens, picks),
                              np.float32)[at:at + k])
    return out


def reference_checks(ctx, params, sample: list) -> compare.Checks:
    checks = compare.Checks()
    lim = ctx.traffic["limits"]
    if not sample:
        checks.add("served_requests_sampled", float("inf"), 0.0,
                   "no finished request to compare")
        return checks
    gaps = np.concatenate(reference_gaps(ctx, params, sample))
    checks.add("served_token_logit_gap_max", float(gaps.max()),
               lim["served_token_gap"],
               f"{len(gaps)} served tokens of {len(sample)} requests, "
               f"longest {max(len(p) + len(s) for p, s in sample)}")
    return checks
