"""serve_bench: throughput-latency of continuous vs static batching.

Methodology (mirrors the root bench.py contract of honest numbers):

- **One synthetic Poisson trace, two servers.** Requests arrive by an
  exponential inter-arrival clock (seeded NumPy — the trace is identical
  across runs and across the two servers). Prompts are random token
  spans with mixed lengths; per-request `max_new_tokens` is drawn from a
  range, which is the realistic heterogeneity static batching handles
  worst (every request pays for the batch's longest).
- **Continuous server**: SlotEngine + Scheduler on the monotonic clock —
  requests join the running decode batch at slot granularity and release
  at their own length.
- **Static baseline**: the one-shot `make_generate_fn` program at batch
  = max_slots, every prompt padded to one width and every request run to
  the trace's MAXIMUM new-token count (one compile, the strongest honest
  static config — bucketing per batch would recompile per composition).
  Arrivals queue while the current batch runs; a request's latency ends
  when its whole batch returns.
- **Useful tokens only.** Both servers are scored on the tokens each
  request asked for; the static server's overshoot past a request's own
  `max_new_tokens` is discarded, not credited.

Wall-clock timing closes with a host readback (np.asarray of the token
block / the scheduler's device_get per step), so no async dispatch leaks
into the window. Warmup compiles happen before the trace clock starts
for BOTH servers.

`--replicas N` additionally replays the trace through N replicas behind
the fault-tolerant router (serve/router.py); with `--fault-plan` the
router row becomes a GOODPUT-under-faults measurement — tokens still
delivered while a seeded FaultPlan crashes replicas, stalls ticks, or
poisons logits. replicas=1 with no plan measures the router's own
overhead against the direct continuous path (should be within noise —
the router adds host-side bookkeeping only).

Every row (except static, which has no phases) reports a per-phase
latency breakdown — queue/prefill/decode/stall p50/p99 from the
completions' flight records — and `--trace-out` writes the run's
request-lifecycle spans as Chrome trace JSON (utils/trace.py; warmup
excluded; tracing overhead measured < 1%, BENCHMARKS.md).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Optional

import numpy as np


def build_trace(
    *,
    n_requests: int,
    rate_hz: float,
    vocab: int,
    prompt_len_range=(2, 16),
    max_new_range=(4, 32),
    seed: int = 0,
) -> list:
    """Poisson arrivals with mixed prompt lengths and token budgets."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_hz, n_requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n_requests):
        plen = int(rng.integers(prompt_len_range[0], prompt_len_range[1] + 1))
        trace.append({
            "rid": i,
            "arrival": float(arrivals[i]),
            "prompt": rng.integers(0, vocab, plen).tolist(),
            "max_new_tokens": int(
                rng.integers(max_new_range[0], max_new_range[1] + 1)
            ),
        })
    return trace


def build_shared_prefix_trace(
    *,
    n_requests: int,
    rate_hz: float,
    vocab: int,
    k_prefixes: int = 2,
    prefix_len: int = 48,
    tail_range=(1, 8),
    max_new_range=(8, 24),
    seed: int = 0,
) -> list:
    """K seeded system prompts x many continuations — the PR-6 prefix
    workload: every request is one of `k_prefixes` fixed prefixes plus a
    short unique tail, arriving Poisson. Deterministic per seed (same
    trace replays through the plain and prefix-sharing engines)."""
    rng = np.random.default_rng(seed)
    prefixes = [
        rng.integers(0, vocab, prefix_len).tolist()
        for _ in range(k_prefixes)
    ]
    gaps = rng.exponential(1.0 / rate_hz, n_requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n_requests):
        pre = prefixes[int(rng.integers(0, k_prefixes))]
        tail = rng.integers(
            0, vocab, int(rng.integers(tail_range[0], tail_range[1] + 1))
        ).tolist()
        trace.append({
            "rid": i,
            "arrival": float(arrivals[i]),
            "prompt": list(pre) + tail,
            "max_new_tokens": int(
                rng.integers(max_new_range[0], max_new_range[1] + 1)
            ),
        })
    return trace


def build_lookup_trace(
    *,
    n_requests: int,
    rate_hz: float,
    vocab: int,
    motif_range=(2, 4),
    prompt_len_range=(6, 16),
    max_new_range=(8, 24),
    seed: int = 0,
) -> list:
    """Lookup-friendly prompts: each is a short random motif repeated to
    length (summarization / code-edit / quoting traffic in miniature —
    the text keeps citing its own earlier spans). This is the workload
    prompt-lookup speculative decoding (serve/spec.py) targets: the
    trailing n-gram recurs, so drafts fire and verify accepts runs.
    Deterministic per seed, same trace replays through both arms."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_hz, n_requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n_requests):
        motif = rng.integers(
            0, vocab, int(rng.integers(motif_range[0], motif_range[1] + 1))
        ).tolist()
        plen = int(rng.integers(prompt_len_range[0],
                                prompt_len_range[1] + 1))
        reps = -(-plen // len(motif))
        trace.append({
            "rid": i,
            "arrival": float(arrivals[i]),
            "prompt": (motif * reps)[:plen],
            "max_new_tokens": int(
                rng.integers(max_new_range[0], max_new_range[1] + 1)
            ),
        })
    return trace


def _build_model(*, vocab, max_len, hidden, depth, heads, mlp,
                 kv_cache_dtype=None):
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.models import create_model

    model = create_model(
        "lm_tiny", vocab_size=vocab, max_len=max_len, hidden_dim=hidden,
        depth=depth, num_heads=heads, mlp_dim=mlp, pos_emb="rope",
        kv_cache_dtype=kv_cache_dtype,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _kv_bytes_per_token(cache, num_blocks, block_size) -> float:
    """HBM bytes one context position costs in a paged pool: every
    non-scalar cache leaf's bytes (K/V + any int8 scale pages), divided
    by the pool's positions. The int8-halving acceptance number."""
    import jax

    total = sum(
        leaf.nbytes for leaf in jax.tree.leaves(cache) if leaf.ndim
    )
    return total / (num_blocks * block_size)


def _percentiles(xs) -> dict:
    # the plane-wide percentile implementation (utils/metrics.py):
    # bench rows, /flight scrapes, and SLO verdicts all quote the same
    # nearest-rank quantiles
    from ddp_practice_tpu.utils.metrics import percentile_summary

    return percentile_summary(xs, (50, 90, 99))


def _phase_breakdown(completions) -> dict:
    """Per-phase latency percentiles from the completions' flight
    records (scheduler/router attach them): WHERE the latency percentile
    rows' time actually went — queue wait vs prefill vs decode vs
    stalled (parked between retries / not on any replica)."""
    out = {}
    flights = [c.flight for c in completions if c.flight is not None]
    for key in ("queue_s", "prefill_s", "decode_s", "stall_s"):
        out[key] = _percentiles([f[key] for f in flights])
    return out


def _make_tracer():
    from ddp_practice_tpu.utils.trace import TraceRecorder

    return TraceRecorder()


class _Scraper:
    """Background self-scraper: GETs /metrics, /healthz, /flight round-
    robin at `hz` for the whole bench window, so the plane-on overhead
    row pays for serving REAL scrape traffic, not an idle listener."""

    def __init__(self, port: int, hz: float = 10.0) -> None:
        import threading

        self.port = port
        self.period = 1.0 / hz
        self.count = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="bench-scraper", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        import http.client

        paths = ("/metrics", "/healthz", "/flight")
        i = 0
        while not self._stop.wait(self.period):
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=1.0
                )
                conn.request("GET", paths[i % len(paths)])
                conn.getresponse().read()
                conn.close()
                self.count += 1
            except Exception:
                # server mid-shutdown or a torn response: keep scraping
                # (a dead scraper would quietly measure an idle listener
                # as "plane on")
                pass
            i += 1

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)


def _run_continuous(model, params, trace, *, max_slots, prompt_buckets,
                    max_len, decode_burst, eos_id, paged: bool = False,
                    block_size: int = 16, prefix_cache: bool = False,
                    num_blocks: Optional[int] = None,
                    spec_decode: bool = False, spec_k: int = 4,
                    collect_tokens: bool = False, tracer=None,
                    telemetry=None, health_slot=None) -> dict:
    from ddp_practice_tpu.serve.engine import (
        EngineConfig,
        PagedEngine,
        SlotEngine,
    )
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler

    if paged:
        # per-slot capacity sized to the WORKLOAD's worst context
        # (bucket + burst-rounded max_new), not to max_len — this is the
        # paged decoupling: attention span follows the request, while
        # the POOL carries max_len-equivalent memory per slot so both
        # engines hold the same HBM. `num_blocks` overrides the pool
        # size (the shared-prefix bench undersizes it so block pressure
        # — what sharing relieves — is actually on the table).
        worst_new = max(t["max_new_tokens"] for t in trace)
        worst_new = -(-worst_new // decode_burst) * decode_burst
        if spec_decode:
            # the verify program grows every slot spec_k + 1 positions
            # before knowing the acceptance — the scheduler's admission
            # slack (_needed_positions) must fit the per-slot capacity
            worst_new += spec_k + 1
        cap_blocks = -(-(max(prompt_buckets) + worst_new) // block_size)
        engine = PagedEngine(
            model, params,
            EngineConfig(
                max_slots=max_slots, max_len=max_len,
                prompt_buckets=prompt_buckets, temperature=0.0,
                decode_burst=decode_burst, eos_id=eos_id,
                block_size=block_size, max_blocks_per_slot=cap_blocks,
                num_blocks=(
                    num_blocks if num_blocks is not None
                    else 1 + max_slots * (-(-max_len // block_size))
                ),
                prefix_cache=prefix_cache,
                spec_decode=spec_decode, spec_k=spec_k,
            ),
        )
    else:
        engine = SlotEngine(
            model, params,
            EngineConfig(
                max_slots=max_slots, max_len=max_len,
                prompt_buckets=prompt_buckets, temperature=0.0,
                decode_burst=decode_burst, eos_id=eos_id,
            ),
        )
    # no ServeMetrics inside the timed window: the bench computes its own
    # percentiles from completions, and the static baseline carries no
    # per-tick bookkeeping — keep the measured loops symmetric.
    # `telemetry` (when the plane is on) IS deliberately inside the
    # window: its cost is exactly what the overhead row measures.
    sched = Scheduler(engine, max_queue=len(trace), tracer=tracer,
                      telemetry=telemetry)
    if health_slot is not None:
        # single replica: /healthz reports one always-healthy lane
        health_slot["fn"] = lambda: {0: "healthy"}
    # warmup compiles outside the timed window: one admit per bucket in
    # play + one decode dispatch, then rewind (slot pool only — paged
    # blocks free individually at release, nothing to rewind)
    widths = sorted({engine.bucket_for(len(t["prompt"])) for t in trace})
    for w in widths:
        # budget only the one warmup burst: a default (reserve-the-cap)
        # paged admit could outsize a small pool that the gated
        # scheduler path would happily serve
        slot = engine.admit(list(range(1, w + 1))[:w],
                            max_positions=decode_burst)
        engine.step_burst()
        engine.release(slot)
    if getattr(engine, "drafter", None) is not None:
        # speculation on: compile the verify program outside the timed
        # window too. An all-ones prompt makes the lookup drafter
        # propose (every trailing n-gram recurs), then the warm
        # dispatch's counters are zeroed so the report reconciles
        # against workload-only numbers (same as engine.warm_engine).
        slot = engine.admit([1] * min(engine.buckets),
                            max_positions=spec_k + 1)
        w_drafts, w_lens, _ = engine.propose_drafts()
        engine.step_verify(w_drafts, w_lens)
        engine.release(slot)
        engine.spec_drafted_tokens = 0
        engine.spec_accepted_tokens = 0
        engine.spec_dispatches = 0
    if paged and prefix_cache:
        # warm the HIT path too: re-admitting a just-cached prompt
        # compiles the suffix-bucket prefix-prefill program. Then the
        # tree and its counters reset, so the timed window starts cold.
        for w in widths:
            slot = engine.admit(list(range(1, w + 1))[:w],
                                max_positions=decode_burst)
            engine.step_burst()
            engine.release(slot)
        engine.radix.clear()
        engine.radix.hit_tokens = engine.radix.miss_tokens = 0
        engine.preemptions = 0
    if not paged:
        engine.reset_epoch()
    if tracer is not None:
        # attach the engine lanes only after warmup, and drop anything
        # recorded so far: compile-time spans would dwarf the workload
        from ddp_practice_tpu.utils.trace import label_replica

        engine.set_tracer(tracer, 0)
        label_replica(tracer, 0, max_slots)
        tracer.clear()
        if telemetry is not None and hasattr(telemetry, "attach"):
            # sink attached only NOW: the stream gets the same
            # warmup-free timeline as the exit dump (labels replay)
            telemetry.attach(tracer)

    t0 = time.monotonic()
    i = 0
    while not (i >= len(trace) and sched.idle):
        now = time.monotonic() - t0
        while i < len(trace) and trace[i]["arrival"] <= now:
            t = trace[i]
            # arrivals are polled between scheduler steps, so a request
            # can be submitted up to one decode dispatch late; stamping
            # the TRUE trace arrival keeps its queueing wait inside the
            # measured TTFT/latency (the static loop is charged from the
            # same trace times)
            sched.submit(Request(
                rid=t["rid"], prompt=t["prompt"],
                max_new_tokens=t["max_new_tokens"],
                arrival=t0 + t["arrival"],
            ))
            i += 1
        if sched.idle:
            time.sleep(max(0.0, trace[i]["arrival"] - now))
            continue
        sched.step()
    elapsed = time.monotonic() - t0

    tokens = sum(len(c.tokens) for c in sched.completions)
    lat = [c.finish - c.arrival for c in sched.completions]
    extra = {}
    if paged:
        extra["preemptions"] = engine.preemptions
        extra["kv_bytes_per_token"] = _kv_bytes_per_token(
            engine._cache, engine.blocks.num_blocks, block_size
        )
        extra["num_blocks"] = engine.blocks.num_blocks
        if getattr(engine, "drafter", None) is not None:
            # the accept-rate observables the spec gate reads: how much
            # was drafted, how much the model agreed with, and how many
            # sequential dispatches speculation actually saved
            extra["spec"] = {
                "spec_k": spec_k,
                "drafted_tokens": engine.spec_drafted_tokens,
                "accepted_tokens": engine.spec_accepted_tokens,
                "accept_rate": (
                    engine.spec_accepted_tokens
                    / max(1, engine.spec_drafted_tokens)
                ),
                "verify_dispatches": engine.spec_dispatches,
            }
        if prefix_cache:
            # the proof-of-reuse counters the acceptance gate reads
            extra["prefix_cache"] = {
                "hit_tokens": engine.radix.hit_tokens,
                "miss_tokens": engine.radix.miss_tokens,
                "hit_rate": (
                    engine.radix.hit_tokens
                    / max(1, engine.radix.hit_tokens
                          + engine.radix.miss_tokens)
                ),
                "nodes": len(engine.radix),
            }
    if collect_tokens:
        # per-rid streams for cross-arm identity checks (the spec bench
        # compares them, then drops them from the written report)
        extra["tokens_by_rid"] = {
            c.rid: list(c.tokens) for c in sched.completions
        }
    return {
        "mode": ("paged+spec" if paged and spec_decode
                 else "paged+prefix" if paged and prefix_cache
                 else "paged" if paged else "continuous"),
        **extra,
        # largest total context one request can reach: the slot pool is
        # hard-capped by its shared clock (a request can never span more
        # than max_len - max_bucket decode positions from base), the
        # paged engine by its per-slot page-table width — which is free
        # to exceed max_len
        "max_servable_context": (
            engine.max_context if paged else max_len
        ),
        "elapsed_s": elapsed,
        "useful_tokens": tokens,
        "tokens_per_sec": tokens / elapsed,
        "ttft_s": _percentiles(
            [c.ttft for c in sched.completions if c.ttft is not None]
        ),
        "tpot_s": _percentiles(
            [c.tpot for c in sched.completions if c.tpot is not None]
        ),
        "latency_s": _percentiles(lat),
        # per-phase breakdown of the same latency population (flight
        # records: queue wait / prefill / decode / stall percentiles)
        "phases": _phase_breakdown(sched.completions),
        "completions": len(sched.completions),
        "compile_stats": engine.compile_stats(),
    }


def _run_router(model, params, trace, *, replicas, max_slots,
                prompt_buckets, max_len, decode_burst, eos_id,
                fault_plan=None, tracer=None, slo_config=None,
                telemetry=None, exporter=None, registry=None,
                health_slot=None, alert_sinks=None) -> dict:
    """The fleet path: N identical replicas behind the fault-tolerant
    router (serve/router.py). Scored like the continuous server — useful
    tokens of requests that finished ok — which under an injected
    FaultPlan is a GOODPUT number: tokens the fleet still delivered
    while replicas crashed, stalled, or emitted NaNs."""
    from ddp_practice_tpu.serve.engine import EngineConfig
    from ddp_practice_tpu.serve.router import RouterConfig, make_router
    from ddp_practice_tpu.serve.scheduler import MonotonicClock, Request

    clock = MonotonicClock()
    watchdog = None
    if slo_config is not None:
        from ddp_practice_tpu.serve.slo import AlertSinks, SLOWatchdog

        # live burn-rate alerting over the run's completions; alert
        # instants land in the trace and the JSONL stream, the router's
        # brown-out listens, and --alert-sink edges PUSH to operators
        # (command/webhook/jsonl with backoff + dead-sink breaker)
        sinks = (AlertSinks(alert_sinks, clock=clock,
                            registry=registry)
                 if alert_sinks else None)
        watchdog = SLOWatchdog(
            slo_config, clock=clock, registry=registry,
            tracer=tracer, telemetry=exporter, sinks=sinks,
        )
    router = make_router(
        model, params, replicas,
        EngineConfig(
            max_slots=max_slots, max_len=max_len,
            prompt_buckets=prompt_buckets, temperature=0.0,
            decode_burst=decode_burst, eos_id=eos_id,
        ),
        clock=clock,
        max_queue=len(trace),
        config=RouterConfig(),
        fault_plan=fault_plan,
        registry=registry,
        tracer=tracer,
        slo=watchdog,
        telemetry=telemetry,
    )
    if health_slot is not None:
        health_slot["fn"] = router.states
    # warm EVERY configured bucket, not just the trace prompts' widths:
    # failover re-prefills carry prompt+salvaged-tokens and can land in
    # a larger bucket — its compile must happen out here, not inside the
    # timed goodput window
    router.warmup()
    if tracer is not None:
        tracer.clear()  # drop warmup spans; keep the workload timeline
        if exporter is not None:
            # sink attached only after the clear: the streamed JSONL is
            # as warmup-free as the exit dump (lane labels replay)
            exporter.attach(tracer)

    t0 = time.monotonic()
    i = 0
    while not (i >= len(trace) and router.idle):
        now = time.monotonic() - t0
        while i < len(trace) and trace[i]["arrival"] <= now:
            t = trace[i]
            router.submit(Request(
                rid=t["rid"], prompt=t["prompt"],
                max_new_tokens=t["max_new_tokens"],
                arrival=t0 + t["arrival"],
            ))
            i += 1
        if router.idle:
            # idle with arrivals left: sleep to the next one. (idle with
            # NONE left is reachable too — door sheds on a dead fleet
            # finalize instantly — and the loop condition exits then.)
            if i < len(trace):
                time.sleep(max(0.0, trace[i]["arrival"] - now))
            continue
        router.step()
    elapsed = time.monotonic() - t0

    ok = [c for c in router.completions if c.status in ("eos", "length")]
    ok_tokens = sum(len(c.tokens) for c in ok)
    statuses: dict = {}
    for c in router.completions:
        statuses[c.status] = statuses.get(c.status, 0) + 1
    m = router.metrics
    out = {
        "mode": f"router x{replicas}",
        "elapsed_s": elapsed,
        "useful_tokens": ok_tokens,
        "tokens_per_sec": ok_tokens / elapsed,
        "goodput_tokens_per_sec": ok_tokens / elapsed,
        "ttft_s": _percentiles([c.ttft for c in ok if c.ttft is not None]),
        "tpot_s": _percentiles([c.tpot for c in ok if c.tpot is not None]),
        "latency_s": _percentiles([c.finish - c.arrival for c in ok]),
        # phase breakdown over the same ok population as latency_s;
        # stall_s here includes retry parking + dead-replica gaps
        "phases": _phase_breakdown(ok),
        "completions": len(router.completions),
        "statuses": statuses,
        "retries": m.retries.value,
        "failovers": m.failovers.value,
        "breaker_trips": m.breaker_trips.value,
        "replica_states": router.states(),
        "compile_stats": router.compile_stats(),
    }
    if watchdog is not None:
        out["slo"] = {
            "alerts": [
                {"t": t, "event": edge, "objective": obj}
                for t, edge, obj in watchdog.alert_log
            ],
            "active": dict(watchdog.alerts),
        }
    return out


def _fleet_wait(router, max_s: float) -> None:
    """Event-driven nap for a FLEET drive loop: sleep on the workers'
    push-stream fds so the parent wakes the instant a completion frame
    lands — no spin stealing the workers' core, no sleep-quantized
    consumption lag. Falls back to a 1 ms nap while streams are down."""
    import select

    fds = []
    for h in router.handles:
        fn = getattr(h, "stream_fileno", None)
        fd = fn() if fn is not None else None
        if fd is not None:
            fds.append(fd)
    if not fds:
        time.sleep(min(max_s, 0.001))
        return
    try:
        select.select(fds, [], [], max_s)
    except (OSError, ValueError):
        time.sleep(0.001)  # a stream died mid-select: step will resync


def _replay_through_router(router, trace, *, rid_offset: int = 0,
                           driver=None, fleet: bool = False) -> dict:
    """Replay one arrival trace through an EXISTING router (in-process
    or fleet — same Router API, that is the seam's point) and score it.
    `rid_offset` keeps rids unique across reps; `driver` is an optional
    FleetFaultDriver polled with elapsed seconds; `fleet=True` makes
    the drive loop EVENT-DRIVEN between ticks (select on the push
    streams — the decode runs in worker processes that a spinning
    parent would preempt on small machines; the in-process router
    decodes inside step(), so its loop must never sleep)."""
    from ddp_practice_tpu.serve.scheduler import Request

    before = len(router.completions)
    t0 = time.monotonic()
    i = 0
    while not (i >= len(trace) and router.idle):
        now = time.monotonic() - t0
        if driver is not None:
            driver.poll(now)
        while i < len(trace) and trace[i]["arrival"] <= now:
            t = trace[i]
            router.submit(Request(
                rid=t["rid"] + rid_offset, prompt=t["prompt"],
                max_new_tokens=t["max_new_tokens"],
                arrival=t0 + t["arrival"],
                # multi-tenant traces (serve/workload.py) carry these;
                # the single-tenant builders don't, and the defaults
                # keep their replays byte-identical
                tenant=t.get("tenant"),
                priority=t.get("priority", 0),
            ))
            i += 1
        if router.idle:
            if i < len(trace):
                time.sleep(max(0.0, trace[i]["arrival"] - now))
            continue
        router.step()
        if fleet:
            until_arrival = (trace[i]["arrival"] - (time.monotonic() - t0)
                             if i < len(trace) else 0.005)
            _fleet_wait(router, min(0.005, max(0.0, until_arrival)))
    elapsed = time.monotonic() - t0
    comps = router.completions[before:]
    ok = [c for c in comps if c.status in ("eos", "length")]
    ok_tokens = sum(len(c.tokens) for c in ok)
    statuses: dict = {}
    for c in comps:
        statuses[c.status] = statuses.get(c.status, 0) + 1
    return {
        "elapsed_s": elapsed,
        "useful_tokens": ok_tokens,
        "goodput_tokens_per_sec": ok_tokens / elapsed,
        "tokens_per_sec": ok_tokens / elapsed,
        "ttft_s": _percentiles([c.ttft for c in ok if c.ttft is not None]),
        "tpot_s": _percentiles([c.tpot for c in ok if c.tpot is not None]),
        "latency_s": _percentiles([c.finish - c.arrival for c in ok]),
        "phases": _phase_breakdown(ok),
        "completions": len(comps),
        # the zero-lost invariant, checked, not assumed
        "lost": len(trace) - len(comps),
        "statuses": statuses,
    }


def fleet_bench(
    *,
    n_requests: int = 32,
    rate_hz: float = 8.0,
    procs: int = 2,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(2, 32),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
    reps: int = 6,
    fault_plan=None,
    metrics_port: Optional[int] = None,
    trace_out: Optional[str] = None,
    trace_sample: float = 1.0,
    trace_keep_slow_s: Optional[float] = None,
    otlp_out: Optional[str] = None,
    otlp_endpoint: Optional[str] = None,
    trace_tenant_rates: Optional[dict] = None,
) -> dict:
    """One Poisson trace through `procs` worker OS PROCESSES behind the
    RPC seam (serve/worker.py + serve/supervisor.py) AND through
    `procs` in-process router replicas — the ratio rows are the seam's
    bill (acceptance gate: latency p50 <= 1.10x at 8 rps).

    `trace_out` arms the FLEET TRACE PLANE on the fleet side: workers
    record their own prefill/decode/request spans and stream them back
    over the push stream, the router-side TraceCollector merges them
    (clock-offset-aligned, pid=worker-N lanes) with the router's own
    dispatch/failover instants into ONE Chrome trace — under a kill
    plan, the dead worker's pre-crash spans and the survivor's spans
    share each migrated request's original trace_id. Validate with
    ``tools/check_traces.py --fleet``.

    Methodology (the PR-5 telemetry-overhead lesson, which measured ~5%
    of pure machine drift on this box): both routers are built ONCE
    (compiles amortized, same warm engines throughout), then the trace
    replays `reps` times ALTERNATING which side goes first; the
    headline ratios are medians of per-rep p50 ratios, so run-order
    drift cancels instead of being billed to the seam. A kill-bearing
    `fault_plan` switches to a single chaos rep (a killed worker is not
    a steady state to amortize) — real SIGKILL/SIGSTOP to live worker
    pids, goodput + zero-lost measured against actual process death."""
    from ddp_practice_tpu.serve.engine import EngineConfig
    from ddp_practice_tpu.serve.faults import FleetFaultDriver
    from ddp_practice_tpu.serve.router import RouterConfig, make_router
    from ddp_practice_tpu.serve.scheduler import MonotonicClock, Request
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_federated_server,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }
    model, params = _build_model(
        vocab=vocab, max_len=max_len, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
    )
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=prompt_len_range, max_new_range=max_new_range,
        seed=seed,
    )
    chaos = fault_plan is not None and bool(fault_plan.kills())
    if fault_plan is not None:
        sim = [f.kind for f in fault_plan.faults if f.kind != "kill"]
        if sim:
            # refusing beats lying: workers carry no injector, so a
            # sim spec here would run fault-FREE while the report
            # stamps a fault plan it never executed
            raise ValueError(
                f"the --procs fleet bench interprets only 'kill' "
                f"specs (real signals); simulated faults {sim} ride "
                f"the in-process --replicas path"
            )
        bad = [f.replica for f in fault_plan.kills()
               if not 0 <= f.replica < procs]
        if bad:
            raise ValueError(
                f"kill spec replica(s) {bad} out of range for "
                f"--procs {procs}"
            )
    if chaos:
        reps = 1
    engine_cfg = EngineConfig(
        max_slots=max_slots, max_len=max_len,
        prompt_buckets=tuple(prompt_buckets), temperature=0.0,
        decode_burst=decode_burst, eos_id=eos_id,
    )
    # enough queue for every rep's worst backlog
    max_queue = len(trace) * max(1, reps)
    inproc = make_router(
        model, params, procs, engine_cfg, clock=MonotonicClock(),
        max_queue=max_queue, config=RouterConfig(),
    )
    inproc.warmup()
    tracer = _make_tracer() if trace_out else None
    spec = WorkerSpec(
        model=model_kw,
        engine={
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": list(prompt_buckets),
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": eos_id,
        },
        max_queue=max_queue,
        trace=(trace_out is not None or otlp_out is not None
               or otlp_endpoint is not None),
        trace_sample=trace_sample,
        trace_keep_slow_s=trace_keep_slow_s,
        trace_tenant_rates=trace_tenant_rates,
    )
    if tracer is None and (otlp_out or otlp_endpoint):
        tracer = _make_tracer()
    fleet_router, sup, handles = make_fleet_router(
        spec, procs, sup_config=SupervisorConfig(restart_base_s=0.25),
        tracer=tracer,
    )
    pusher = None
    if otlp_endpoint is not None and tracer is not None:
        # live egress for the whole run: kept spans drain to the
        # collector as they land, not at exit — the operator posture
        # the ISSUE-12 plane exists for
        from ddp_practice_tpu.utils.telemetry import OtlpPusher

        pusher = OtlpPusher(otlp_endpoint, tracer)
    server = None
    rep_rows = {"in_process": [], "fleet": []}
    ratios_p50 = []
    try:
        if metrics_port is not None:
            _, server = make_federated_server(sup, handles,
                                              port=metrics_port)
        driver = (FleetFaultDriver(fault_plan, sup.kill)
                  if chaos else None)
        for rep in range(reps):
            order = ["in_process", "fleet"]
            if rep % 2:
                order.reverse()
            for side in order:
                if side == "in_process":
                    row = _replay_through_router(
                        inproc, trace, rid_offset=rep * 1_000_000,
                    )
                else:
                    row = _replay_through_router(
                        fleet_router, trace,
                        rid_offset=rep * 1_000_000,
                        driver=driver, fleet=True,
                    )
                rep_rows[side].append(row)
            ratios_p50.append(
                rep_rows["fleet"][-1]["latency_s"]["p50"]
                / rep_rows["in_process"][-1]["latency_s"]["p50"]
            )

        def med(xs):
            s = sorted(xs)
            n = len(s)
            return (s[n // 2] if n % 2
                    else 0.5 * (s[n // 2 - 1] + s[n // 2]))

        def agg(side, key, pct):
            return med([r[key][pct] for r in rep_rows[side]])

        m = fleet_router.metrics
        fleet_row = dict(rep_rows["fleet"][-1])
        fleet_row.update({
            "mode": f"fleet x{procs}", "procs": procs,
            "latency_s": {p: agg("fleet", "latency_s", p)
                          for p in ("p50", "p90", "p99")},
            "ttft_s": {p: agg("fleet", "ttft_s", p)
                       for p in ("p50", "p90", "p99")},
            "lost": sum(r["lost"] for r in rep_rows["fleet"]),
            "retries": m.retries.value,
            "failovers": m.failovers.value,
            "breaker_trips": m.breaker_trips.value,
            "replica_states": fleet_router.states(),
            "worker_restarts": list(sup.restarts),
        })
        if driver is not None:
            fleet_row["kills_fired"] = [
                {"replica": f.replica, "sig": f.sig, "at_s": f.at_s}
                for f in driver.fired
            ]
        if server is not None:
            fleet_row["federated_port"] = server.port
        inproc_row = dict(rep_rows["in_process"][-1])
        inproc_row.update({
            "mode": f"router x{procs}",
            "latency_s": {p: agg("in_process", "latency_s", p)
                          for p in ("p50", "p90", "p99")},
            "ttft_s": {p: agg("in_process", "ttft_s", p)
                       for p in ("p50", "p90", "p99")},
            "lost": sum(r["lost"] for r in rep_rows["in_process"]),
        })
        report = {
            "trace": {
                "n_requests": n_requests, "rate_hz": rate_hz,
                "seed": seed,
                "prompt_len_range": list(prompt_len_range),
                "max_new_range": list(max_new_range),
            },
            "procs": procs,
            "reps": reps,
            "in_process": inproc_row,
            "fleet": fleet_row,
            # medians of per-rep ratios: order-balanced, drift-robust
            "latency_ratio_p50": med(ratios_p50),
            "latency_ratio_p50_per_rep": ratios_p50,
            "latency_ratio_p99": med(
                [f["latency_s"]["p99"] / i["latency_s"]["p99"]
                 for f, i in zip(rep_rows["fleet"],
                                 rep_rows["in_process"])]
            ),
            "goodput_ratio": med(
                [f["goodput_tokens_per_sec"]
                 / i["goodput_tokens_per_sec"]
                 for f, i in zip(rep_rows["fleet"],
                                 rep_rows["in_process"])]
            ),
        }
        # steady-state decode parity (TPOT: inter-token latency after
        # the first token — the RPC seam is off this path entirely) and
        # admission overhead (TTFT: the submit hop + worker wake ARE on
        # this path) — the decomposition of where the ratio comes from
        report["tpot_ratio_p50"] = med(
            [f["tpot_s"]["p50"] / i["tpot_s"]["p50"]
             for f, i in zip(rep_rows["fleet"], rep_rows["in_process"])
             if i["tpot_s"]["p50"]]
        )
        report["ttft_ratio_p50"] = med(
            [f["ttft_s"]["p50"] / i["ttft_s"]["p50"]
             for f, i in zip(rep_rows["fleet"], rep_rows["in_process"])
             if i["ttft_s"]["p50"]]
        )
        if fault_plan is not None:
            report["fault_plan"] = fault_plan.to_json()
        if tracer is not None:
            col = fleet_router.trace_collector
            if trace_out:
                tracer.save(trace_out)
                report["trace_out"] = trace_out
            if otlp_out:
                tracer.save_otlp(otlp_out)
                report["otlp_out"] = otlp_out
            report["trace_events"] = len(tracer)
            report["trace_plane"] = {
                "worker_frames": col.frames if col else 0,
                "worker_events": col.events if col else 0,
                "dropped": tracer.dropped,
                "skew_bound_s": col.skew_bound() if col else None,
            }
            meta = tracer.sampling_meta()
            if meta is not None:
                report["sampling"] = meta
        if pusher is not None:
            pusher.close()  # final drain before the counters are read
            report["otlp_push"] = {
                "endpoint": otlp_endpoint,
                "batches_sent": pusher.batches_sent,
                "spans_sent": pusher.spans_sent,
                "batches_dropped": pusher.batches_dropped,
                "post_failures": pusher.post_failures,
                "dead": pusher.dead,
            }
            pusher = None
        return report
    finally:
        if pusher is not None:
            pusher.close()
        if server is not None:
            server.close()
        sup.stop()


def _fleet_kv_counters(router) -> tuple:
    """Summed (hit_tokens, miss_tokens) over every worker's
    heartbeat-carried kv summary — the fleet's prefix-cache ledger."""
    hit = miss = 0
    for h in router.handles:
        kv = getattr(h, "kv_summary", None)
        if isinstance(kv, dict):
            hit += kv.get("hit_tokens", 0)
            miss += kv.get("miss_tokens", 0)
    return hit, miss


def cache_routing_bench(
    *,
    n_requests: int = 48,
    rate_hz: float = 100.0,
    procs: int = 2,
    max_slots: int = 4,
    block_size: int = 16,
    # undersized on purpose: 24 usable blocks can hold TWO families'
    # prefix blocks (12) plus the transient working set, but not all
    # FOUR (24) — so spraying every family across the fleet (least-
    # loaded) keeps evicting and re-paying cold prefill in steady
    # state, while affinity's partition stays warm. 32+ blocks fit
    # everything resident and flatten the contrast to the one-time
    # warmup; tighter starves decode on both arms.
    num_blocks: int = 25,
    k_prefixes: int = 4,
    prefix_len: int = 96,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    decode_burst: int = 8,
    seed: int = 0,
    reps: int = 6,
) -> dict:
    """The cache-aware routing A/B: ONE shared-prefix trace (K system
    prompts x unique tails) replayed through TWO identical 2-worker
    fleets at the same paged pool — one routing by prefix affinity
    (RouterConfig.cache_aware, serve/affinity.py), one by the classic
    least-loaded order. Affinity partitions the K families across the
    fleet so each warms ONCE; least-loaded sprays them, so every family
    pays its cold prefill on every worker (and re-pays it whenever
    churn evicts a copy). Headlines: the fleet prefix-hit-token rate
    (from the workers' own radix hit/miss counters — ground truth, not
    the router's estimate) and the goodput ratio, plus the zero-lost
    and greedy token-identity invariants (routing must change WHERE
    requests run, never WHAT they produce). Order-balanced alternating
    reps, medians of per-rep ratios, same methodology as fleet_bench."""
    from ddp_practice_tpu.serve.router import RouterConfig
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    trace = build_shared_prefix_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        k_prefixes=k_prefixes, prefix_len=prefix_len,
        tail_range=(1, 8), max_new_range=(8, 24), seed=seed,
    )
    max_prompt = max(len(t["prompt"]) for t in trace)
    bucket = block_size
    while bucket < max_prompt:
        bucket += block_size
    # small buckets matter: a warm admit prefills only the UNCACHED
    # remainder, and its span is matched + bucket_for(remainder) — with
    # only the full-prompt bucket, every warm request would blow the
    # per-slot capacity and be rejected instead of hitting the cache
    buckets = sorted({16, 32, 64, bucket})
    spec = WorkerSpec(
        model={
            "vocab_size": vocab, "max_len": max_len,
            "hidden_dim": hidden, "depth": depth, "num_heads": heads,
            "mlp_dim": mlp, "pos_emb": "rope",
        },
        engine={
            "paged": True, "prefix_cache": True,
            "num_blocks": num_blocks, "block_size": block_size,
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": buckets,
            # greedy: the token-identity invariant needs bit-equal
            # streams across arms
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": None,
        },
        max_queue=len(trace) * max(1, reps),
    )
    arms = {}
    sups = []
    try:
        for name, aware in (("affinity", True), ("least_loaded", False)):
            router, sup, _handles = make_fleet_router(
                spec, procs,
                config=RouterConfig(cache_aware=aware),
                sup_config=SupervisorConfig(restart_base_s=0.25),
            )
            arms[name] = router
            sups.append(sup)
        rep_rows = {"affinity": [], "least_loaded": []}
        tokens_by_rid = {"affinity": {}, "least_loaded": {}}
        for rep in range(reps):
            order = ["affinity", "least_loaded"]
            if rep % 2:
                order.reverse()
            for side in order:
                router = arms[side]
                before_kv = _fleet_kv_counters(router)
                n_before = len(router.completions)
                row = _replay_through_router(
                    router, trace, rid_offset=rep * 1_000_000,
                    fleet=True,
                )
                # one settle tick so the final heartbeat's kv counters
                # (which rode the last poll) are current before the delta
                router.step()
                hit0, miss0 = before_kv
                hit1, miss1 = _fleet_kv_counters(router)
                dh, dm = hit1 - hit0, miss1 - miss0
                row["hit_tokens"] = dh
                row["miss_tokens"] = dm
                row["hit_rate"] = dh / (dh + dm) if dh + dm else 0.0
                rep_rows[side].append(row)
                for c in router.completions[n_before:]:
                    if c.status in ("eos", "length"):
                        tokens_by_rid[side][c.rid] = list(c.tokens)

        def med(xs):
            s = sorted(xs)
            n = len(s)
            return (s[n // 2] if n % 2
                    else 0.5 * (s[n // 2 - 1] + s[n // 2]))

        # greedy token identity: same rid (rep-offset included) must
        # yield the same tokens on both arms — routing is placement,
        # never content
        shared = set(tokens_by_rid["affinity"]) & set(
            tokens_by_rid["least_loaded"])
        same = sum(
            1 for r in shared
            if tokens_by_rid["affinity"][r]
            == tokens_by_rid["least_loaded"][r]
        )
        identity = same / len(shared) if shared else 0.0
        routes: dict = {}
        for c in arms["affinity"].completions:
            fl = c.flight or {}
            r = fl.get("route")
            if r is not None:
                routes[r] = routes.get(r, 0) + 1

        def arm_row(side):
            rows = rep_rows[side]
            return {
                "mode": f"{side} x{procs}",
                "goodput_tokens_per_sec": med(
                    [r["goodput_tokens_per_sec"] for r in rows]),
                "hit_rate": med([r["hit_rate"] for r in rows]),
                "hit_tokens": sum(r["hit_tokens"] for r in rows),
                "miss_tokens": sum(r["miss_tokens"] for r in rows),
                "latency_s": {p: med([r["latency_s"][p] for r in rows])
                              for p in ("p50", "p90", "p99")},
                "lost": sum(r["lost"] for r in rows),
            }

        aff, ll = arm_row("affinity"), arm_row("least_loaded")
        aff["route_decisions"] = routes
        return {
            "trace": {
                "n_requests": n_requests, "rate_hz": rate_hz,
                "seed": seed, "k_prefixes": k_prefixes,
                "prefix_len": prefix_len,
            },
            "pool": {"num_blocks": num_blocks,
                     "block_size": block_size},
            "procs": procs,
            "reps": reps,
            "affinity": aff,
            "least_loaded": ll,
            # medians of per-rep ratios (order-balanced): the fleet
            # prefix memory's bill, robust to machine drift
            "hit_rate_ratio": med([
                (a["hit_rate"] / b["hit_rate"]) if b["hit_rate"]
                else float(a["hit_rate"] > 0)
                for a, b in zip(rep_rows["affinity"],
                                rep_rows["least_loaded"])
            ]),
            "goodput_ratio": med([
                a["goodput_tokens_per_sec"]
                / b["goodput_tokens_per_sec"]
                for a, b in zip(rep_rows["affinity"],
                                rep_rows["least_loaded"])
            ]),
            "token_identity": identity,
            "lost": aff["lost"] + ll["lost"],
        }
    finally:
        for sup in sups:
            sup.stop()


def fleet_trace_overhead_bench(
    *,
    n_requests: int = 32,
    rate_hz: float = 8.0,
    procs: int = 2,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(2, 32),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
    pairs: int = 12,
    trace_out: Optional[str] = None,
) -> dict:
    """Fleet trace COLLECTION on/off overhead at the
    fleet_x2_overhead_8rps operating point (the acceptance gate:
    mean <= 2%).

    ONE warm worker fleet serves every rep; the whole trace plane —
    worker-side span recording (flipped live via the rpc ``trace``
    op), push-frame streaming, router-side collection and the fleet
    recorder — toggles between reps. Reps run in ALTERNATING order
    (on-first, then off-first) and the headline is the median of
    per-pair ratios, the PR-5/PR-7 methodology that cancels this box's
    ±15% drift instead of billing it to the plane. The ON reps' merged
    timeline is saved to `trace_out` (validated fleet-mode by the
    caller/tests), and the report carries the exemplar-resolution
    check: every trace_id exposed as a /metrics bucket exemplar must
    name a request present in the merged trace."""
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=prompt_len_range, max_new_range=max_new_range,
        seed=seed,
    )
    tracer = _make_tracer()
    spec = WorkerSpec(
        model=model_kw,
        engine={
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": list(prompt_buckets),
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": eos_id,
        },
        max_queue=len(trace) * (2 * pairs + 2),
        trace=True,
    )
    router, sup, handles = make_fleet_router(
        spec, procs, sup_config=SupervisorConfig(restart_base_s=0.25),
        tracer=tracer,
    )

    def set_plane(on: bool) -> None:
        for h in handles:
            h.set_trace(on)
        if on:
            tracer.enable()
        else:
            tracer.disable()

    rows = {"on": [], "off": []}
    try:
        # one untimed shakeout rep with the plane ON: streams connect,
        # clock offsets get their first samples, then the recorder
        # clears so the saved timeline holds only measured reps
        set_plane(True)
        _replay_through_router(router, trace, rid_offset=90_000_000,
                               fleet=True)
        tracer.clear()
        for i in range(pairs):
            order = ["on", "off"] if i % 2 == 0 else ["off", "on"]
            for side in order:
                set_plane(side == "on")
                rows[side].append(_replay_through_router(
                    router, trace,
                    rid_offset=(2 * i + order.index(side)) * 1_000_000,
                    fleet=True,
                ))
        # one final ON rep: the buckets' last-exemplar slots now point
        # at requests that ARE in the merged timeline (off-rep requests
        # legitimately are not — their spans were never recorded)
        set_plane(True)
        _replay_through_router(router, trace, rid_offset=91_000_000,
                               fleet=True)

        def med(xs):
            s = sorted(xs)
            n = len(s)
            return (s[n // 2] if n % 2
                    else 0.5 * (s[n // 2 - 1] + s[n // 2]))

        ratios_p50 = [on["latency_s"]["p50"] / off["latency_s"]["p50"]
                      for on, off in zip(rows["on"], rows["off"])]
        ratios_mean = [on["latency_s"]["mean"] / off["latency_s"]["mean"]
                       for on, off in zip(rows["on"], rows["off"])]
        col = router.trace_collector
        report = {
            "trace": {
                "n_requests": n_requests, "rate_hz": rate_hz,
                "seed": seed,
                "prompt_len_range": list(prompt_len_range),
                "max_new_range": list(max_new_range),
            },
            "procs": procs,
            "pairs": pairs,
            "gate": "mean <= 1.02x",
            "latency_ratio_p50": med(ratios_p50),
            "latency_ratio_mean": med(ratios_mean),
            "latency_ratio_mean_per_pair": ratios_mean,
            "goodput_ratio": med(
                [on["goodput_tokens_per_sec"]
                 / off["goodput_tokens_per_sec"]
                 for on, off in zip(rows["on"], rows["off"])]
            ),
            "on": {"latency_s": rows["on"][-1]["latency_s"],
                   "lost": sum(r["lost"] for r in rows["on"])},
            "off": {"latency_s": rows["off"][-1]["latency_s"],
                    "lost": sum(r["lost"] for r in rows["off"])},
            "trace_events": len(tracer),
            "trace_plane": {
                "worker_frames": col.frames if col else 0,
                "worker_events": col.events if col else 0,
                "dropped": tracer.dropped,
                "skew_bound_s": col.skew_bound() if col else None,
            },
        }
        # exemplar resolution: every trace_id a worker's /metrics
        # exposes as a bucket exemplar must point at a request present
        # in the merged timeline — the p99-bucket-to-trace jump works
        report["exemplars"] = _exemplar_resolution(sup, handles, tracer)
        if trace_out:
            tracer.save(trace_out)
            report["trace_out"] = trace_out
        return report
    finally:
        sup.stop()


def fleet_trace_sampling_bench(
    *,
    n_requests: int = 200,
    rate_hz: float = 100.0,
    procs: int = 2,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(2, 32),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
    pairs: int = 6,
    sample: float = 0.01,
    keep_slow_s: Optional[float] = None,
    trace_out: Optional[str] = None,
    otlp_out: Optional[str] = None,
) -> dict:
    """Head-sampled trace plane at 100 rps: three arms against ONE warm
    worker fleet — ``sampled`` (head rate `sample`, default 1%),
    ``full`` (rate 1.0) and ``off`` (plane disabled), rotated in
    order-balanced rounds (the PR-5/7 drift-cancelling methodology).

    The two acceptance numbers:

    - ``span_reduction``: 1 - sampled/full recorded-span count (median
      over rounds; gate >= 0.95 at 1%) — upstream SUPPRESSION, counted
      at the fleet recorder after worker streaming, so it proves the
      workers never recorded/streamed the suppressed spans, not that a
      collector filtered them;
    - ``mean_ratio``: sampled-arm / off-arm mean latency (median over
      rounds; gate <= 1.02x) — what the 1% plane costs against no
      plane at all.

    Both ends of the RPC seam hold a sampler over the SAME crc32 hash
    (utils/trace.py head_keep) and the router's verdict additionally
    rides each submit frame, so worker and router cannot disagree; the
    per-arm rate flips live via the rpc ``trace`` op's ``sample``
    field. The final sampled rep's merged timeline is saved to
    `trace_out` (Chrome) and `otlp_out` (OTLP-JSON,
    tools/check_otlp.py)."""
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=prompt_len_range, max_new_range=max_new_range,
        seed=seed,
    )
    tracer = _make_tracer()
    spec = WorkerSpec(
        model=model_kw,
        engine={
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": list(prompt_buckets),
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": eos_id,
        },
        max_queue=len(trace) * (3 * pairs + 2),
        trace=True,
        trace_sample=sample,
        trace_keep_slow_s=keep_slow_s,
    )
    router, sup, handles = make_fleet_router(
        spec, procs, sup_config=SupervisorConfig(restart_base_s=0.25),
        tracer=tracer,
    )
    if tracer.sampler is None:  # --trace-sample 1.0: still need a knob
        from ddp_practice_tpu.utils.trace import TraceSampler

        tracer.set_sampler(TraceSampler(sample, keep_slow_s=keep_slow_s))
    arms = ("sampled", "full", "off")
    rates = {"sampled": sample, "full": 1.0}

    def set_arm(arm: str) -> None:
        if arm == "off":
            for h in handles:
                h.set_trace(False)
            tracer.disable()
            return
        for h in handles:
            h.set_trace(True, sample=rates[arm])
        tracer.sampler.rate = rates[arm]
        tracer.enable()

    def drain_frames() -> None:
        # trace frames ride the push stream behind the pub frames —
        # give the last worker flush a moment to land before counting
        deadline = time.monotonic() + 0.5
        while time.monotonic() < deadline:
            router.step()
            _fleet_wait(router, 0.01)

    rows = {a: [] for a in arms}
    spans = {a: [] for a in arms}
    try:
        # untimed shakeout: streams connect, offsets sampled, compiles
        # long since amortized by make_fleet_router's warm boot
        set_arm("sampled")
        _replay_through_router(router, trace, rid_offset=90_000_000,
                               fleet=True)
        drain_frames()
        tracer.clear()
        for i in range(pairs):
            order = arms[i % 3:] + arms[:i % 3]
            for arm in order:
                set_arm(arm)
                rows[arm].append(_replay_through_router(
                    router, trace,
                    rid_offset=(3 * i + order.index(arm)) * 1_000_000,
                    fleet=True,
                ))
                if arm != "off":
                    drain_frames()
                spans[arm].append(len(tracer))
                tracer.clear()
        # one final SAMPLED rep, kept in the recorder: the exported
        # artifacts show what a 1% operator actually ships
        set_arm("sampled")
        _replay_through_router(router, trace, rid_offset=91_000_000,
                               fleet=True)
        drain_frames()

        def med(xs):
            s = sorted(xs)
            n = len(s)
            return (s[n // 2] if n % 2
                    else 0.5 * (s[n // 2 - 1] + s[n // 2]))

        mean_ratios = [
            s["latency_s"]["mean"] / o["latency_s"]["mean"]
            for s, o in zip(rows["sampled"], rows["off"])
        ]
        # headline = ratio of per-arm MEDIAN means, not the median of
        # per-round ratios: one scheduler hiccup in one round inflates
        # a paired ratio permanently, while the pooled medians shrug
        # off a spiked round on either side (the per-round ratios stay
        # in the report to keep the spread visible)
        pooled_mean_ratio = (
            med([r["latency_s"]["mean"] for r in rows["sampled"]])
            / med([r["latency_s"]["mean"] for r in rows["off"]])
        )
        reductions = [
            1.0 - (s / f) if f else 0.0
            for s, f in zip(spans["sampled"], spans["full"])
        ]
        col = router.trace_collector
        report = {
            "trace": {
                "n_requests": n_requests, "rate_hz": rate_hz,
                "seed": seed,
                "prompt_len_range": list(prompt_len_range),
                "max_new_range": list(max_new_range),
            },
            "procs": procs,
            "pairs": pairs,
            "head_rate": sample,
            "keep_slow_s": keep_slow_s,
            "gate": "mean <= 1.02x vs off; span reduction >= 0.95",
            "mean_ratio": pooled_mean_ratio,
            "mean_ratio_per_round": mean_ratios,
            "span_reduction": med(reductions),
            "span_reduction_per_round": reductions,
            "spans_per_rep": {a: spans[a] for a in arms},
            "sampled": {
                "latency_s": rows["sampled"][-1]["latency_s"],
                "lost": sum(r["lost"] for r in rows["sampled"]),
            },
            "off": {"latency_s": rows["off"][-1]["latency_s"],
                    "lost": sum(r["lost"] for r in rows["off"])},
            "full": {"lost": sum(r["lost"] for r in rows["full"])},
            "sampling": tracer.sampling_meta(),
            "trace_plane": {
                "worker_frames": col.frames if col else 0,
                "worker_events": col.events if col else 0,
                "dropped": tracer.dropped,
                "skew_bound_s": col.skew_bound() if col else None,
            },
        }
        if trace_out:
            tracer.save(trace_out)
            report["trace_out"] = trace_out
        if otlp_out:
            tracer.save_otlp(otlp_out)
            report["otlp_out"] = otlp_out
        return report
    finally:
        sup.stop()


def fleet_otlp_push_bench(
    *,
    n_requests: int = 200,
    rate_hz: float = 100.0,
    procs: int = 2,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(2, 32),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
    pairs: int = 6,
    sample: float = 1.0,
    otlp_endpoint: Optional[str] = None,
    capture_dir: Optional[str] = None,
) -> dict:
    """Live OTLP/HTTP push vs file-only export at 100 rps: two arms
    against ONE warm worker fleet — ``file`` (tracer on, spans kept in
    memory for an exit-time save, the PR-11 posture) and ``push`` (the
    same tracer drained live by a background OtlpPusher POSTing real
    batches over real HTTP), rotated in order-balanced rounds.

    The acceptance number is ``mean_ratio``: push-arm / file-arm mean
    latency (ratio of per-arm median means; gate <= 1.02x) — what
    LIVE egress costs the serve loop against batching to disk. The
    tracer runs at FULL head rate by default so the pusher is fed the
    worst-case span flow, not a 1% trickle.

    With no ``otlp_endpoint`` the bench stands up its own
    StubOtlpCollector and additionally audits COMPLETENESS: every span
    the pusher claims to have sent must be present in the collector's
    batch-id-deduped capture (``spans_delivered`` == ``spans_pushed``).
    Each push round gets a fresh pusher whose final flush happens in
    ``close()`` OUTSIDE the timed window — the timed cost is the
    concurrent drain/POST traffic, which is the thing the gate is
    about."""
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec
    from ddp_practice_tpu.utils.telemetry import (
        OtlpPusher,
        StubOtlpCollector,
    )

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=prompt_len_range, max_new_range=max_new_range,
        seed=seed,
    )
    tracer = _make_tracer()
    spec = WorkerSpec(
        model=model_kw,
        engine={
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": list(prompt_buckets),
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": eos_id,
        },
        max_queue=len(trace) * (2 * pairs + 2),
        trace=True,
        trace_sample=sample,
    )
    router, sup, handles = make_fleet_router(
        spec, procs, sup_config=SupervisorConfig(restart_base_s=0.25),
        tracer=tracer,
    )
    collector = None
    endpoint = otlp_endpoint
    if endpoint is None:
        collector = StubOtlpCollector(capture_dir=capture_dir)
        endpoint = collector.endpoint

    def drain_frames() -> None:
        deadline = time.monotonic() + 0.5
        while time.monotonic() < deadline:
            router.step()
            _fleet_wait(router, 0.01)

    arms = ("file", "push")
    rows = {a: [] for a in arms}
    push_stats = {"batches_sent": 0, "spans_sent": 0,
                  "post_failures": 0, "batches_dropped": 0}
    try:
        # untimed shakeout (streams, offsets, warm boot amortized)
        _replay_through_router(router, trace, rid_offset=90_000_000,
                               fleet=True)
        drain_frames()
        tracer.clear()
        for i in range(pairs):
            order = arms if i % 2 == 0 else arms[::-1]
            for arm in order:
                rid_offset = (2 * i + order.index(arm)) * 1_000_000
                if arm == "push":
                    pusher = OtlpPusher(endpoint, tracer,
                                        interval_s=0.25)
                    try:
                        rows[arm].append(_replay_through_router(
                            router, trace, rid_offset=rid_offset,
                            fleet=True))
                        drain_frames()
                    finally:
                        pusher.close()  # final flush, untimed
                    for k in push_stats:
                        push_stats[k] += getattr(pusher, k)
                else:
                    rows[arm].append(_replay_through_router(
                        router, trace, rid_offset=rid_offset,
                        fleet=True))
                    drain_frames()
                tracer.clear()

        def med(xs):
            s = sorted(xs)
            n = len(s)
            return (s[n // 2] if n % 2
                    else 0.5 * (s[n // 2 - 1] + s[n // 2]))

        mean_ratios = [
            p["latency_s"]["mean"] / f["latency_s"]["mean"]
            for p, f in zip(rows["push"], rows["file"])
        ]
        pooled_mean_ratio = (
            med([r["latency_s"]["mean"] for r in rows["push"]])
            / med([r["latency_s"]["mean"] for r in rows["file"]])
        )
        report = {
            "trace": {
                "n_requests": n_requests, "rate_hz": rate_hz,
                "seed": seed,
                "prompt_len_range": list(prompt_len_range),
                "max_new_range": list(max_new_range),
            },
            "procs": procs,
            "pairs": pairs,
            "head_rate": sample,
            "gate": "mean <= 1.02x vs file-only export",
            "mean_ratio": pooled_mean_ratio,
            "mean_ratio_per_round": mean_ratios,
            "push": {
                **push_stats,
                "spans_pushed": push_stats["spans_sent"],
            },
            "file": {"latency_s": rows["file"][-1]["latency_s"],
                     "lost": sum(r["lost"] for r in rows["file"])},
            "push_arm": {"latency_s": rows["push"][-1]["latency_s"],
                         "lost": sum(r["lost"] for r in rows["push"])},
        }
        if collector is not None:
            report["push"]["spans_delivered"] = collector.spans
            report["push"]["batches_received"] = len(collector.seen)
            report["push"]["duplicate_batches"] = collector.duplicates
            report["push"]["complete"] = bool(
                collector.spans == push_stats["spans_sent"])
            if capture_dir:
                report["push"]["capture_dir"] = capture_dir
        return report
    finally:
        sup.stop()
        if collector is not None:
            collector.close()


def fleet_adaptive_sampling_bench(
    *,
    rate_hz: float = 100.0,
    step_factor: float = 4.0,
    budget_sps: float = 150.0,
    chunk_s: float = 1.0,
    chunks_base: int = 2,
    chunks_step: int = 5,
    chunks_measure: int = 3,
    procs: int = 2,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(2, 32),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
) -> dict:
    """Adaptive head-rate control under a real load step: one warm
    fleet driven in ~`chunk_s` arrival chunks at `rate_hz`, then
    stepped to `rate_hz * step_factor` (default 4x), with an
    AdaptiveHeadRateController stepping between chunks and pushing
    every rate change to the workers via the live rpc ``trace`` op.

    The acceptance pair, measured over the FINAL `chunks_measure`
    chunks (after the controller has had the step phase to converge):

    - ``kept_sps`` vs ``budget_sps`` as ``budget_err`` (relative), and
    - ``within_budget``: 1.0 iff the error is <= 0.20 — the ±20%
      contract, reported as a 0/1 so check_bench can gate it
      absolutely (baseline 1, tol 0).

    Both the controller's observations and the final measurement use
    the same wall-clock basis (real elapsed time including inter-chunk
    drains), so the loop is judged against exactly the flow it could
    see. ``rate_changes``/``rate_log`` keep the correction history
    visible — a converged run makes 2-4 changes, not a change per
    evaluation."""
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec
    from ddp_practice_tpu.utils.trace import AdaptiveHeadRateController

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }

    def chunk(rate: float, k: int):
        return build_trace(
            n_requests=max(8, int(rate * chunk_s)), rate_hz=rate,
            vocab=vocab, prompt_len_range=prompt_len_range,
            max_new_range=max_new_range, seed=seed + 7 * k + 1,
        )

    step_rate = rate_hz * step_factor
    total_chunks = chunks_base + chunks_step + chunks_measure
    tracer = _make_tracer()
    spec = WorkerSpec(
        model=model_kw,
        engine={
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": list(prompt_buckets),
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": eos_id,
        },
        max_queue=int(step_rate * chunk_s) * (total_chunks + 2),
        trace=True,
        trace_sample=1.0,
    )
    router, sup, handles = make_fleet_router(
        spec, procs, sup_config=SupervisorConfig(restart_base_s=0.25),
        tracer=tracer,
    )
    if tracer.sampler is None:  # rate 1.0 attaches no sampler by itself
        from ddp_practice_tpu.utils.trace import TraceSampler

        tracer.set_sampler(TraceSampler(1.0))

    def push_rate(rate: float) -> None:
        for h in handles:
            h.set_trace(True, sample=rate)

    ctl = AdaptiveHeadRateController(
        tracer, budget_sps, interval_s=0.5, hold_s=1.0,
        apply_fn=push_rate,
    )

    def drain_frames() -> None:
        deadline = time.monotonic() + 0.3
        while time.monotonic() < deadline:
            router.step()
            _fleet_wait(router, 0.01)

    lost = 0

    def run_chunk(rate: float, k: int) -> None:
        nonlocal lost
        r = _replay_through_router(router, chunk(rate, k),
                                   rid_offset=(k + 1) * 1_000_000,
                                   fleet=True)
        lost += r["lost"]
        drain_frames()
        ctl.step()

    try:
        # untimed shakeout, then the controller's measurement baseline
        _replay_through_router(router, chunk(rate_hz, 0),
                               rid_offset=90_000_000, fleet=True)
        drain_frames()
        ctl.step()
        k = 1
        for _ in range(chunks_base):
            run_chunk(rate_hz, k)
            k += 1
        for _ in range(chunks_step):
            run_chunk(step_rate, k)
            k += 1
        # final window: same wall-clock basis the controller steers by
        k0 = tracer.spans_sampled + tracer.spans_kept
        t0 = time.monotonic()
        for _ in range(chunks_measure):
            run_chunk(step_rate, k)
            k += 1
        kept_sps = ((tracer.spans_sampled + tracer.spans_kept) - k0) \
            / (time.monotonic() - t0)
        budget_err = abs(kept_sps - budget_sps) / budget_sps
        return {
            "rate_hz": rate_hz,
            "step_rate_hz": step_rate,
            "step_factor": step_factor,
            "budget_sps": budget_sps,
            "chunk_s": chunk_s,
            "chunks": {"base": chunks_base, "step": chunks_step,
                       "measure": chunks_measure},
            "procs": procs,
            "gate": "kept_sps within ±20% of budget after the step",
            "kept_sps": kept_sps,
            "budget_err": budget_err,
            "within_budget": 1.0 if budget_err <= 0.20 else 0.0,
            "rate_final": ctl.rate,
            "rate_changes": ctl.changes,
            "rate_log": ctl.rate_log,
            "lost": lost,
            "sampling": tracer.sampling_meta(),
        }
    finally:
        sup.stop()


def fleet_autoscale_bench(
    *,
    rate_hz: float = 25.0,
    step_factor: float = 4.0,
    chunk_s: float = 1.0,
    chunks_base: int = 2,
    chunks_step: int = 3,
    chunks_post: int = 7,
    procs: int = 3,
    autoscale_min: int = 1,
    autoscale_max: int = 3,
    standby: int = 1,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(32, 96),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
) -> dict:
    """Elastic fleet vs fixed fleet under a 4x arrival step, at equal
    SLO: the same chunked trace (base rate -> `step_factor`x burst ->
    base again) is replayed through TWO separately-built fleets — a
    fixed fleet PROVISIONED FOR THE PEAK (`procs`, the fair fight:
    matching the elastic ceiling `autoscale_max` is what an operator
    without an autoscaler must deploy to survive the burst), then an
    autoscaled fleet that starts at `autoscale_min` with `standby`
    pre-warmed standbys. Both arms carry an identical SLOWatchdog, so
    brown-out shedding judges them by the same rules; the elastic arm's
    claim is GOODPUT PER WORKER-SECOND, not raw goodput.

    The check_bench-gated keys:

    - ``goodput_per_worker_ratio``: elastic useful-tokens per
      worker-second over fixed (worker-seconds integrate the active
      fleet size over the scale-event timeline; the fixed arm pays
      `procs` the whole run);
    - ``lost``: submitted-but-never-completed across BOTH arms (shed at
      the door is a status, lost is a bug) — gated 0;
    - ``reaction_within_window``: 1.0 iff the first scale-up after the
      step landed within ``reaction_window_s`` (one policy evaluation
      interval + eval-phase slack) of the first policy evaluation that
      SAW trigger pressure — the loop's own latency, separated from
      the queue-build physics reported as ``signal_build_s``;
    - ``oscillation_ok``: 1.0 iff scale-direction changes <= the
      hold-window bound floor(elapsed/hold_s) + 1 — the no-thrash
      contract, same shape as the adaptive head-rate gate;
    - ``promote_join_s``: warm-standby promotion latency (pool take ->
      dispatch join), the number that must sit well under the ~15s
      cold spawn also reported here as ``cold_spawn_s``.
    """
    from ddp_practice_tpu.serve.autoscaler import (
        Autoscaler,
        AutoscalerConfig,
    )
    from ddp_practice_tpu.serve.scheduler import MonotonicClock
    from ddp_practice_tpu.serve.slo import SLOConfig, SLOWatchdog
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }

    def chunk(rate: float, k: int):
        return build_trace(
            n_requests=max(8, int(rate * chunk_s)), rate_hz=rate,
            vocab=vocab, prompt_len_range=prompt_len_range,
            max_new_range=max_new_range, seed=seed + 7 * k + 1,
        )

    step_rate = rate_hz * step_factor
    total_chunks = chunks_base + chunks_step + chunks_post
    spec = WorkerSpec(
        model=model_kw,
        engine={
            "max_slots": max_slots, "max_len": max_len,
            "prompt_buckets": list(prompt_buckets),
            "temperature": 0.0, "decode_burst": decode_burst,
            "eos_id": eos_id,
        },
        max_queue=int(step_rate * chunk_s) * (total_chunks + 2),
    )
    # the elastic policy: trip fast (one sub-second evaluation interval,
    # up_pressure just under the router's brown-out threshold so growth
    # fires before shedding clamps the signal), resolve slow (calm must
    # hold down_stable_s, reversals blocked inside hold_s)
    acfg = AutoscalerConfig(
        min_size=autoscale_min, max_size=autoscale_max,
        eval_interval_s=0.4, up_pressure=1.3, down_pressure=0.45,
        hold_s=4.0, cooldown_up_s=1.0, cooldown_down_s=3.0,
        down_stable_s=1.5, standby_target=standby,
    )
    # "within one evaluation window" of the signal: the commit may land
    # an eval after the crossing eval, plus scheduling slack on a
    # loaded box
    reaction_window_s = acfg.eval_interval_s + 0.25

    def slo_watchdog(clock):
        # equal-SLO contract: both arms get this exact config
        return SLOWatchdog(SLOConfig(
            ttft_p99_s=1.5, fast_window_s=1.5, slow_window_s=5.0,
            trip_burn=2.0, resolve_burn=1.0, min_events=8,
        ), clock=clock)

    def drain_frames(router) -> None:
        deadline = time.monotonic() + 0.3
        while time.monotonic() < deadline:
            router.step()
            _fleet_wait(router, 0.01)

    def integrate_size(events, t0, t1, size0) -> float:
        """Worker-seconds from the scale-event ledger: piecewise-
        constant active size over [t0, t1] (event "t"/"size" share the
        bench's time.monotonic basis via MonotonicClock)."""
        pts = [(t0, size0)]
        for e in events:
            if t0 <= e["t"] <= t1:
                pts.append((e["t"], e["size"]))
        ws = 0.0
        last_t, last_s = pts[0]
        for t, s in pts[1:]:
            ws += (t - last_t) * last_s
            last_t, last_s = t, s
        ws += (t1 - last_t) * last_s
        return ws

    def run_arm(auto: bool) -> dict:
        clock = MonotonicClock()
        n0 = autoscale_min if auto else procs
        router, sup, handles = make_fleet_router(
            spec, n0, clock=clock,
            sup_config=SupervisorConfig(restart_base_s=0.25,
                                        shrink_kill_after_s=10.0),
            slo=slo_watchdog(clock),
        )
        asc = None
        cold_spawn_s = None
        try:
            if auto:
                asc = Autoscaler(router, sup, spec, config=acfg,
                                 clock=clock)
                router.autoscaler = asc
                t0 = time.monotonic()
                if not asc.pool.wait_ready(timeout_s=300.0, n=standby):
                    raise RuntimeError("standby pool never warmed")
                # the pool fill IS a cold spawn — the latency a warm
                # promotion buys its way out of
                cold_spawn_s = time.monotonic() - t0
            # untimed shakeout: compile warmup through the seam
            _replay_through_router(router, chunk(rate_hz, 0),
                                   rid_offset=90_000_000, fleet=True)
            drain_frames(router)

            rows = []

            def run_chunk(rate: float, k: int) -> None:
                rows.append(_replay_through_router(
                    router, chunk(rate, k),
                    rid_offset=(k + 1) * 1_000_000, fleet=True))
                drain_frames(router)

            t_start = time.monotonic()
            size0 = sup.active_slots()
            k = 1
            for _ in range(chunks_base):
                run_chunk(rate_hz, k)
                k += 1
            t_burst = time.monotonic()
            for _ in range(chunks_step):
                run_chunk(step_rate, k)
                k += 1
            for _ in range(chunks_post):
                run_chunk(rate_hz, k)
                k += 1
            if auto:
                # let in-flight drains retire so the worker-seconds
                # ledger charges the elastic arm for its drain tail
                deadline = time.monotonic() + 15.0
                while time.monotonic() < deadline and asc._draining:
                    router.step()
                    _fleet_wait(router, 0.02)
            t_end = time.monotonic()

            events = list(asc.events) if auto else []
            ws = (integrate_size(events, t_start, t_end, size0)
                  if auto else procs * (t_end - t_start))
            useful = sum(r["useful_tokens"] for r in rows)
            statuses: dict = {}
            for r in rows:
                for s, n in r["statuses"].items():
                    statuses[s] = statuses.get(s, 0) + n
            arm = {
                "mode": "autoscaled" if auto else "fixed",
                "workers_start": n0,
                "elapsed_s": t_end - t_start,
                "worker_seconds": ws,
                "useful_tokens": useful,
                "goodput_per_worker": useful / ws if ws > 0 else 0.0,
                "lost": sum(r["lost"] for r in rows),
                "statuses": statuses,
                "slo": router.slo.burn_signal(),
            }
            if auto:
                ups = [e for e in events if e["direction"] == "up"]
                post = [e for e in ups if e["t"] >= t_burst]
                warm = [e for e in ups if e.get("warm")]
                dirs = [e["direction"] for e in events]
                changes = sum(1 for a, b in zip(dirs, dirs[1:])
                              if a != b)
                bound = int((t_end - t_start) / acfg.hold_s) + 1
                reaction_s = signal_build_s = None
                if post:
                    # the first policy evaluation that SAW trigger
                    # pressure after the step: reaction is the loop's
                    # own latency from that signal; the queue-build
                    # time before it is physics, reported separately
                    t_up = post[0]["t"]
                    xs = [r["t"] for r in asc.pressure_log
                          if t_burst <= r["t"] <= t_up
                          and r["pressure"] >= acfg.up_pressure]
                    signal_t = xs[0] if xs else t_up
                    reaction_s = t_up - signal_t
                    signal_build_s = signal_t - t_burst
                arm.update({
                    "final_size": sup.active_slots(),
                    "cold_spawn_s": cold_spawn_s,
                    "reaction_s": reaction_s,
                    "signal_build_s": signal_build_s,
                    "promote_join_s": (warm[0]["join_s"]
                                       if warm else None),
                    "direction_changes": changes,
                    "oscillation_bound": bound,
                    "scale_events": events,
                    "autoscaler": asc.snapshot(),
                })
            return arm
        finally:
            if asc is not None:
                asc.close()
            sup.stop()

    fixed = run_arm(auto=False)
    auto = run_arm(auto=True)
    reaction_s = auto.get("reaction_s")
    gpw_ratio = (auto["goodput_per_worker"]
                 / max(fixed["goodput_per_worker"], 1e-9))
    return {
        "rate_hz": rate_hz,
        "step_rate_hz": step_rate,
        "step_factor": step_factor,
        "chunk_s": chunk_s,
        "chunks": {"base": chunks_base, "step": chunks_step,
                   "post": chunks_post},
        "procs_fixed": procs,
        "autoscale": {"min": autoscale_min, "max": autoscale_max,
                      "standby": standby,
                      "eval_interval_s": acfg.eval_interval_s,
                      "hold_s": acfg.hold_s,
                      "up_pressure": acfg.up_pressure,
                      "down_pressure": acfg.down_pressure},
        "gate": ("goodput/worker >= fixed at equal SLO, react within "
                 "one eval window, no thrash, zero lost, warm "
                 "promotion << cold spawn"),
        "fixed": fixed,
        "autoscaled": auto,
        "goodput_per_worker_ratio": gpw_ratio,
        "lost": fixed["lost"] + auto["lost"],
        "reaction_s": reaction_s,
        "signal_build_s": auto.get("signal_build_s"),
        "reaction_window_s": reaction_window_s,
        "reaction_within_window": (
            1.0 if reaction_s is not None
            and reaction_s <= reaction_window_s else 0.0),
        "oscillation_ok": (
            1.0 if auto["direction_changes"]
            <= auto["oscillation_bound"] else 0.0),
        "promote_join_s": auto.get("promote_join_s"),
        "cold_spawn_s": auto.get("cold_spawn_s"),
    }


def _score_streams(router, comps) -> dict:
    """Score and CLEAR the router's TokenStreams from the consumer's
    seat (the bench IS the consumer). Everything here is re-derived
    from the delivered events, independently of the router's own
    cursors: `chunk_dupes`/`chunk_gaps` recount token-offset overlaps
    and holes (the exactly-once gate pins both at 0 — `suppressed` is
    the router absorbing re-decoded salvage and is EXPECTED under
    chaos), `inter_token_s` is the per-token delivery cadence between
    consecutive chunk arrivals, `ttft_s` is first DELIVERED token
    minus arrival, and `resume_gap_s` is the consumer-visible stall a
    failover splice cost each resumed stream."""
    arrival = {c.rid: c.arrival for c in comps}
    final_tokens = {c.rid: c.tokens for c in comps}
    inter, ttft, gaps_s = [], [], []
    dupes = holes = suppressed = resumed = 0
    unterminated = mismatched = 0
    for rid, st in router.streams.items():
        delivered = 0
        last_t = None
        for ev in st.events:
            if ev.kind == "resumed":
                resumed += 1
                continue
            if ev.kind != "tokens" or not ev.tokens:
                continue
            if ev.start < delivered:
                dupes += delivered - ev.start
            elif ev.start > delivered:
                holes += ev.start - delivered
            delivered = ev.start + len(ev.tokens)
            if last_t is None:
                if rid in arrival:
                    ttft.append(ev.t - arrival[rid])
            else:
                # one chunk = one consumer-visible delivery; its
                # tokens share the arrival instant, so the per-token
                # cadence is the chunk gap amortized over the chunk
                inter.extend([(ev.t - last_t) / len(ev.tokens)]
                             * len(ev.tokens))
            last_t = ev.t
        if not st.closed:
            unterminated += 1
        if st.tokens() != final_tokens.get(rid, st.tokens()):
            mismatched += 1  # stream view disagrees with completion
        if st.resume_gap_s:
            gaps_s.append(st.resume_gap_s)
        suppressed += st.suppressed
        holes += st.gaps
    n = len(router.streams)
    router.streams.clear()
    return {
        "streams": n,
        "chunk_dupes": dupes,
        "chunk_gaps": holes,
        "suppressed_tokens": suppressed,
        "resumed_markers": resumed,
        "unterminated": unterminated,
        "stream_completion_mismatches": mismatched,
        "inter_token_s": _percentiles(inter),
        "consumer_ttft_s": _percentiles(ttft),
        "resume_gap_s": _percentiles(gaps_s),
        "resume_gap_p99_s": (_percentiles(gaps_s).get("p99", 0.0)
                             if gaps_s else 0.0),
        "inter_token_p99_s": (_percentiles(inter).get("p99", 0.0)
                              if inter else 0.0),
    }


def streaming_bench(
    *,
    n_requests: int = 32,
    rate_hz: float = 8.0,
    procs: int = 2,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    max_new_range=(2, 32),
    decode_burst: int = 8,
    eos_id: Optional[int] = 46,
    seed: int = 0,
    reps: int = 6,
    fault_plan=None,
    telemetry_out: Optional[str] = None,
) -> dict:
    """Token STREAMING through the worker fleet, two operating points:

    - overhead (no kill plan): the same trace replays through TWO warm
      worker fleets — streaming delivery (chunks in every pub frame,
      router TokenStreams armed) vs end-of-request delivery (chunk
      plane fully off, worker-side and router-side) — in alternating
      order per rep; the headline is the median per-rep MEAN-latency
      ratio (acceptance gate: <= 1.05x at 8 rps). Every rep also
      cross-checks each stream's concatenation against its completion.

    - chaos (`fault_plan` with kill specs): ONE streaming fleet, real
      signals mid-stream, and the report is the CONSUMER'S ledger —
      re-derived duplicate/missing token counts (gated at zero),
      inter-token p99 at the consumer, resume-gap p99 (the stall a
      SIGKILL splice actually cost), resumed-marker count, and the
      tools/check_stream.py audit over the run's telemetry JSONL
      (`telemetry_out`; a temp file when not asked for)."""
    from ddp_practice_tpu.serve.router import RouterConfig
    from ddp_practice_tpu.serve.faults import FleetFaultDriver
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec
    from ddp_practice_tpu.utils.telemetry import TelemetryExporter

    model_kw = {
        "vocab_size": vocab, "max_len": max_len, "hidden_dim": hidden,
        "depth": depth, "num_heads": heads, "mlp_dim": mlp,
        "pos_emb": "rope",
    }
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=prompt_len_range, max_new_range=max_new_range,
        seed=seed,
    )
    chaos = fault_plan is not None and bool(fault_plan.kills())
    if fault_plan is not None and not chaos:
        raise ValueError("streaming_bench interprets only 'kill' specs")
    engine_kw = {
        "max_slots": max_slots, "max_len": max_len,
        "prompt_buckets": list(prompt_buckets),
        "temperature": 0.0, "decode_burst": decode_burst,
        "eos_id": eos_id,
    }
    max_queue = len(trace) * max(1, reps)

    def build(stream: bool, telemetry=None):
        return make_fleet_router(
            WorkerSpec(model=model_kw, engine=dict(engine_kw),
                       max_queue=max_queue, stream=stream),
            procs,
            config=RouterConfig(streaming=stream),
            sup_config=SupervisorConfig(restart_base_s=0.25),
            telemetry=telemetry,
        )

    def med(xs):
        s = sorted(xs)
        n = len(s)
        return (s[n // 2] if n % 2
                else 0.5 * (s[n // 2 - 1] + s[n // 2]))

    report = {
        "trace": {
            "n_requests": n_requests, "rate_hz": rate_hz, "seed": seed,
            "prompt_len_range": list(prompt_len_range),
            "max_new_range": list(max_new_range),
        },
        "procs": procs,
    }

    if chaos:
        # ---------------- chaos arm: one streaming fleet, real kills
        tmp = None
        if telemetry_out is None:
            import tempfile

            tmp = tempfile.NamedTemporaryFile(
                suffix=".jsonl", delete=False)
            telemetry_out = tmp.name
            tmp.close()
        exporter = TelemetryExporter(telemetry_out,
                                     snapshot_interval_s=0.0)
        router, sup, handles = build(True, telemetry=exporter)
        try:
            driver = FleetFaultDriver(fault_plan, sup.kill)
            before = len(router.completions)
            row = _replay_through_router(router, trace, driver=driver,
                                         fleet=True)
            comps = router.completions[before:]
            streams = _score_streams(router, comps)
            m = router.metrics
            row.update({
                "mode": f"stream fleet x{procs}",
                "failovers": m.failovers.value,
                "retries": m.retries.value,
                "worker_restarts": list(sup.restarts),
                "kills_fired": [
                    {"replica": f.replica, "sig": f.sig, "at_s": f.at_s}
                    for f in driver.fired
                ],
            })
            report.update({
                "reps": 1,
                "fleet": row,
                "fault_plan": fault_plan.to_json(),
                "telemetry_out": telemetry_out,
                # the gated keys, at top level for check_bench's dotted
                # paths: exactly-once re-derived at the consumer
                "chunk_dupes": streams["chunk_dupes"],
                "chunk_gaps": streams["chunk_gaps"],
                "lost": row["lost"],
                "unterminated": streams["unterminated"],
                "stream_completion_mismatches":
                    streams["stream_completion_mismatches"],
                "inter_token_p99_s": streams["inter_token_p99_s"],
                "resume_gap_p99_s": streams["resume_gap_p99_s"],
                "streams": streams,
            })
        finally:
            sup.stop()
            exporter.close()
        # offline audit of the SAME contract from the telemetry file
        # alone — the artifact a production incident would have
        try:
            from tools.check_stream import load_jsonl, stream_verdict

            ok, audit = stream_verdict(load_jsonl(telemetry_out))
            report["check_stream"] = {
                "ok": ok, "streams": audit["streams"],
                "violations": sum(len(v)
                                  for v in audit["violations"].values()),
            }
        except ImportError:  # tools/ not importable (installed pkg)
            report["check_stream"] = {"ok": None}
        if tmp is not None:
            os.unlink(telemetry_out)
            report.pop("telemetry_out")
        return report

    # ------------- overhead arm: streaming vs end-of-request delivery
    r_on, sup_on, _ = build(True)
    r_off, sup_off, _ = build(False)
    rows = {"on": [], "off": []}
    mismatches = 0
    try:
        for rep in range(reps):
            order = ["on", "off"] if rep % 2 == 0 else ["off", "on"]
            for side in order:
                router = r_on if side == "on" else r_off
                before = len(router.completions)
                rows[side].append(_replay_through_router(
                    router, trace, rid_offset=rep * 1_000_000,
                    fleet=True,
                ))
                if side == "on":
                    comps = router.completions[before:]
                    streams = _score_streams(router, comps)
                    mismatches += (
                        streams["stream_completion_mismatches"]
                        + streams["chunk_dupes"] + streams["chunk_gaps"]
                        + streams["unterminated"])
                    rows[side][-1]["streams"] = streams
        ratios_mean = [on["latency_s"]["mean"] / off["latency_s"]["mean"]
                       for on, off in zip(rows["on"], rows["off"])]
        ratios_p50 = [on["latency_s"]["p50"] / off["latency_s"]["p50"]
                      for on, off in zip(rows["on"], rows["off"])]
        report.update({
            "reps": reps,
            "gate": "mean <= 1.05x",
            "latency_ratio_mean": med(ratios_mean),
            "latency_ratio_mean_per_rep": ratios_mean,
            "latency_ratio_p50": med(ratios_p50),
            "goodput_ratio": med(
                [on["goodput_tokens_per_sec"]
                 / off["goodput_tokens_per_sec"]
                 for on, off in zip(rows["on"], rows["off"])]
            ),
            "streaming": {
                "latency_s": rows["on"][-1]["latency_s"],
                "lost": sum(r["lost"] for r in rows["on"]),
                "last_rep_streams": rows["on"][-1]["streams"],
            },
            "end_of_request": {
                "latency_s": rows["off"][-1]["latency_s"],
                "lost": sum(r["lost"] for r in rows["off"]),
            },
            # every rep's exactly-once cross-check, summed: stream-vs-
            # completion disagreements + re-derived dupes/gaps +
            # unterminated streams (all must be 0 fault-free)
            "stream_violations": mismatches,
        })
        return report
    finally:
        sup_on.stop()
        sup_off.stop()


def _mixed_prefill_trace(*, n_requests, rate_hz, vocab, long_len,
                         short_range=(2, 10), max_new_range=(4, 8),
                         seed=0) -> list:
    """Every third request carries a LONG cold prompt, the rest are
    short interactive ones — the Sarathi mixed workload where one
    monolithic long prefill head-of-line-blocks every short request
    queued behind it. Chunked prefill's whole claim is the short
    requests' TTFT tail on exactly this trace."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_hz, n_requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n_requests):
        long = i % 3 == 0
        plen = (long_len if long
                else int(rng.integers(short_range[0],
                                      short_range[1] + 1)))
        trace.append({
            "rid": i,
            "arrival": float(arrivals[i]),
            "prompt": rng.integers(0, vocab, plen).tolist(),
            "max_new_tokens": int(rng.integers(
                max_new_range[0], max_new_range[1] + 1)),
            "long": long,
        })
    return trace


def _wire_replay(port, trace, *, body_extra=None,
                 timeout_s: float = 600.0) -> tuple:
    """Fire one arrival trace at a live Frontdoor through REAL client
    sockets — one thread per request, sleeping to its Poisson arrival,
    then a blocking `sse_request`. Returns ``({rid: {status, sent,
    events}}, elapsed_s)``; `body_extra(t)` merges per-request fields
    (sampling knobs, tenant) into the POSTed JSON."""
    import threading

    from ddp_practice_tpu.serve.frontdoor import sse_request

    results: dict = {}
    lock = threading.Lock()
    t0 = time.monotonic()

    def one(t):
        wait = t0 + t["arrival"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        body = {"prompt": t["prompt"],
                "max_new_tokens": t["max_new_tokens"], "seed": 0}
        if body_extra is not None:
            body.update(body_extra(t))
        sent = time.monotonic()
        try:
            status, events = sse_request(
                "127.0.0.1", port, body, timeout_s=timeout_s)
        except OSError:
            status, events = -1, []
        with lock:
            results[t["rid"]] = {
                "status": status, "sent": sent, "events": events}

    threads = [threading.Thread(target=one, args=(t,), daemon=True)
               for t in trace]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return results, time.monotonic() - t0


def _score_wire(trace, results, elapsed) -> tuple:
    """Score a wire replay the way _replay_through_router scores an
    in-process one — goodput over terminal-ok streams, client-side
    TTFT/latency percentiles, loss — and keep the raw SSE capture
    (`{"stream", "id", "event", "data"}` records) for the
    tools/check_stream.py --sse audit. Returns (row, tokens_by_rid,
    capture)."""
    tokens: dict = {}
    capture: list = []
    ttfts, lats = [], []
    statuses: dict = {}
    ended_ok = 0
    resumed = 0
    ok_tokens = 0
    for t in trace:
        rid = t["rid"]
        r = results.get(rid)
        if r is None or r["status"] != 200:
            statuses[f"http_{r['status'] if r else 'none'}"] = (
                statuses.get(
                    f"http_{r['status'] if r else 'none'}", 0) + 1)
            continue
        toks: list = []
        end_status = None
        first_tok_t = None
        for ev in r["events"]:
            capture.append({"stream": f"rid:{rid}", "id": ev["id"],
                            "event": ev["event"], "data": ev["data"]})
            data = ev["data"] if isinstance(ev["data"], dict) else {}
            if ev["event"] == "tokens":
                toks.extend(data.get("tokens") or [])
                if first_tok_t is None:
                    first_tok_t = ev["t"]
            elif ev["event"] == "resumed":
                resumed += 1
            elif ev["event"] == "end":
                end_status = data.get("status")
        tokens[rid] = toks
        key = end_status if end_status is not None else "unterminated"
        statuses[key] = statuses.get(key, 0) + 1
        if end_status in ("eos", "length", "stop"):
            ended_ok += 1
            ok_tokens += len(toks)
            if first_tok_t is not None:
                ttfts.append(first_tok_t - r["sent"])
            lats.append(r["events"][-1]["t"] - r["sent"])
    row = {
        "elapsed_s": elapsed,
        "useful_tokens": ok_tokens,
        "goodput_tokens_per_sec": ok_tokens / elapsed,
        "ttft_s": _percentiles(ttfts) if ttfts else {},
        "latency_s": _percentiles(lats) if lats else {},
        "completions": ended_ok,
        "lost": len(trace) - ended_ok,
        "statuses": statuses,
        "resumed_markers": resumed,
    }
    return row, tokens, capture


def _sse_audit(capture) -> dict:
    """The offline wire audit, in-process: map the SSE capture through
    tools/check_stream.py --sse and report the verdict (the bench's
    own acceptance row, same rules the CLI applies to a dump)."""
    try:
        from tools.check_stream import sse_to_chunks, stream_verdict
    except ImportError:  # tools/ not importable (installed pkg)
        return {"ok": None}
    ok, audit = stream_verdict(sse_to_chunks(capture))
    return {
        "ok": ok, "streams": audit["streams"],
        "violations": sum(len(v)
                          for v in audit["violations"].values()),
    }


def frontdoor_bench(
    *,
    n_requests: int = 24,
    rate_hz: float = 100.0,
    max_slots: int = 8,
    vocab: int = 32,
    hidden: int = 64,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 128,
    decode_burst: int = 8,
    procs: int = 2,
    seed: int = 0,
    sse_out: Optional[str] = None,
) -> dict:
    """End-to-end HTTP/SSE front door (serve/frontdoor.py), four arms
    producing the BENCH_serve.json `frontdoor_100rps` entry and its
    check_bench-gated keys:

    - **wire vs in-process** — the SAME Poisson trace replays through a
      bare `Router.stream` loop and through real client sockets against
      a Frontdoor over an identical router. Gates: `token_identity`
      (greedy streams bit-identical across the wire, 1.0) and
      `goodput_ratio` (wire/in-process — the whole HTTP+SSE+thread hop
      must cost single-digit percent). The wire capture is audited by
      tools/check_stream.py --sse (`check_stream.ok`).
    - **chunked prefill TTFT** — a mixed long/short trace through two
      paged+prefix-cache front doors, `prefill_chunk` on vs off. Gate:
      `ttft_p99_ratio_chunked`, the SHORT (interactive) requests'
      client-side TTFT p99 ratio — chunking exists to stop a monolithic
      long prefill head-of-line-blocking them (<= 0.85 acceptance).
    - **mid-stream SIGKILL** — the same wire consumer against a
      `procs`-worker FLEET front door with a real SIGKILL mid-decode.
      Gate: `sigkill_lost` == 0 (every socket still gets its typed
      terminal; resumes splice under the same ids the --sse audit
      checks).
    - **mixed sampling churn** — greedy and per-request sampled
      traffic interleaved through one per_slot_sampling engine. Gate:
      `sampling_new_compiles` == 0 (one jitted decode program serves
      both, no shape/program churn from the knobs).
    """
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.frontdoor import (
        Frontdoor,
        FrontdoorConfig,
    )
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.router import (
        Router,
        RouterConfig,
        make_router,
    )
    from ddp_practice_tpu.serve.scheduler import (
        MonotonicClock,
        Request,
        Scheduler,
    )

    model, params = _build_model(
        vocab=vocab, max_len=128, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
    )
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=(2, 16), max_new_range=(4, 24), seed=seed,
    )
    ecfg = EngineConfig(
        max_slots=max_slots, max_len=96, prompt_buckets=(16,),
        temperature=0.0, decode_burst=decode_burst, eos_id=None,
    )
    report: dict = {
        "trace": {
            "n_requests": n_requests, "rate_hz": rate_hz,
            "seed": seed, "prompt_len_range": [2, 16],
            "max_new_range": [4, 24],
        },
    }

    # ---------------- arm 1: wire identity + goodput vs in-process
    ip_router = make_router(model, params, 1, ecfg)
    ip_router.warmup()
    row_ip = _replay_through_router(ip_router, trace)
    ref_tokens = {t["rid"]: ip_router.stream(t["rid"]).tokens()
                  for t in trace}
    row_ip["mode"] = "in-process router.stream"
    report["in_process"] = row_ip

    fd = Frontdoor(make_router(model, params, 1, ecfg),
                   config=FrontdoorConfig())
    fd.driver.router.warmup()
    fd.start()
    try:
        results, elapsed = _wire_replay(fd.port, trace)
    finally:
        fd.close()
    row_wire, wire_tokens, capture = _score_wire(
        trace, results, elapsed)
    row_wire["mode"] = "frontdoor wire"
    matched = sum(
        1 for t in trace
        if wire_tokens.get(t["rid"]) == ref_tokens[t["rid"]]
        and ref_tokens[t["rid"]]
    )
    report.update({
        "wire": row_wire,
        "token_identity": matched / len(trace),
        "goodput_ratio": (row_wire["goodput_tokens_per_sec"]
                          / row_ip["goodput_tokens_per_sec"]),
        "check_stream": _sse_audit(capture),
    })

    # ---------------- arm 2: chunked prefill TTFT on mixed long/short
    long_len = 720
    model_l, params_l = _build_model(
        vocab=vocab, max_len=1024, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
    )
    mixed = _mixed_prefill_trace(
        n_requests=18, rate_hz=rate_hz, vocab=vocab,
        long_len=long_len, seed=seed,
    )

    def paged_frontdoor(chunk: int) -> Frontdoor:
        # bucket 768 + a burst-rounded reservation + the request's own
        # new tokens: leave two bursts of headroom past the bucket
        cap_blocks = -(-(768 + 2 * 32 + decode_burst) // 16)
        engine = PagedEngine(
            model_l, params_l,
            EngineConfig(
                max_slots=4, max_len=1024,
                prompt_buckets=(16, 32, 768), temperature=0.0,
                decode_burst=decode_burst, eos_id=None,
                block_size=16, max_blocks_per_slot=cap_blocks,
                num_blocks=1 + 4 * cap_blocks,
                prefix_cache=True, prefill_chunk=chunk,
            ),
        )
        clock = MonotonicClock()
        sched = Scheduler(engine, clock=clock,
                          max_queue=len(mixed),
                          metrics=ServeMetrics())
        router = Router([sched], clock=clock)
        router.warmup()
        return Frontdoor(router, config=FrontdoorConfig())

    chunk_rows = {}
    chunk_tokens = {}
    for label, chunk in (("unchunked", 0), ("chunked", 32)):
        fd2 = paged_frontdoor(chunk)
        fd2.start()
        try:
            results, elapsed = _wire_replay(fd2.port, mixed)
        finally:
            fd2.close()
        row, toks, _ = _score_wire(mixed, results, elapsed)
        short_ttfts = []
        for t in mixed:
            r = results.get(t["rid"])
            if t["long"] or r is None or r["status"] != 200:
                continue
            first = next((ev["t"] for ev in r["events"]
                          if ev["event"] == "tokens"), None)
            if first is not None:
                short_ttfts.append(first - r["sent"])
        row["ttft_short_s"] = (_percentiles(short_ttfts)
                               if short_ttfts else {})
        chunk_rows[label] = row
        chunk_tokens[label] = toks
    ttft_ratio = (chunk_rows["chunked"]["ttft_short_s"]["p99"]
                  / chunk_rows["unchunked"]["ttft_short_s"]["p99"])
    report.update({
        "chunked_prefill": {
            "trace": {"n_requests": len(mixed), "long_len": long_len,
                      "prefill_chunk": 32},
            "chunked": chunk_rows["chunked"],
            "unchunked": chunk_rows["unchunked"],
            "token_identity": sum(
                1 for t in mixed
                if chunk_tokens["chunked"].get(t["rid"])
                == chunk_tokens["unchunked"].get(t["rid"])
                and chunk_tokens["unchunked"].get(t["rid"])
            ) / len(mixed),
        },
        "ttft_p99_ratio_chunked": ttft_ratio,
    })

    # ---------------- arm 3: mid-stream worker SIGKILL, zero lost
    import threading

    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    kill_trace = [
        dict(t, rid=t["rid"] + 300_000, max_new_tokens=32)
        for t in build_trace(
            n_requests=12, rate_hz=rate_hz, vocab=vocab,
            prompt_len_range=(2, 16), max_new_range=(24, 48),
            seed=seed + 1,
        )
    ]
    router_f, sup, handles = make_fleet_router(
        WorkerSpec(
            model={"vocab_size": vocab, "max_len": 128,
                   "hidden_dim": hidden, "depth": depth,
                   "num_heads": heads, "mlp_dim": mlp,
                   "pos_emb": "rope"},
            engine={"max_slots": max_slots, "max_len": 96,
                    "prompt_buckets": [16], "temperature": 0.0,
                    "decode_burst": decode_burst, "eos_id": None},
            max_queue=len(kill_trace), stream=True,
        ),
        procs,
        config=RouterConfig(streaming=True),
        sup_config=SupervisorConfig(restart_base_s=0.25),
    )
    fd3 = Frontdoor(router_f, config=FrontdoorConfig())
    fd3.start()
    kill_at_s = 0.75
    killer = threading.Timer(kill_at_s, sup.kill, (0, "SIGKILL"))
    try:
        killer.start()
        results, elapsed = _wire_replay(fd3.port, kill_trace)
    finally:
        killer.cancel()
        fd3.close()
        sup.stop()
    row_kill, _, kill_capture = _score_wire(
        kill_trace, results, elapsed)
    row_kill["mode"] = f"frontdoor fleet x{procs} + SIGKILL"
    capture.extend(kill_capture)
    report.update({
        "sigkill": {
            **row_kill,
            "kill_at_s": kill_at_s,
            "worker_restarts": list(sup.restarts),
            "check_stream": _sse_audit(kill_capture),
        },
        "sigkill_lost": row_kill["lost"],
    })

    # ---------------- arm 4: mixed greedy+sampled, zero new compiles
    ecfg_s = dataclasses.replace(ecfg, per_slot_sampling=True)
    router_s = make_router(model, params, 1, ecfg_s)
    router_s.warmup()
    # settle: one greedy + one sampled request so every program the
    # mixed traffic exercises is resident BEFORE the snapshot
    router_s.submit(Request(rid=400_000, prompt=[1, 2, 3],
                            max_new_tokens=4))
    router_s.submit(Request(rid=400_001, prompt=[4, 5, 6],
                            max_new_tokens=4, temperature=0.9,
                            top_k=8, top_p=0.9, seed=7))
    router_s.run_until_idle()
    before = router_s.compile_stats()

    def _count(stats) -> int:
        if isinstance(stats, dict):
            return sum(_count(v) for v in stats.values())
        return int(stats)

    churn = [
        dict(t, rid=t["rid"] + 410_000)
        for t in build_trace(
            n_requests=16, rate_hz=rate_hz, vocab=vocab,
            prompt_len_range=(2, 16), max_new_range=(4, 16),
            seed=seed + 2,
        )
    ]

    def sampling_fields(t):
        i = t["rid"] - 410_000
        if i % 2 == 0:
            return {}
        return {"temperature": 0.6 + 0.05 * (i % 5),
                "top_k": 8 if i % 4 == 1 else 0,
                "top_p": 0.9 if i % 4 == 3 else 0.0,
                "seed": i}

    fd4 = Frontdoor(router_s, config=FrontdoorConfig())
    fd4.start()
    try:
        results, elapsed = _wire_replay(
            fd4.port, churn, body_extra=sampling_fields)
    finally:
        fd4.close()
    row_mix, _, mix_capture = _score_wire(churn, results, elapsed)
    after = router_s.compile_stats()
    report.update({
        "sampling": {
            **row_mix,
            "mode": "per_slot_sampling mixed greedy+sampled",
            "compile_stats_before": before,
            "compile_stats_after": after,
            "check_stream": _sse_audit(mix_capture),
        },
        "sampling_new_compiles": _count(after) - _count(before),
    })

    if sse_out:
        with open(sse_out, "w") as f:
            for rec in capture:
                f.write(json.dumps(rec) + "\n")
        report["sse_out"] = sse_out
    return report


def _exemplar_resolution(sup, handles, tracer) -> dict:
    """Scrape each worker's /metrics and answer the acceptance
    question: does the TTFT p99 latency bucket carry an exemplar
    trace_id that resolves to a request present in the merged trace?
    (Plus counts over every bucket exemplar found — earlier buckets may
    legitimately hold exemplars from trace-plane-off reps.)"""
    import http.client
    import re

    ids_in_trace = set()
    for ev in tracer.to_chrome_trace()["traceEvents"]:
        args = ev.get("args") or {}
        if "trace_id" in args:
            ids_in_trace.add(args["trace_id"])
        if ev.get("id") is not None:
            ids_in_trace.add(ev["id"])
    found = []
    p99_rows = []
    for h in handles:
        w = sup.worker(h.id)
        if w is None:
            continue
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", w.telemetry_port, timeout=2.0
            )
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
            conn.close()
        except OSError:
            continue
        buckets = {}
        for m in re.finditer(
                r'serve_ttft_s_bucket\{le="([^"]+)"\} \d+'
                r'(?: # \{trace_id="([^"]+)"\} ([0-9.e+-]+))?', text):
            le = (float("inf") if m.group(1) == "+Inf"
                  else float(m.group(1)))
            buckets[le] = m.group(2)
            if m.group(2) is not None:
                found.append({"worker": h.id, "le": m.group(1),
                              "trace_id": m.group(2),
                              "resolves": m.group(2) in ids_in_trace})
        p99m = re.search(r'serve_ttft_s\{quantile="0\.99"\} ([0-9.e+-]+)',
                         text)
        if p99m is None or not buckets:
            continue
        p99 = float(p99m.group(1))
        le = min(b for b in buckets if b >= p99)
        tid = buckets[le]
        p99_rows.append({
            "worker": h.id, "p99": p99,
            "le": "+Inf" if le == float("inf") else le,
            "trace_id": tid,
            "resolves": tid is not None and tid in ids_in_trace,
        })
    return {
        "found": len(found),
        "resolved": sum(f["resolves"] for f in found),
        "p99_buckets": p99_rows,
        # ANY worker's p99 bucket naming a merged-trace request proves
        # the jump works; a worker whose p99 bucket was last touched by
        # a trace-plane-OFF rep legitimately points outside the
        # timeline (an always-on fleet has no such reps — the e2e test
        # pins the strict all-resolve case)
        "p99_resolves": any(r["resolves"] for r in p99_rows),
    }


def _tenant_rows_from(completions) -> dict:
    """Per-tenant latency/volume rows over one arm's completions.

    `window_tokens` counts only tokens delivered while load was still
    ARRIVING (finish <= the last arrival) — the contended window.
    These runs drain to idle, so TOTAL delivered tokens always equal
    the offered totals whatever the scheduler did; only the
    window-bounded count can show who actually got served during the
    fight, which is what Jain's index is judged over."""
    from ddp_practice_tpu.serve.fairshare import tenant_name

    by: dict = {}
    for c in completions:
        by.setdefault(tenant_name(getattr(c, "tenant", None)),
                      []).append(c)
    window_end = max((c.arrival for c in completions
                      if c.arrival is not None), default=None)
    out = {}
    for t, comps in sorted(by.items()):
        ok = [c for c in comps if c.status in ("eos", "length")]
        out[t] = {
            "completions": len(comps),
            "ok": len(ok),
            "output_tokens": sum(len(c.tokens) for c in ok),
            "window_tokens": sum(
                len(c.tokens) for c in ok
                if window_end is not None and c.finish is not None
                and c.finish <= window_end),
            "ttft_s": _percentiles(
                [c.ttft for c in ok if c.ttft is not None]),
            "latency_s": _percentiles(
                [c.finish - c.arrival for c in ok]),
        }
    return out


def qos_bench(
    *,
    rate_hz: float = 100.0,
    duration_s: float = 2.0,
    hostile_share: float = 4.0,
    procs: int = 2,
    max_slots: int = 2,
    vocab: int = 64,
    # heavier than the other serve benches on purpose: the arm is only
    # a fairness experiment if 100 req/s genuinely saturates the
    # fleet, so per-step cost is tuned to put capacity well BELOW the
    # hostile tenant's offered token rate
    hidden: int = 256,
    depth: int = 4,
    heads: int = 4,
    mlp: int = 512,
    decode_burst: int = 2,
    seed: int = 0,
    slo=None,
    workload=None,
    kill_at_s: float = 0.75,
    telemetry_out=None,
    trace_out=None,
) -> dict:
    """The multi-tenant QoS lab's bench: one adversarial workload plan
    (serve/workload.py — a hostile tenant offering `hostile_share`x
    the compliant tenant's rate) replayed through three arms, producing
    the BENCH_serve.json ``qos_mixed_tenants_100rps`` entry:

    - **FIFO** — RouterConfig(fair=False): the control. The hostile
      tenant's backlog head-of-line-blocks the compliant tenant.
    - **fair** — RouterConfig(fair=True): per-tenant weighted-fair
      queues (serve/fairshare.py VTC) + a TenantSLORegistry. Gates:
      ``isolation_ttft_p99_ratio`` (compliant tenant's TTFT p99,
      fair/FIFO — the contrast is the feature, acceptance <= 0.7),
      ``fairness_index`` (Jain over delivered tokens, >= 0.9),
      ``hostile_alert_tripped`` / ``compliant_clean`` (the per-tenant
      watchdogs attribute the burn to its cause — 0/1 contracts), and
      ``token_identity`` vs the FIFO arm (scheduling reorders WHO runs
      next, never WHAT a greedy request decodes — 1.0, tol 0) with
      ``lost`` == 0 across both arms.
    - **SIGKILL** — the same plan through a `procs`-worker FLEET
      (WorkerSpec(fair=True): each worker runs its own VTC + ledger)
      with a real mid-run SIGKILL + supervised restart. Gates:
      ``sigkill.lost`` == 0, ``sigkill.token_identity`` == 1.0
      (failover salvage keeps greedy identity), fairness/isolation
      claims re-judged by tools/check_qos.py over the leg's telemetry
      (``sigkill.check_qos_ok``) and the merged fleet timeline
      validated by tools/check_traces.py (``sigkill.trace_ok``).

    `telemetry_out` (a path PREFIX) writes one JSONL per arm —
    ``<prefix>.fifo.jsonl`` / ``.fair.jsonl`` / ``.sigkill.jsonl`` —
    each judgeable offline by tools/check_qos.py; `trace_out` saves
    the SIGKILL leg's merged fleet trace."""
    import threading

    from ddp_practice_tpu.serve.engine import EngineConfig
    from ddp_practice_tpu.serve.fairshare import (
        TenantLedger,
        VirtualTokenCounter,
        jains_index,
        tenant_name,
    )
    from ddp_practice_tpu.serve.router import RouterConfig, make_router
    from ddp_practice_tpu.serve.scheduler import MonotonicClock
    from ddp_practice_tpu.serve.slo import SLOConfig, TenantSLORegistry
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec
    from ddp_practice_tpu.serve.workload import TenantSpec, WorkloadPlan
    from ddp_practice_tpu.utils.telemetry import TelemetryExporter

    # short windows so a ~2 s run can trip/resolve; the production
    # defaults (60/300 s) are for fleets, not benches
    slo_cfg = SLOConfig.from_json(slo) if slo is not None else SLOConfig(
        ttft_p99_s=0.5, fast_window_s=0.5, slow_window_s=1.0,
        min_events=5,
    )
    if workload is not None:
        plan = WorkloadPlan.from_json(workload)
    else:
        compliant_rps = rate_hz / (1.0 + hostile_share)
        plan = WorkloadPlan([
            TenantSpec(name="bulk", rate_rps=rate_hz - compliant_rps,
                       arrivals="bursty", burst_every_s=1.0,
                       burst_len_s=0.4, burst_mult=2.0,
                       # long prompts + full budgets: the flood has to
                       # OUTRUN the fleet or there is no contention to
                       # be fair about
                       prompt_len_mean=32.0, prompt_len_cap=64,
                       max_new_mean=16.0, max_new_cap=16,
                       hostile=True),
            TenantSpec(name="acme", rate_rps=compliant_rps,
                       sessions=2, turns_per_session=3,
                       session_prefix_len=8, prompt_len_mean=4.0,
                       prompt_len_cap=8, max_new_mean=8.0,
                       max_new_cap=12),
        ], duration_s=duration_s)
    trace = plan.build(vocab=vocab, seed=seed)
    hostile = set(plan.hostile_tenants())
    compliant = sorted(
        {tenant_name(t["tenant"]) for t in trace}
        - {tenant_name(h) for h in hostile})
    model, params = _build_model(
        vocab=vocab, max_len=128, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
    )
    ecfg = EngineConfig(
        max_slots=max_slots, max_len=96, prompt_buckets=(16, 64),
        temperature=0.0, decode_burst=decode_burst, eos_id=None,
    )

    def _arm_out(tag):
        return (f"{telemetry_out}.{tag}.jsonl"
                if telemetry_out else None)

    def _judge(slo_reg, rows):
        """The isolation verdict off the live registry's alert log."""
        tripped = {t for _, edge, _, t in slo_reg.alert_log
                   if edge == "trip"}
        return {
            "alerts": [
                {"t": t, "event": edge, "objective": obj, "tenant": tn}
                for t, edge, obj, tn in slo_reg.alert_log
            ],
            "hostile_alert_tripped": float(bool(
                tripped & {tenant_name(h) for h in hostile})),
            "compliant_clean": float(
                not (tripped & set(compliant))),
            # judged over the CONTENDED window (_tenant_rows_from):
            # a drain-to-idle run delivers everyone's totals in the
            # end, so whole-run token counts cannot show starvation
            "fairness_index": jains_index(
                [rows[t]["window_tokens"] for t in sorted(rows)]),
        }

    def run_arm(fair: bool, tag: str) -> dict:
        from ddp_practice_tpu.utils.metrics import MetricsRegistry

        registry = MetricsRegistry()
        clock = MonotonicClock()
        exporter = None
        out_path = _arm_out(tag)
        if out_path:
            exporter = TelemetryExporter(out_path, registry=registry,
                                         clock=clock)
        slo_reg = TenantSLORegistry(slo_cfg, clock=clock,
                                    registry=registry,
                                    telemetry=exporter)
        vtc = VirtualTokenCounter() if fair else None
        ledger = TenantLedger(registry=registry, vtc=vtc)
        router = make_router(
            model, params, procs, ecfg, clock=clock,
            max_queue=len(trace), config=RouterConfig(fair=fair),
            registry=registry, slo=slo_reg, telemetry=exporter,
            vtc=vtc, ledger=ledger,
        )
        router.warmup()
        row = _replay_through_router(router, trace)
        rows = _tenant_rows_from(router.completions)
        row.update({
            "mode": f"{'fair' if fair else 'fifo'} x{procs}",
            "per_tenant": rows,
            "tenants": ledger.report(),
            **_judge(slo_reg, rows),
        })
        if exporter is not None:
            exporter.close()
            row["telemetry_out"] = out_path
        tokens = {c.rid: list(c.tokens) for c in router.completions
                  if c.status in ("eos", "length")}
        return row, tokens

    fifo_row, fifo_tokens = run_arm(False, "fifo")
    fair_row, fair_tokens = run_arm(True, "fair")
    matched = sum(1 for rid, toks in fifo_tokens.items()
                  if toks and fair_tokens.get(rid) == toks)
    comp = compliant[0] if compliant else None
    isolation = (
        fair_row["per_tenant"][comp]["ttft_s"]["p99"]
        / fifo_row["per_tenant"][comp]["ttft_s"]["p99"]
        if comp and fifo_row["per_tenant"].get(comp, {})
        .get("ttft_s", {}).get("p99") else None
    )
    report: dict = {
        "workload": json.loads(plan.to_json()),
        "slo": json.loads(slo_cfg.to_json()),
        "seed": seed,
        "hostile_tenants": sorted(hostile),
        "compliant_tenants": compliant,
        "fifo": fifo_row,
        "fair": fair_row,
        "isolation_ttft_p99_ratio": isolation,
        # the gated form: the raw ratio sits near 0.03x and jitters
        # run-to-run, so CI pins the verdict against the acceptance
        # bound, not the ratio (tools/check_bench.py DEFAULT_GATES)
        "isolation_ok": float(isolation is not None
                              and isolation <= 0.7),
        "token_identity": (matched / len(fifo_tokens)
                           if fifo_tokens else 0.0),
        "lost": fifo_row["lost"] + fair_row["lost"],
        "fairness_index": fair_row["fairness_index"],
        "hostile_alert_tripped": fair_row["hostile_alert_tripped"],
        "compliant_clean": fair_row["compliant_clean"],
    }

    # ------------- SIGKILL leg: fair FLEET + real mid-run worker death
    from ddp_practice_tpu.utils.metrics import MetricsRegistry

    # the chaos leg is judged against the FAILURE budget, not the
    # steady-state one: when the worker holding a tenant's flights is
    # SIGKILLed, those TTFTs ride out the restart no matter who the
    # scheduler favours, so the steady-state target would page every
    # tenant and the per-tenant attribution claim (hostile trips,
    # compliant doesn't) would be unfalsifiable. 5x the latency
    # targets is the single-worker-outage budget; the flooder's
    # backlog sails past it anyway.
    chaos_cfg = dataclasses.replace(
        slo_cfg,
        ttft_p99_s=(None if slo_cfg.ttft_p99_s is None
                    else slo_cfg.ttft_p99_s * 5),
        tpot_p99_s=(None if slo_cfg.tpot_p99_s is None
                    else slo_cfg.tpot_p99_s * 5),
    )
    registry = MetricsRegistry()
    clock = MonotonicClock()
    exporter = None
    kill_path = _arm_out("sigkill")
    if kill_path:
        exporter = TelemetryExporter(kill_path, registry=registry,
                                     clock=clock)
    slo_reg = TenantSLORegistry(chaos_cfg, clock=clock,
                                registry=registry, telemetry=exporter)
    ledger = TenantLedger(registry=registry)
    tracer = _make_tracer() if trace_out else None
    router_f, sup, handles = make_fleet_router(
        WorkerSpec(
            model={"vocab_size": vocab, "max_len": 128,
                   "hidden_dim": hidden, "depth": depth,
                   "num_heads": heads, "mlp_dim": mlp,
                   "pos_emb": "rope"},
            engine={"max_slots": max_slots, "max_len": 96,
                    "prompt_buckets": [16, 64], "temperature": 0.0,
                    "decode_burst": decode_burst, "eos_id": None},
            max_queue=len(trace), fair=True,
            trace=tracer is not None,
        ),
        procs,
        clock=clock,
        sup_config=SupervisorConfig(restart_base_s=0.25),
        registry=registry, tracer=tracer, slo=slo_reg,
        telemetry=exporter, ledger=ledger,
    )
    killer = threading.Timer(kill_at_s, sup.kill, (0, "SIGKILL"))
    try:
        killer.start()
        kill_row = _replay_through_router(router_f, trace, fleet=True)
    finally:
        killer.cancel()
        sup.stop()
    rows = _tenant_rows_from(router_f.completions)
    kill_tokens = {c.rid: list(c.tokens) for c in router_f.completions
                   if c.status in ("eos", "length")}
    kmatched = sum(1 for rid, toks in fifo_tokens.items()
                   if toks and kill_tokens.get(rid) == toks)
    kill_row.update({
        "mode": f"fair fleet x{procs} + SIGKILL",
        "kill_at_s": kill_at_s,
        "slo_chaos": json.loads(chaos_cfg.to_json()),
        "per_tenant": rows,
        "worker_restarts": list(sup.restarts),
        "token_identity": (kmatched / len(fifo_tokens)
                           if fifo_tokens else 0.0),
        **_judge(slo_reg, rows),
    })
    if exporter is not None:
        exporter.close()
        kill_row["telemetry_out"] = kill_path
        # the offline verdict over the leg's own telemetry: per-tenant
        # SLOs + fairness + hostile-trip attribution, same tool a CI
        # run applies to the checked-in artifact
        try:
            from tools.check_qos import qos_report
            from tools.check_slo import load_events

            records, _trunc = load_events(kill_path)
            qr = qos_report(
                records, chaos_cfg, hostile=sorted(hostile),
                min_fairness=0.5, expect_hostile_trip=True)
            kill_row["check_qos_ok"] = float(qr["ok"])
            kill_row["check_qos_problems"] = qr["problems"]
        except ImportError:
            kill_row["check_qos_ok"] = None
    if tracer is not None:
        tracer.save(trace_out)
        kill_row["trace_out"] = trace_out
        try:
            from tools.check_traces import validate_fleet

            with open(trace_out) as f:
                errs = validate_fleet(json.load(f))
            kill_row["trace_ok"] = float(not errs)
            kill_row["trace_errors"] = errs[:5]
        except ImportError:
            kill_row["trace_ok"] = None
    report["sigkill"] = kill_row
    report["sigkill_lost"] = kill_row["lost"]
    return report


def _run_static(model, params, trace, *, max_slots, width, max_new,
                eos_id) -> dict:
    """Static-batch baseline: fixed (max_slots, width) prompts, everyone
    decodes `max_new` tokens, arrivals wait for the whole batch. EOS
    only pads the tail — the fixed-length scan runs to max_new
    regardless, which is exactly the decode compute continuous batching
    reclaims."""
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.inference import make_generate_fn

    gen = jax.jit(make_generate_fn(
        model, max_new_tokens=max_new, temperature=0.0, eos_id=eos_id,
        pad_id=-1,  # distinguishable from real tokens when counting
    ))

    def run_batch(batch):
        toks = np.full((max_slots, width), 0, np.int32)
        lens = np.ones((max_slots,), np.int32)
        for j, t in enumerate(batch):
            p = t["prompt"]
            toks[j, width - len(p):] = p
            lens[j] = len(p)
        out = np.asarray(gen(
            params, jnp.asarray(toks), None, jnp.asarray(lens)
        ))
        return out[:, width:]

    run_batch(trace[:1])  # warmup compile outside the window

    t0 = time.monotonic()
    i = 0
    done = []
    while i < len(trace):
        now = time.monotonic() - t0
        if trace[i]["arrival"] > now:
            time.sleep(trace[i]["arrival"] - now)
            continue
        batch = []
        while i < len(trace) and len(batch) < max_slots \
                and trace[i]["arrival"] <= time.monotonic() - t0:
            batch.append(trace[i])
            i += 1
        new = run_batch(batch)
        finish = time.monotonic() - t0
        for j, t in enumerate(batch):
            # useful tokens: up to this request's OWN budget, cut at its
            # EOS (post-EOS slots hold the pad sentinel) — the same
            # accounting the continuous server's release logic applies
            row = new[j, : t["max_new_tokens"]]
            done.append({
                "rid": t["rid"],
                "tokens": int((row != -1).sum()),
                "latency": finish - t["arrival"],
            })
    elapsed = time.monotonic() - t0
    tokens = sum(d["tokens"] for d in done)
    lat = [d["latency"] for d in done]
    return {
        "mode": "static",
        "elapsed_s": elapsed,
        "useful_tokens": tokens,
        "tokens_per_sec": tokens / elapsed,
        # every token arrives when the batch returns: TTFT == latency
        "ttft_s": _percentiles(lat),
        "latency_s": _percentiles(lat),
        "completions": len(done),
    }


def shared_prefix_bench(
    *,
    n_requests: int = 32,
    # effectively-instant arrivals: the tiny CPU bench model drains 100
    # real rps without queueing, and an arrival-bound run measures the
    # Poisson clock, not the pool — saturate so the ratio is the
    # engines' goodput at full block pressure
    rate_hz: float = 1000.0,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(16, 128),
    # the workload: K fixed system prompts (block-aligned so the radix
    # tree caches exactly the prefix) x short unique tails — prefixes
    # deliberately DOMINATE each prompt (96 of ~100 tokens), the
    # production shape ROADMAP item 2 names
    k_prefixes: int = 2,
    prefix_len: int = 96,
    tail_range=(1, 8),
    max_new_range=(4, 8),
    decode_burst: int = 4,
    block_size: int = 16,
    # UNDERSIZED pool (19 real blocks ~ 2 plain worst-case contexts for
    # 8 slots): block pressure is what prefix sharing + preemption
    # relieve, so the pool must actually be contended — the plain row
    # runs ~2 contexts at a time while the prefix row's slots share the
    # two 6-block prefixes and fit ~7
    num_blocks: int = 20,
    seed: int = 0,
    kv_int8: bool = False,
) -> dict:
    """Replay ONE shared-prefix Poisson trace through the plain paged
    engine and the prefix-sharing engine at the SAME pool size.

    The report's `prefix_vs_paged` goodput ratio is the PR-6 acceptance
    number (>= 1.5x target): the prefix engine pays prefill only for
    each request's tail and shares the K prefixes' blocks refcounted,
    so the same 24 blocks hold ~2x the concurrent contexts. Hit/miss
    token counters prove the reuse. `kv_int8=True` additionally stores
    the pool int8 with per-block scale pages (halved KV bytes/token —
    reported against the same model's fp32 pool)."""
    model, params = _build_model(
        vocab=vocab, max_len=max_len, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
        kv_cache_dtype="int8" if kv_int8 else None,
    )
    trace = build_shared_prefix_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        k_prefixes=k_prefixes, prefix_len=prefix_len,
        tail_range=tail_range, max_new_range=max_new_range, seed=seed,
    )
    common = dict(
        max_slots=max_slots, prompt_buckets=tuple(prompt_buckets),
        max_len=max_len, decode_burst=decode_burst, eos_id=None,
        paged=True, block_size=block_size, num_blocks=num_blocks,
    )
    plain = _run_continuous(model, params, trace, **common)
    prefix = _run_continuous(model, params, trace, prefix_cache=True,
                             **common)
    report = {
        "trace": {
            "n_requests": n_requests, "rate_hz": rate_hz, "seed": seed,
            "k_prefixes": k_prefixes, "prefix_len": prefix_len,
            "tail_range": list(tail_range),
            "max_new_range": list(max_new_range),
        },
        "pool": {
            "num_blocks": num_blocks, "block_size": block_size,
            "max_slots": max_slots,
            "kv_cache_dtype": "int8" if kv_int8 else "f32",
        },
        "paged": plain,
        "paged_prefix": prefix,
        "prefix_vs_paged": (
            prefix["tokens_per_sec"] / plain["tokens_per_sec"]
            if plain["tokens_per_sec"] else float("inf")
        ),
    }
    if kv_int8:
        # bytes/token against the SAME architecture's fp32 pool — the
        # halved-KV acceptance number (shapes only, no fp32 arrays)
        import jax

        f32_model, _ = _build_model(
            vocab=vocab, max_len=max_len, hidden=hidden, depth=depth,
            heads=heads, mlp=mlp,
        )
        from ddp_practice_tpu.serve.kv_pages import make_paged_cache

        f32_cache = jax.eval_shape(
            lambda: make_paged_cache(f32_model, num_blocks, block_size)
        )
        f32_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(f32_cache) if leaf.ndim
        ) / (num_blocks * block_size)
        report["kv_bytes_per_token_f32"] = f32_bytes
        report["kv_bytes_ratio"] = (
            prefix["kv_bytes_per_token"] / f32_bytes
        )
    return report


def spec_decode_bench(
    *,
    n_requests: int = 32,
    rate_hz: float = 8.0,
    max_slots: int = 4,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    max_len: int = 128,
    prompt_buckets=(16,),
    # the workload: repeated-motif prompts (build_lookup_trace) — the
    # self-quoting traffic shape where prompt-lookup drafts actually hit
    motif_range=(2, 4),
    prompt_len_range=(6, 16),
    max_new_range=(8, 24),
    # burst=1 for BOTH arms: the honest comparison pins tokens-per-
    # dispatch at 1 on the plain side, so the ratio isolates exactly
    # what speculation changes — the number of sequential dispatches
    # per emitted token. (At burst=B the plain arm lands B tokens per
    # dispatch and the comparison conflates bursting with drafting.)
    decode_burst: int = 1,
    block_size: int = 16,
    spec_k: int = 4,
    seed: int = 0,
) -> dict:
    """Replay ONE lookup-friendly Poisson trace through the plain paged
    engine and the spec-decoding paged engine at the same pool.

    The report's `tpot_ratio` (spec p50 / plain p50, < 1.0 target) is
    the ISSUE-13 acceptance number: a verified run lands k+1 tokens in
    one dispatch, so inter-token pacing drops wherever drafts hit.
    `token_identity` (fraction of requests with bit-identical streams,
    target 1.0) is the exactness half of the claim — speculation is a
    latency lever, never a quality knob. `accept_rate` explains WHY the
    ratio moved (no accepts = no speedup, by construction)."""
    model, params = _build_model(
        vocab=vocab, max_len=max_len, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
    )
    trace = build_lookup_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        motif_range=motif_range, prompt_len_range=prompt_len_range,
        max_new_range=max_new_range, seed=seed,
    )
    common = dict(
        max_slots=max_slots, prompt_buckets=tuple(prompt_buckets),
        max_len=max_len, decode_burst=decode_burst, eos_id=None,
        paged=True, block_size=block_size, collect_tokens=True,
    )
    plain = _run_continuous(model, params, trace, **common)
    spec = _run_continuous(model, params, trace, spec_decode=True,
                           spec_k=spec_k, **common)
    plain_toks = plain.pop("tokens_by_rid")
    spec_toks = spec.pop("tokens_by_rid")
    identical = sum(
        1 for rid in plain_toks if spec_toks.get(rid) == plain_toks[rid]
    )
    return {
        "trace": {
            "n_requests": n_requests, "rate_hz": rate_hz, "seed": seed,
            "motif_range": list(motif_range),
            "prompt_len_range": list(prompt_len_range),
            "max_new_range": list(max_new_range),
        },
        "spec_k": spec_k,
        "paged": plain,
        "paged_spec": spec,
        "token_identity": identical / max(1, len(plain_toks)),
        "tpot_ratio": (
            spec["tpot_s"]["p50"] / plain["tpot_s"]["p50"]
            if plain["tpot_s"]["p50"] else float("inf")
        ),
        "latency_ratio_p50": (
            spec["latency_s"]["p50"] / plain["latency_s"]["p50"]
            if plain["latency_s"]["p50"] else float("inf")
        ),
        "accept_rate": spec["spec"]["accept_rate"],
    }


def serve_bench(
    *,
    n_requests: int = 32,
    rate_hz: float = 8.0,
    max_slots: int = 8,
    vocab: int = 64,
    hidden: int = 128,
    depth: int = 2,
    heads: int = 4,
    mlp: int = 256,
    # sized to the trace: the decode-attention span is the whole pool
    # every step (the shared-cursor design reads [0, max_len) masked), so
    # an oversized pool taxes ONLY the continuous server — 128 fits the
    # 96-token cap plus the 16-wide prompt base with room to spare
    max_len: int = 128,
    prompt_buckets=(8, 16),
    prompt_len_range=(2, 16),
    # wide budget spread: the static baseline pays max_new for everyone,
    # the continuous engine pays what each request asked (+burst round-up)
    max_new_range=(2, 96),
    decode_burst: int = 8,
    # the trace's end-of-sequence token: with the default params seed,
    # greedy decode emits 46 early in roughly half the streams and never
    # in the rest — a realistic early-stop mix. The continuous server
    # reclaims the slot at EOS; the static scan runs to max_new
    # regardless. None = no EOS in the trace.
    eos_id: Optional[int] = 46,
    seed: int = 0,
    # fleet path: 0 = skip the router bench; N >= 1 runs the SAME trace
    # through N replicas behind serve/router.py (replicas=1 measures the
    # router's overhead against the direct continuous path)
    replicas: int = 0,
    fault_plan=None,
    # also run the trace through the paged-KV engine (serve/kv_pages.py)
    # — the span-decoupling measurement: the slot engine's decode
    # attention scans [0, max_len) every step, the paged engine only
    # each request's own pages, so growing max_len taxes the slot row
    # and leaves the paged row flat (BENCHMARKS.md)
    paged: bool = False,
    block_size: int = 16,
    # Chrome trace-event JSON output (utils/trace.py): the recorder
    # rides the ROUTER run when replicas >= 1, else the continuous run
    # (warmup spans excluded either way). Validate/eyeball with
    # tools/check_traces.py; None = tracing fully off.
    trace_out: Optional[str] = None,
    # ---- live telemetry plane (utils/telemetry.py): all default-off.
    # telemetry_out streams kind-tagged JSONL (trace events via the
    # recorder sink, flight records, metrics snapshots) DURING the run;
    # metrics_port binds the /metrics /healthz /flight scrape server
    # (0 = ephemeral); scrape_hz self-scrapes all three endpoints from a
    # background thread — the overhead-measurement methodology, so the
    # "plane on" bench row pays for serving real scrapes, not an idle
    # listener. slo (SLOConfig/JSON/path) arms the burn-rate watchdog
    # on the router run (needs replicas >= 1).
    telemetry_out: Optional[str] = None,
    metrics_port: Optional[int] = None,
    scrape_hz: float = 0.0,
    slo=None,
    alert_sinks=None,
) -> dict:
    """Replay one Poisson trace through both servers; return the report."""
    model, params = _build_model(
        vocab=vocab, max_len=max_len, hidden=hidden, depth=depth,
        heads=heads, mlp=mlp,
    )
    trace = build_trace(
        n_requests=n_requests, rate_hz=rate_hz, vocab=vocab,
        prompt_len_range=prompt_len_range, max_new_range=max_new_range,
        seed=seed,
    )
    # a recorder exists for EITHER output: --trace-out wants the exit
    # dump, --telemetry-out wants the live stream (the sink) — each is
    # self-sufficient
    tracer = _make_tracer() if (trace_out or telemetry_out) else None

    slo_config = None
    if slo is not None:
        from ddp_practice_tpu.serve.slo import SLOConfig

        if replicas < 1:
            raise ValueError("--slo needs --replicas N (the watchdog "
                             "feeds the router's brown-out hook)")
        slo_config = SLOConfig.from_json(slo)
    plane_on = telemetry_out is not None or metrics_port is not None
    registry = exporter = server = scraper = None
    health_slot = {"fn": None}
    if plane_on or slo_config is not None:
        from ddp_practice_tpu.utils.metrics import MetricsRegistry

        registry = MetricsRegistry()
    try:
        if telemetry_out is not None:
            from ddp_practice_tpu.utils.telemetry import TelemetryExporter

            # NOT attached to the tracer yet: the runs attach the sink
            # only after their warmup + tracer.clear(), so compile-time
            # spans stay out of the stream exactly as they stay out of
            # the trace_out dump
            exporter = TelemetryExporter(telemetry_out, registry=registry)
        if metrics_port is not None:
            from ddp_practice_tpu.utils.telemetry import (
                FlightStats,
                TelemetryServer,
            )

            flight = exporter.flight if exporter else FlightStats()
            server = TelemetryServer(
                registry=registry,
                health_fn=lambda: (health_slot["fn"]()
                                   if health_slot["fn"] else {}),
                flight_fn=flight.report,
                port=metrics_port,
            )
            if exporter is None:
                # no JSONL stream, but /flight still needs feeding
                exporter_or_flight = flight
            else:
                exporter_or_flight = exporter
        else:
            exporter_or_flight = exporter
        if server is not None and scrape_hz > 0:
            scraper = _Scraper(server.port, hz=scrape_hz)
    except BaseException:
        # half-built plane (e.g. the port is taken): drain and close
        # what already started before surfacing the error
        if server is not None:
            server.close()
        if exporter is not None:
            exporter.close()
        raise

    try:
        cont = _run_continuous(
            model, params, trace, max_slots=max_slots,
            prompt_buckets=tuple(prompt_buckets), max_len=max_len,
            decode_burst=decode_burst, eos_id=eos_id,
            tracer=None if replicas >= 1 else tracer,
            telemetry=None if replicas >= 1 else exporter_or_flight,
            health_slot=None if replicas >= 1 else health_slot,
        )
        static = _run_static(
            model, params, trace, max_slots=max_slots,
            width=max(prompt_buckets), max_new=max(max_new_range),
            eos_id=eos_id,
        )
        report = {
            "trace": {
                "n_requests": n_requests, "rate_hz": rate_hz, "seed": seed,
                "prompt_len_range": list(prompt_len_range),
                "max_new_range": list(max_new_range),
            },
            "max_len": max_len,
            "continuous": cont,
            "static": static,
            "throughput_ratio": (
                cont["tokens_per_sec"] / static["tokens_per_sec"]
                if static["tokens_per_sec"] else float("inf")
            ),
        }
        if paged:
            report["paged"] = _run_continuous(
                model, params, trace, max_slots=max_slots,
                prompt_buckets=tuple(prompt_buckets), max_len=max_len,
                decode_burst=decode_burst, eos_id=eos_id,
                paged=True, block_size=block_size,
            )
            report["paged_vs_static"] = (
                report["paged"]["tokens_per_sec"] / static["tokens_per_sec"]
                if static["tokens_per_sec"] else float("inf")
            )
            report["paged_vs_continuous"] = (
                report["paged"]["tokens_per_sec"] / cont["tokens_per_sec"]
                if cont["tokens_per_sec"] else float("inf")
            )
        if replicas >= 1:
            report["router"] = _run_router(
                model, params, trace, replicas=replicas,
                max_slots=max_slots,
                prompt_buckets=tuple(prompt_buckets), max_len=max_len,
                decode_burst=decode_burst, eos_id=eos_id,
                fault_plan=fault_plan, tracer=tracer,
                slo_config=slo_config, telemetry=exporter_or_flight,
                exporter=exporter, registry=registry,
                health_slot=health_slot, alert_sinks=alert_sinks,
            )
            if fault_plan is not None:
                report["fault_plan"] = fault_plan.to_json()
            report["router_vs_continuous"] = (
                report["router"]["tokens_per_sec"] / cont["tokens_per_sec"]
                if cont["tokens_per_sec"] else float("inf")
            )
        if tracer is not None and trace_out:
            tracer.save(trace_out)
            report["trace_out"] = trace_out
            report["trace_events"] = len(tracer)
    finally:
        # the plane outlives a crashed run only as a closed, drained
        # file — that is the flush-on-crash contract
        if scraper is not None:
            scraper.stop()
        if server is not None:
            server.close()
        if exporter is not None:
            exporter.close()
    if plane_on:
        report["telemetry"] = {
            "telemetry_out": telemetry_out,
            "metrics_port": server.port if server is not None else None,
            "scrapes": scraper.count if scraper is not None else 0,
            "dropped": exporter.dropped if exporter is not None else 0,
        }
    return report


# --------------------------------------------------------------------- CLI
def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "ddp_practice_tpu serve",
        description="continuous-batching serving: bench a synthetic "
                    "Poisson trace (default) or serve prompts from a "
                    "trained RoPE LM checkpoint",
    )
    p.add_argument("--ckpt_dir", default=None,
                   help="serve these --prompt strings from a checkpoint "
                        "instead of running the bench (needs a "
                        "pos_emb=rope LM checkpoint)")
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
                        "overhead; releases are burst-granular; default: "
                        "8 for the bench, 1 for checkpoint serving)")
    p.add_argument("--requests", type=int, default=32,
                   help="bench: trace length")
    p.add_argument("--rate", type=float, default=8.0,
                   help="bench: Poisson arrival rate (req/s)")
    p.add_argument("--replicas", type=int, default=0,
                   help="bench: also run the trace through N engine "
                        "replicas behind the fault-tolerant router "
                        "(serve/router.py; 0 = skip)")
    p.add_argument("--procs", type=int, default=0,
                   help="bench: run the trace through N worker OS "
                        "PROCESSES behind the RPC seam AND through N "
                        "in-process router replicas — reports the "
                        "seam's latency/goodput overhead "
                        "(serve/worker.py + serve/supervisor.py; "
                        "--fault-plan kill specs deliver real "
                        "SIGKILL/SIGSTOP to live workers)")
    p.add_argument("--fault-plan", dest="fault_plan", default=None,
                   metavar="JSON",
                   help="bench: inject a serve/faults.py FaultPlan into "
                        "the router run — a JSON string or a path to a "
                        "JSON file; the router row then reports GOODPUT "
                        "under those faults (requires --replicas)")
    p.add_argument("--paged", action="store_true",
                   help="bench: also run the trace through the paged-KV "
                        "engine (serve/kv_pages.py) — adds a 'paged' row; "
                        "compare against 'continuous' at large --max-len "
                        "to see the span decoupling")
    p.add_argument("--block-size", dest="block_size", type=int, default=16,
                   help="paged engine: positions per KV block")
    p.add_argument("--shared-prefix", dest="shared_prefix",
                   action="store_true",
                   help="bench: replay a deterministic K-system-prompts x"
                        " continuations trace through the plain paged "
                        "engine AND the prefix-sharing engine at the "
                        "same (undersized) pool — reports the goodput "
                        "ratio plus prefix-cache hit/miss token "
                        "counters (serve/kv_pages.py RadixPrefixCache)")
    p.add_argument("--kv-int8", dest="kv_int8", action="store_true",
                   help="with --shared-prefix: store the paged pool "
                        "int8 with per-block scale pages — halves KV "
                        "bytes/token (reported vs the fp32 pool)")
    p.add_argument("--spec-decode", dest="spec_decode",
                   action="store_true",
                   help="bench: replay ONE lookup-friendly trace "
                        "(repeated-motif prompts) through the plain "
                        "paged engine AND the speculative-decoding "
                        "engine (serve/spec.py prompt-lookup drafts + "
                        "jitted k-token verify) — reports tpot_ratio, "
                        "accept_rate, and token_identity (greedy "
                        "streams must be bit-identical across arms)")
    p.add_argument("--spec-k", dest="spec_k", type=int, default=4,
                   help="with --spec-decode: drafted tokens per verify "
                        "window")
    p.add_argument("--trace-out", "--trace_out", dest="trace_out",
                   default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of the request "
                        "lifecycle (queued/prefill/decode-burst spans, "
                        "retry/failover instants; pid=replica, tid=slot) "
                        "— the router run when --replicas, else the "
                        "continuous run; open in Perfetto, validate with "
                        "tools/check_traces.py")
    p.add_argument("--telemetry-out", "--telemetry_out",
                   dest="telemetry_out", default=None, metavar="PATH",
                   help="stream the run's telemetry as line-delimited "
                        "JSONL WHILE it runs (trace events, flight "
                        "records, periodic metrics snapshots — "
                        "utils/telemetry.py): a killed run still leaves "
                        "a parseable file; validate with "
                        "tools/check_traces.py, judge with "
                        "tools/check_slo.py")
    p.add_argument("--metrics-port", "--metrics_port",
                   dest="metrics_port", type=int, default=None,
                   metavar="PORT",
                   help="serve /metrics (Prometheus exposition), "
                        "/healthz (per-replica health), /flight "
                        "(rolling phase percentiles) on this port "
                        "during the bench (0 = ephemeral; the report "
                        "records the bound port)")
    p.add_argument("--scrape-hz", dest="scrape_hz", type=float,
                   default=0.0,
                   help="self-scrape the endpoints at this rate during "
                        "the run (overhead-measurement methodology; "
                        "needs --metrics-port)")
    p.add_argument("--slo", default=None, metavar="JSON|PATH",
                   help="SLO config (serve/slo.py SLOConfig: ttft_p99_s/"
                        "tpot_p99_s/error_rate/availability + windows) — "
                        "arms the burn-rate watchdog on the router run; "
                        "alerts land in the trace/telemetry stream and "
                        "can trip the router's brown-out (requires "
                        "--replicas)")
    p.add_argument("--alert-sink", "--alert_sink", dest="alert_sink",
                   action="append", default=None, metavar="KIND:TARGET",
                   help="repeatable; PUSH SLO alert edges to an operator "
                        "sink — command:..., webhook:http://..., "
                        "jsonl:path (serve/slo.py AlertSinks: per-sink "
                        "retry backoff, dead-sink breaker); needs --slo")
    p.add_argument("--streaming", action="store_true",
                   help="with --procs: bench STREAMING token delivery "
                        "(per-burst TokenChunks over the push stream, "
                        "router TokenStreams). Without --fault-plan: "
                        "A/B vs end-of-request delivery over order-"
                        "balanced reps (gate: mean latency <= 1.05x). "
                        "With a kill --fault-plan: one chaos rep, real "
                        "signals mid-stream, consumer-side exactly-once "
                        "ledger (dupes/gaps gated 0, inter-token p99, "
                        "resume-gap p99) + tools/check_stream.py audit "
                        "of the telemetry JSONL")
    p.add_argument("--trace-overhead", dest="trace_overhead",
                   action="store_true",
                   help="with --procs: measure the fleet trace plane's "
                        "on/off overhead (worker span recording + push "
                        "streaming + router-side collection) over "
                        "order-balanced alternating reps against ONE "
                        "warm fleet; reports the latency ratios the "
                        "<=2%% acceptance gate judges, saves the merged "
                        "ON-rep timeline to --trace-out, and checks "
                        "/metrics bucket exemplars resolve into it")
    p.add_argument("--trace-sampling", dest="trace_sampling",
                   action="store_true",
                   help="with --procs: bench the HEAD-SAMPLED trace "
                        "plane (utils/trace.py TraceSampler) at the "
                        "--rate operating point — three arms (sampled/"
                        "full/off) rotated against ONE warm fleet; "
                        "reports span_reduction (gate >= 0.95 at 1%%) "
                        "and mean latency vs off (gate <= 1.02x); "
                        "saves the final sampled timeline to "
                        "--trace-out / --otlp-out")
    p.add_argument("--trace-sample", dest="trace_sample", type=float,
                   default=None, metavar="RATE",
                   help="head-sampling rate in [0,1]: one deterministic "
                        "keep/stage decision per trace_id (crc32 hash — "
                        "every process agrees), staged spans promoted "
                        "by the tail keep-rules (errors, sheds, "
                        "retries, failovers, resumes, preemptions, "
                        "--trace-keep-slow-s). Default: no sampling "
                        "(rate 1.0); the sampling bench defaults 0.01")
    p.add_argument("--trace-keep-slow-s", dest="trace_keep_slow_s",
                   type=float, default=None, metavar="S",
                   help="tail keep-rule: a request slower than this "
                        "end-to-end is kept regardless of the head "
                        "decision (set from the SLO: ~2x the latency "
                        "p99 target)")
    p.add_argument("--otlp-out", "--otlp_out", dest="otlp_out",
                   default=None, metavar="PATH",
                   help="write the run's request spans as OTLP-JSON "
                        "(ExportTraceServiceRequest shape — POST-able "
                        "to any OTLP/HTTP collector's /v1/traces); "
                        "validate with tools/check_otlp.py")
    p.add_argument("--otlp-endpoint", "--otlp_endpoint",
                   dest="otlp_endpoint", default=None, metavar="URL",
                   help="push kept spans LIVE to this OTLP/HTTP "
                        "collector (.../v1/traces) from a background "
                        "batcher (utils/telemetry.py OtlpPusher: "
                        "bounded queue, retry backoff, dead-endpoint "
                        "breaker; at-least-once with ddp.push.batch_id "
                        "for collector-side dedup). With "
                        "--otlp-push-overhead and no endpoint, a stub "
                        "collector is stood up automatically")
    p.add_argument("--otlp-push-overhead", dest="otlp_push_overhead",
                   action="store_true",
                   help="with --procs: A/B the LIVE push pipeline "
                        "against file-only export over order-balanced "
                        "rounds on ONE warm fleet (gate: mean latency "
                        "<= 1.02x) and audit capture completeness "
                        "against the batch-id-deduped collector")
    p.add_argument("--adaptive-sampling", dest="adaptive_sampling",
                   action="store_true",
                   help="with --procs: drive a 4x arrival step through "
                        "one warm fleet with the adaptive head-rate "
                        "controller active (utils/trace.py "
                        "AdaptiveHeadRateController) and report "
                        "kept-spans/s vs --trace-budget-sps (gate: "
                        "within ±20%% after the step, no thrash)")
    p.add_argument("--trace-budget-sps", dest="trace_budget_sps",
                   type=float, default=None, metavar="SPS",
                   help="kept-spans-per-second budget the adaptive "
                        "controller steers the fleet head rate toward "
                        "(multiplicative correction, deadband + hold "
                        "window; every change stamped as a trace_rate "
                        "instant and pushed live over the rpc trace op)")
    p.add_argument("--trace-tenant-rates", "--trace_tenant_rates",
                   dest="trace_tenant_rates", default=None,
                   metavar="JSON",
                   help="per-tenant head-rate overrides as a JSON "
                        'object, e.g. \'{"acme": 1.0, "free-tier": '
                        "0.01}' — tenants not listed use the fleet "
                        "rate; tail keep-rules stay tenant-blind, so "
                        "fault-affected requests are kept for EVERY "
                        "tenant")
    p.add_argument("--cache-aware", dest="cache_aware",
                   action="store_true",
                   help="with --procs: A/B cache-aware (prefix-"
                        "affinity) routing against least-loaded over "
                        "one shared-prefix trace through two identical "
                        "paged+prefix-cache worker fleets at the same "
                        "pool (serve/affinity.py) — reports the fleet "
                        "prefix-hit-token rate and goodput ratios, "
                        "zero-lost, and greedy token identity")
    p.add_argument("--frontdoor", action="store_true",
                   help="bench the HTTP/SSE front door end-to-end "
                        "through REAL client sockets "
                        "(serve/frontdoor.py): wire-vs-in-process "
                        "goodput + greedy token identity, chunked-"
                        "prefill short-request TTFT p99 ratio, "
                        "mid-stream worker SIGKILL with zero lost "
                        "streams (--procs workers), and mixed greedy+"
                        "sampled churn with zero new compiles — the "
                        "BENCH_serve.json frontdoor_100rps entry")
    p.add_argument("--sse-out", dest="sse_out", default=None,
                   metavar="PATH",
                   help="with --frontdoor: dump the wire-side SSE "
                        "frame capture as JSONL — audit with "
                        "tools/check_stream.py --sse")
    p.add_argument("--qos", action="store_true",
                   help="the multi-tenant QoS lab "
                        "(serve/workload.py): one adversarial plan "
                        "(hostile tenant at 4x the compliant share) "
                        "through FIFO and weighted-fair arms plus a "
                        "fair FLEET leg under a real SIGKILL — "
                        "reports the compliant tenant's TTFT-p99 "
                        "isolation ratio, Jain's fairness index, "
                        "per-tenant alert attribution, greedy token "
                        "identity and zero-lost; the "
                        "BENCH_serve.json qos_mixed_tenants_100rps "
                        "entry. --workload/--slo override the plan "
                        "and targets; --telemetry-out (prefix) "
                        "writes per-arm JSONLs for tools/"
                        "check_qos.py; --trace-out saves the kill "
                        "leg's fleet timeline")
    p.add_argument("--workload", default=None, metavar="JSON|PATH",
                   help="with --qos: a serve/workload.py WorkloadPlan "
                        "(JSON literal or path) replacing the default "
                        "hostile+compliant plan")
    p.add_argument("--qos-duration", dest="qos_duration", type=float,
                   default=2.0,
                   help="with --qos: plan duration in seconds "
                        "(arrival window; the run drains past it)")
    p.add_argument("--autoscale", action="store_true",
                   help="with --procs: A/B an ELASTIC fleet against the "
                        "fixed --procs fleet under a 4x arrival step "
                        "(serve/autoscaler.py: SLO-burn/queue-pressure "
                        "policy, pre-warmed standby promotion, graceful "
                        "drain scale-down) — gates goodput per "
                        "worker-second at equal SLO, reaction within "
                        "one evaluation window, zero lost, no thrash")
    p.add_argument("--autoscale-max", dest="autoscale_max", type=int,
                   default=3,
                   help="with --autoscale: elastic fleet size ceiling "
                        "(floor is 1)")
    p.add_argument("--standby", type=int, default=1,
                   help="with --autoscale: pre-warmed standby workers "
                        "kept ready to promote (pool replenishes in "
                        "the background after each promotion)")
    p.add_argument("--max-len", dest="max_len", type=int, default=None,
                   help="bench: slot-pool span / paged pool sizing "
                        "(default 128); the slot engine's decode cost "
                        "scales with this, the paged engine's does not")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", action="store_true")
    return p


def _serve_checkpoint(args) -> int:
    import jax.numpy as jnp

    from ddp_practice_tpu.generate import load_lm
    from ddp_practice_tpu.inference import decode_bytes, encode_bytes
    from ddp_practice_tpu.serve.engine import EngineConfig, SlotEngine
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler

    model, params, batch_stats, step = load_lm(args.ckpt_dir)
    prompts = args.prompt or ["\n"]
    max_prompt = max(len(p.encode("utf-8")) for p in prompts)
    bucket = 8
    while bucket < max_prompt:
        bucket *= 2
    engine = SlotEngine(
        model, params,
        EngineConfig(
            max_slots=args.max_slots,
            prompt_buckets=(bucket,),
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id,
            decode_burst=args.decode_burst or 1,
        ),
        batch_stats=batch_stats,
    )
    tracer = None
    if args.trace_out:
        from ddp_practice_tpu.utils.trace import label_replica

        tracer = _make_tracer()
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


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax

    from ddp_practice_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    # every serving number names the device it came from; stderr under
    # --json so stdout stays one parseable document
    print(f"[serve] platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={jax.device_count()}",
          file=sys.stderr if args.json else sys.stdout)
    if args.ckpt_dir:
        return _serve_checkpoint(args)
    if args.kv_int8 and not args.shared_prefix:
        raise SystemExit("--kv-int8 rides the --shared-prefix bench")
    if args.shared_prefix:
        report = shared_prefix_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, block_size=args.block_size,
            seed=args.seed, kv_int8=args.kv_int8,
        )
        if args.json:
            print(json.dumps(report))
        else:
            pl, pf = report["paged"], report["paged_prefix"]
            pc = pf["prefix_cache"]
            print(f"[shared_prefix_bench] {args.requests} requests @ "
                  f"{args.rate}/s, pool {report['pool']['num_blocks']} "
                  f"blocks x {report['pool']['block_size']} "
                  f"({report['pool']['kv_cache_dtype']})")
            for r in (pl, pf):
                print(f"  {r['mode']:>12}: {r['tokens_per_sec']:8.1f} "
                      f"tok/s  ttft p50 {r['ttft_s']['p50'] * 1e3:7.1f} "
                      f"ms  p99 {r['ttft_s']['p99'] * 1e3:7.1f} ms  "
                      f"preemptions {r['preemptions']}")
            print(f"  prefix/paged goodput: "
                  f"{report['prefix_vs_paged']:.2f}x  "
                  f"hit/miss tokens {pc['hit_tokens']}/"
                  f"{pc['miss_tokens']} "
                  f"(hit rate {pc['hit_rate']:.2f})")
            if args.kv_int8:
                print(f"  kv bytes/token: int8 "
                      f"{pf['kv_bytes_per_token']:.0f} vs f32 "
                      f"{report['kv_bytes_per_token_f32']:.0f} "
                      f"({report['kv_bytes_ratio']:.2f}x)")
        return 0
    if args.spec_decode:
        report = spec_decode_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, block_size=args.block_size,
            spec_k=args.spec_k, seed=args.seed,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            pl, sp = report["paged"], report["paged_spec"]
            print(f"[spec_decode_bench] {args.requests} requests @ "
                  f"{args.rate}/s, spec_k {report['spec_k']}")
            for r in (pl, sp):
                print(f"  {r['mode']:>12}: {r['tokens_per_sec']:8.1f} "
                      f"tok/s  tpot p50 {r['tpot_s']['p50'] * 1e3:6.2f} "
                      f"ms  latency p50 "
                      f"{r['latency_s']['p50'] * 1e3:7.1f} ms")
            print(f"  spec/paged tpot: {report['tpot_ratio']:.2f}x  "
                  f"latency p50: {report['latency_ratio_p50']:.2f}x  "
                  f"accept rate {report['accept_rate']:.2f}  "
                  f"token identity {report['token_identity']:.2f}")
        return 0
    if args.frontdoor:
        report = frontdoor_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, procs=args.procs or 2,
            seed=args.seed, sse_out=args.sse_out,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            ip, w = report["in_process"], report["wire"]
            print(f"[frontdoor_bench] "
                  f"{report['trace']['n_requests']} requests @ "
                  f"{report['trace']['rate_hz']}/s through real "
                  f"sockets")
            for r in (ip, w):
                print(f"  {r['mode']:>24}: "
                      f"{r['goodput_tokens_per_sec']:8.1f} tok/s  "
                      f"ttft p50 {r['ttft_s']['p50'] * 1e3:7.1f} ms  "
                      f"lost {r['lost']}")
            cs = report["check_stream"]
            print(f"  wire/in-process goodput "
                  f"{report['goodput_ratio']:.3f}x  token identity "
                  f"{report['token_identity']:.2f}  --sse audit "
                  f"ok={cs.get('ok')} ({cs.get('streams', 0)} "
                  f"streams, {cs.get('violations', 0)} violations)")
            cp = report["chunked_prefill"]
            print(f"  chunked prefill: short-TTFT p99 "
                  f"{cp['chunked']['ttft_short_s']['p99'] * 1e3:.0f}"
                  f" ms vs "
                  f"{cp['unchunked']['ttft_short_s']['p99'] * 1e3:.0f}"
                  f" ms unchunked — ratio "
                  f"{report['ttft_p99_ratio_chunked']:.3f}x  "
                  f"identity {cp['token_identity']:.2f}")
            sk = report["sigkill"]
            print(f"  SIGKILL @ {sk['kill_at_s']}s: lost "
                  f"{report['sigkill_lost']}  resumed markers "
                  f"{sk['resumed_markers']}  restarts "
                  f"{sk['worker_restarts']}  audit "
                  f"ok={sk['check_stream'].get('ok')}")
            sm = report["sampling"]
            print(f"  sampling churn: new compiles "
                  f"{report['sampling_new_compiles']}  statuses "
                  f"{sm['statuses']}  audit "
                  f"ok={sm['check_stream'].get('ok')}")
            if "sse_out" in report:
                print(f"  wrote SSE capture to {report['sse_out']} — "
                      f"audit with tools/check_stream.py --sse")
        return 0
    if args.qos:
        report = qos_bench(
            rate_hz=args.rate, duration_s=args.qos_duration,
            max_slots=args.max_slots, procs=args.procs or 2,
            seed=args.seed, slo=args.slo, workload=args.workload,
            telemetry_out=args.telemetry_out, trace_out=args.trace_out,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            print(f"[qos_bench] {len(report['workload']['tenants'])} "
                  f"tenants @ {args.rate}/s for "
                  f"{report['workload']['duration_s']}s — hostile "
                  f"{report['hostile_tenants']} vs compliant "
                  f"{report['compliant_tenants']}")
            for tag in ("fifo", "fair"):
                r = report[tag]
                for t, row in r["per_tenant"].items():
                    print(f"  {r['mode']:>10} {t:>8}: ttft p99 "
                          f"{row['ttft_s'].get('p99', 0) * 1e3:7.1f} "
                          f"ms  {row['output_tokens']:5d} tok  "
                          f"({row['ok']}/{row['completions']} ok)")
                print(f"  {r['mode']:>10} fairness "
                      f"{r['fairness_index']:.4f}  trips "
                      f"{sum(a['event'] == 'trip' for a in r['alerts'])}"
                      f"  lost {r['lost']}")
            print(f"  isolation ttft p99 fair/fifo "
                  f"{report['isolation_ttft_p99_ratio']:.3f}x  "
                  f"token identity {report['token_identity']:.2f}  "
                  f"hostile tripped "
                  f"{report['hostile_alert_tripped']:.0f}  compliant "
                  f"clean {report['compliant_clean']:.0f}")
            sk = report["sigkill"]
            print(f"  SIGKILL @ {sk['kill_at_s']}s: lost "
                  f"{sk['lost']}  identity "
                  f"{sk['token_identity']:.2f}  fairness "
                  f"{sk['fairness_index']:.4f}  restarts "
                  f"{len(sk['worker_restarts'])}  check_qos "
                  f"ok={sk.get('check_qos_ok')}  trace "
                  f"ok={sk.get('trace_ok')}")
        return 0
    if args.procs and args.otlp_push_overhead:
        report = fleet_otlp_push_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, procs=args.procs,
            seed=args.seed, otlp_endpoint=args.otlp_endpoint,
            **({"sample": args.trace_sample}
               if args.trace_sample is not None else {}),
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            pu = report["push"]
            print(f"[fleet_otlp_push] {args.requests} requests @ "
                  f"{args.rate}/s, {args.procs} workers, head rate "
                  f"{report['head_rate']}, {report['pairs']} "
                  f"order-balanced rounds")
            print(f"  push vs file-only: latency mean "
                  f"{report['mean_ratio']:.3f}x  ({report['gate']})")
            print(f"  pushed {pu['spans_sent']} spans in "
                  f"{pu['batches_sent']} batches "
                  f"(dropped {pu['batches_dropped']}, post failures "
                  f"{pu['post_failures']})")
            if "spans_delivered" in pu:
                print(f"  collector: {pu['spans_delivered']} spans "
                      f"after dedup of {pu['duplicate_batches']} "
                      f"duplicate batch(es) — complete="
                      f"{pu['complete']}")
        return 0
    if args.procs and args.autoscale:
        report = fleet_autoscale_bench(
            rate_hz=args.rate, procs=args.procs,
            autoscale_max=args.autoscale_max, standby=args.standby,
            max_slots=args.max_slots, seed=args.seed,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            au, fx = report["autoscaled"], report["fixed"]
            print(f"[fleet_autoscale] {args.rate}/s -> "
                  f"{report['step_rate_hz']}/s step; fixed "
                  f"{report['procs_fixed']} workers vs elastic "
                  f"{report['autoscale']['min']}.."
                  f"{report['autoscale']['max']} "
                  f"(+{report['autoscale']['standby']} standby)")
            for r in (fx, au):
                print(f"  {r['mode']:>10}: "
                      f"{r['goodput_per_worker']:7.1f} tok/s/worker  "
                      f"({r['useful_tokens']} tok over "
                      f"{r['worker_seconds']:.1f} worker-s)  lost "
                      f"{r['lost']}")
            rs, pj = report["reaction_s"], report["promote_join_s"]
            print(f"  goodput/worker ratio "
                  f"{report['goodput_per_worker_ratio']:.2f}x  "
                  + (f"reaction {rs:.2f}s (window "
                     f"{report['reaction_window_s']:.2f}s, within="
                     f"{report['reaction_within_window']:.0f})"
                     if rs is not None
                     else "no scale-up observed after the step"))
            print("  warm promotion "
                  + (f"{pj:.3f}s" if pj is not None else "n/a")
                  + f" vs cold spawn {report['cold_spawn_s']:.1f}s  "
                  f"direction changes {au['direction_changes']} "
                  f"(bound {au['oscillation_bound']}, "
                  f"ok={report['oscillation_ok']:.0f})")
        return 0
    if args.procs and args.adaptive_sampling:
        report = fleet_adaptive_sampling_bench(
            rate_hz=args.rate, procs=args.procs,
            max_slots=args.max_slots, seed=args.seed,
            **({"budget_sps": args.trace_budget_sps}
               if args.trace_budget_sps is not None else {}),
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            print(f"[fleet_adaptive_sampling] {args.rate}/s -> "
                  f"{report['step_rate_hz']}/s step, {args.procs} "
                  f"workers, budget {report['budget_sps']} kept "
                  f"spans/s")
            print(f"  kept {report['kept_sps']:.1f} spans/s in the "
                  f"final window — err {report['budget_err']:.2f} "
                  f"({report['gate']}; within_budget="
                  f"{report['within_budget']:.0f})")
            print(f"  head rate {report['rate_final']:.4f} after "
                  f"{report['rate_changes']} change(s): "
                  + ", ".join(
                      f"{c['prev']:.3f}->{c['rate']:.3f}"
                      for c in report["rate_log"]))
        return 0
    if args.procs and args.trace_sampling:
        report = fleet_trace_sampling_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, procs=args.procs,
            seed=args.seed, trace_out=args.trace_out,
            otlp_out=args.otlp_out,
            keep_slow_s=args.trace_keep_slow_s,
            **({"sample": args.trace_sample}
               if args.trace_sample is not None else {}),
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            print(f"[fleet_trace_sampling] {args.requests} requests @ "
                  f"{args.rate}/s, {args.procs} workers, head rate "
                  f"{report['head_rate']}, {report['pairs']} "
                  f"order-balanced rounds")
            print(f"  span reduction vs full tracing: "
                  f"{report['span_reduction']:.3f}  latency mean vs "
                  f"off: {report['mean_ratio']:.3f}x  "
                  f"({report['gate']})")
            sm = report.get("sampling") or {}
            print(f"  traces: {sm.get('traces_sampled', 0)} head-"
                  f"sampled, {sm.get('traces_kept', 0)} tail-kept "
                  f"{dict(sm.get('kept_reasons') or {})}, "
                  f"{sm.get('traces_suppressed', 0)} suppressed; "
                  f"spans suppressed "
                  f"{sm.get('spans_suppressed', 0)}")
            if "trace_out" in report:
                print(f"  wrote sampled timeline to "
                      f"{report['trace_out']} — validate with "
                      f"tools/check_traces.py --fleet")
            if "otlp_out" in report:
                print(f"  wrote OTLP export to {report['otlp_out']} — "
                      f"validate with tools/check_otlp.py")
        return 0
    if args.procs and args.trace_overhead:
        report = fleet_trace_overhead_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, procs=args.procs,
            seed=args.seed, trace_out=args.trace_out,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            print(f"[fleet_trace_overhead] {args.requests} requests @ "
                  f"{args.rate}/s, {args.procs} workers, "
                  f"{report['pairs']} order-balanced pairs")
            print(f"  trace plane on/off: latency p50 "
                  f"{report['latency_ratio_p50']:.3f}x  mean "
                  f"{report['latency_ratio_mean']:.3f}x  goodput "
                  f"{report['goodput_ratio']:.3f}x  ({report['gate']})")
            tp = report["trace_plane"]
            print(f"  merged timeline: {report['trace_events']} events "
                  f"({tp['worker_events']} from workers in "
                  f"{tp['worker_frames']} frames, dropped "
                  f"{tp['dropped']}, skew bound "
                  f"{(tp['skew_bound_s'] or 0) * 1e3:.2f} ms)")
            ex = report["exemplars"]
            print(f"  exemplars: {ex['resolved']}/{ex['found']} bucket "
                  f"exemplars resolve; p99 bucket resolves: "
                  f"{ex['p99_resolves']}")
            if "trace_out" in report:
                print(f"  wrote merged trace to {report['trace_out']} — "
                      f"validate with tools/check_traces.py --fleet")
        return 0
    if args.procs and args.streaming:
        from ddp_practice_tpu.serve.faults import FaultPlan

        plan = (FaultPlan.from_json(args.fault_plan)
                if args.fault_plan else None)
        report = streaming_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, procs=args.procs,
            seed=args.seed, fault_plan=plan,
            telemetry_out=args.telemetry_out,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        elif "fleet" in report:  # chaos arm
            fl, st = report["fleet"], report["streams"]
            print(f"[streaming_bench chaos] {args.requests} requests @ "
                  f"{args.rate}/s, {args.procs} workers, kills "
                  f"{fl['kills_fired']}")
            print(f"  consumer ledger: dupes {report['chunk_dupes']}  "
                  f"gaps {report['chunk_gaps']}  lost {report['lost']}  "
                  f"unterminated {report['unterminated']}  "
                  f"resumed markers {st['resumed_markers']}  "
                  f"suppressed {st['suppressed_tokens']} tok")
            print(f"  inter-token p99 "
                  f"{report['inter_token_p99_s'] * 1e3:.2f} ms  "
                  f"resume gap p99 "
                  f"{report['resume_gap_p99_s'] * 1e3:.1f} ms")
            cs = report.get("check_stream", {})
            print(f"  check_stream audit: ok={cs.get('ok')} over "
                  f"{cs.get('streams', 0)} stream(s), "
                  f"{cs.get('violations', 0)} violation(s)")
        else:
            print(f"[streaming_bench] {args.requests} requests @ "
                  f"{args.rate}/s, {args.procs} workers, "
                  f"{report['reps']} order-balanced reps")
            print(f"  streaming vs end-of-request: latency mean "
                  f"{report['latency_ratio_mean']:.3f}x  p50 "
                  f"{report['latency_ratio_p50']:.3f}x  goodput "
                  f"{report['goodput_ratio']:.3f}x  ({report['gate']})")
            print(f"  exactly-once cross-check violations: "
                  f"{report['stream_violations']}")
        return 0
    if args.procs and args.cache_aware:
        report = cache_routing_bench(
            n_requests=args.requests, rate_hz=args.rate,
            procs=args.procs, max_slots=args.max_slots,
            block_size=args.block_size, seed=args.seed,
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            aff, ll = report["affinity"], report["least_loaded"]
            print(f"[cache_routing_bench] {report['trace']['n_requests']}"
                  f" requests @ {report['trace']['rate_hz']}/s, "
                  f"{report['procs']} workers, "
                  f"{report['trace']['k_prefixes']} prefix families x "
                  f"{report['trace']['prefix_len']} tokens, pool "
                  f"{report['pool']['num_blocks']} x "
                  f"{report['pool']['block_size']}")
            for r in (ll, aff):
                print(f"  {r['mode']:>16}: "
                      f"{r['goodput_tokens_per_sec']:8.1f} tok/s  "
                      f"hit rate {r['hit_rate']:.3f}  "
                      f"({r['hit_tokens']}/"
                      f"{r['hit_tokens'] + r['miss_tokens']} prefill "
                      f"tokens warm)  lost {r['lost']}")
            print(f"  affinity/least-loaded: hit rate "
                  f"{report['hit_rate_ratio']:.2f}x  goodput "
                  f"{report['goodput_ratio']:.2f}x  token identity "
                  f"{report['token_identity']:.2f}  routes "
                  f"{aff['route_decisions']}")
        return 0
    if args.procs:
        from ddp_practice_tpu.serve.faults import FaultPlan

        plan = (FaultPlan.from_json(args.fault_plan)
                if args.fault_plan else None)
        report = fleet_bench(
            n_requests=args.requests, rate_hz=args.rate,
            max_slots=args.max_slots, procs=args.procs,
            seed=args.seed, fault_plan=plan,
            metrics_port=args.metrics_port,
            trace_out=args.trace_out,
            otlp_out=args.otlp_out,
            otlp_endpoint=args.otlp_endpoint,
            trace_keep_slow_s=args.trace_keep_slow_s,
            trace_tenant_rates=(
                json.loads(args.trace_tenant_rates)
                if args.trace_tenant_rates else None),
            **({"trace_sample": args.trace_sample}
               if args.trace_sample is not None else {}),
            **({"decode_burst": args.decode_burst}
               if args.decode_burst is not None else {}),
        )
        if args.json:
            print(json.dumps(report))
        else:
            ip, fl = report["in_process"], report["fleet"]
            kills = " under real kills" if args.fault_plan else ""
            print(f"[fleet_bench] {args.requests} requests @ "
                  f"{args.rate}/s, {args.procs} workers{kills}")
            for r in (ip, fl):
                print(f"  {r['mode']:>12}: "
                      f"{r['goodput_tokens_per_sec']:8.1f} tok/s  "
                      f"ttft p50 {r['ttft_s']['p50'] * 1e3:7.1f} ms  "
                      f"latency p50 {r['latency_s']['p50'] * 1e3:7.1f}"
                      f"/p99 {r['latency_s']['p99'] * 1e3:.1f} ms")
            print(f"  contended latency ratio p50 "
                  f"{report['latency_ratio_p50']:.3f}x  p99 "
                  f"{report['latency_ratio_p99']:.3f}x  goodput "
                  f"{report['goodput_ratio']:.3f}x")
            if "tpot_ratio_p50" in report:
                print(f"  decomposition: tpot (steady decode) "
                      f"{report['tpot_ratio_p50']:.3f}x  ttft "
                      f"(admission hop) {report['ttft_ratio_p50']:.3f}x")
            print(f"  fleet: statuses {fl['statuses']}  lost "
                  f"{fl['lost']}  failovers {fl['failovers']:.0f}  "
                  f"restarts {fl['worker_restarts']}"
                  + (f"  kills {fl.get('kills_fired')}"
                     if "kills_fired" in fl else ""))
            if "trace_out" in report:
                tp = report["trace_plane"]
                print(f"  wrote merged fleet trace to "
                      f"{report['trace_out']} "
                      f"({report['trace_events']} events, "
                      f"{tp['worker_events']} from workers, dropped "
                      f"{tp['dropped']}) — validate with "
                      f"tools/check_traces.py --fleet")
            if "sampling" in report:
                sm = report["sampling"]
                print(f"  sampling: head rate {sm['head_rate']:g} — "
                      f"{sm['traces_sampled']} head-sampled, "
                      f"{sm['traces_kept']} tail-kept "
                      f"{sm['kept_reasons']}, "
                      f"{sm['traces_suppressed']} suppressed")
            if "otlp_out" in report:
                print(f"  wrote OTLP export to {report['otlp_out']} — "
                      f"validate with tools/check_otlp.py")
        return 0
    if args.trace_overhead:
        raise SystemExit("--trace-overhead needs --procs N (it measures "
                         "the fleet trace plane against worker "
                         "processes)")
    if args.streaming:
        raise SystemExit("--streaming needs --procs N (chunks ride the "
                         "worker push stream; the in-process router "
                         "streams by default already)")
    if args.alert_sink and not args.slo:
        raise SystemExit("--alert-sink needs --slo (the sinks carry the "
                         "watchdog's trip/resolve edges)")
    if args.fault_plan and not args.replicas:
        raise SystemExit("--fault-plan needs --replicas N (faults are "
                         "injected into the router fleet run)")
    if args.slo and not args.replicas:
        raise SystemExit("--slo needs --replicas N (the watchdog feeds "
                         "the router's brown-out hook)")
    if args.scrape_hz and args.metrics_port is None:
        raise SystemExit("--scrape-hz needs --metrics-port (there is "
                         "nothing to scrape without the server)")
    bench_kw = {}
    if args.decode_burst is not None:
        bench_kw["decode_burst"] = args.decode_burst
    if args.paged:
        bench_kw["paged"] = True
        bench_kw["block_size"] = args.block_size
    if args.max_len is not None:
        bench_kw["max_len"] = args.max_len
    if args.trace_out:
        bench_kw["trace_out"] = args.trace_out
    if args.telemetry_out:
        bench_kw["telemetry_out"] = args.telemetry_out
    if args.metrics_port is not None:
        bench_kw["metrics_port"] = args.metrics_port
        bench_kw["scrape_hz"] = args.scrape_hz
    if args.slo:
        bench_kw["slo"] = args.slo
        if args.alert_sink:
            bench_kw["alert_sinks"] = args.alert_sink
    if args.replicas:
        from ddp_practice_tpu.serve.faults import FaultPlan

        bench_kw["replicas"] = args.replicas
        if args.fault_plan:
            bench_kw["fault_plan"] = FaultPlan.from_json(args.fault_plan)
    report = serve_bench(
        n_requests=args.requests, rate_hz=args.rate,
        max_slots=args.max_slots, seed=args.seed, **bench_kw,
    )
    if args.json:
        print(json.dumps(report))
    else:
        c, s = report["continuous"], report["static"]
        print(
            f"[serve_bench] {args.requests} requests @ {args.rate}/s, "
            f"{args.max_slots} slots"
        )
        rows = [c, s] + ([report["paged"]] if "paged" in report else []) \
            + ([report["router"]] if "router" in report else [])
        for r in rows:
            print(
                f"  {r['mode']:>10}: {r['tokens_per_sec']:8.1f} tok/s  "
                f"ttft p50 {r['ttft_s']['p50'] * 1e3:7.1f} ms  "
                f"p99 {r['ttft_s']['p99'] * 1e3:7.1f} ms  "
                f"latency p50 {r['latency_s']['p50'] * 1e3:7.1f} ms"
            )
            ph = r.get("phases")
            if ph:
                print(
                    "              phases p50/p99 ms:  "
                    + "  ".join(
                        f"{k[:-2]} {ph[k]['p50'] * 1e3:.1f}/"
                        f"{ph[k]['p99'] * 1e3:.1f}"
                        for k in ("queue_s", "prefill_s", "decode_s",
                                  "stall_s")
                    )
                )
        print(f"  continuous/static throughput: "
              f"{report['throughput_ratio']:.2f}x")
        if "paged" in report:
            print(
                f"  paged/continuous throughput: "
                f"{report['paged_vs_continuous']:.2f}x  "
                f"(max servable context: paged "
                f"{report['paged']['max_servable_context']} vs slot "
                f"{report['continuous']['max_servable_context']} "
                f"@ max_len {report['max_len']})"
            )
        if "router" in report:
            r = report["router"]
            faults = " under injected faults" if args.fault_plan else ""
            print(
                f"  router{faults}: goodput "
                f"{r['goodput_tokens_per_sec']:.1f} tok/s  statuses "
                f"{r['statuses']}  retries {r['retries']:.0f}  "
                f"failovers {r['failovers']:.0f}  "
                f"breaker trips {r['breaker_trips']:.0f}"
            )
            print(f"  router/continuous throughput: "
                  f"{report['router_vs_continuous']:.2f}x")
        if "trace_out" in report:
            print(f"  wrote trace to {report['trace_out']} "
                  f"({report['trace_events']} events) — validate with "
                  f"tools/check_traces.py")
        if "telemetry" in report:
            t = report["telemetry"]
            line = (f"  telemetry plane: port {t['metrics_port']}  "
                    f"scrapes {t['scrapes']}  dropped {t['dropped']}")
            if t["telemetry_out"]:
                line += (f"  jsonl {t['telemetry_out']} — judge with "
                         f"tools/check_slo.py")
            print(line)
        slo_rep = report.get("router", {}).get("slo")
        if slo_rep:
            trips = sum(a["event"] == "trip" for a in slo_rep["alerts"])
            print(f"  slo: {trips} alert trip(s), "
                  f"active at end: "
                  f"{[k for k, v in slo_rep['active'].items() if v]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
