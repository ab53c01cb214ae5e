"""Chaos tests: the fault-tolerant router under a deterministic FaultPlan.

The acceptance scenario (ISSUE 2): a seeded Poisson trace on fake-clock
replicas, a replica killed mid-decode, and three invariants that make
failover trustworthy rather than hopeful:

1. NONE LOST — every submitted request ends in a defined terminal
   status (ok / shed / timeout / rejected / error), crash or not;
2. TOKEN IDENTITY — a migrated request's greedy tokens equal a
   fault-free single-replica run's (failover re-admits prompt +
   tokens-so-far; greedy decoding is a pure function of the prefix);
3. NO NEW COMPILES — failover re-prefills land in already-warmed
   buckets on survivors (jit cache sizes pinned before/after).

Everything replays bit-for-bit: FakeClock time, seeded trace, seeded
fault plan, deterministic backoff jitter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.serve import (
    EngineConfig,
    FakeClock,
    FaultPlan,
    FaultSpec,
    PagedEngine,
    Request,
    RouterConfig,
    Scheduler,
    make_router,
)
from ddp_practice_tpu.serve.workload import build_trace

VOCAB = 32

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def lm():
    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=96, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _trace(n, rate_hz=50.0, seed=11, max_new=(3, 7), plen=(2, 6)):
    return build_trace(
        n_requests=n, rate_hz=rate_hz, vocab=VOCAB,
        prompt_len_range=plen, max_new_range=max_new, seed=seed,
    )


def _reference_tokens(lm, trace, engine_cfg):
    """Fault-free single-replica run (the PR-1 path) of the same trace."""
    model, params = lm
    engine = PagedEngine(model, params, engine_cfg)
    sched = Scheduler(engine, clock=FakeClock(step_s=0.01),
                      max_queue=len(trace))
    for t in trace:
        sched.submit(Request(
            rid=t["rid"], prompt=t["prompt"],
            max_new_tokens=t["max_new_tokens"],
        ))
    sched.run_until_idle()
    return {c.rid: c.tokens for c in sched.completions}


def _drive(router, trace):
    """Replay arrivals on the router's fake clock until the fleet drains."""
    i = 0
    while not (i >= len(trace) and router.idle):
        while i < len(trace) \
                and trace[i]["arrival"] <= router.clock.now():
            t = trace[i]
            router.submit(Request(
                rid=t["rid"], prompt=t["prompt"],
                max_new_tokens=t["max_new_tokens"],
                arrival=t["arrival"],
            ))
            i += 1
        router.step()
    return router.completions


ENGINE_CFG = EngineConfig(
    max_slots=2, max_len=96, prompt_buckets=(32,), temperature=0.0,
)


@pytest.mark.slow  # ~25 s: three engines (reference + 2-replica fleet)
def test_failover_token_identity_none_lost(devices, lm):
    """Kill replica 0 mid-decode: migrated requests finish with tokens
    identical to the fault-free run, nothing is lost, survivors compile
    nothing new."""
    model, params = lm
    trace = _trace(12)
    want = _reference_tokens(lm, trace, ENGINE_CFG)

    plan = FaultPlan([FaultSpec(kind="crash", tick=6, replica=0)])
    clock = FakeClock(step_s=0.01)
    router = make_router(
        model, params, 2, ENGINE_CFG, clock=clock, max_queue=64,
        config=RouterConfig(max_retries=2, retry_jitter=0.0),
        fault_plan=plan,
    )
    router.warmup()
    warm = router.compile_stats()
    # (`cow_compiles` is process-wide: it reads what earlier tests left)
    assert {k: v for k, v in warm[1].items() if k != "cow_compiles"} == {
        "prefill_compiles": 1, "decode_compiles": 1,
        "prefix_prefill_compiles": 0, "verify_compiles": 0}

    comps = _drive(router, trace)

    # none lost: every request has exactly one terminal completion
    by_rid = {c.rid: c for c in comps}
    assert sorted(by_rid) == [t["rid"] for t in trace]
    assert all(
        c.status in ("eos", "length", "shed", "timeout", "rejected",
                     "error")
        for c in comps
    )
    # the crash actually hit in-flight work and failover fired
    assert router.metrics.failovers.value >= 1
    assert router.states()[0] == "dead" and router.states()[1] == "healthy"
    # token identity: every served request — including the migrated ones,
    # whose continuation ran as prompt+prefix on the survivor — matches
    # the fault-free single-replica run bit-for-bit (greedy)
    served = [c for c in comps if c.status in ("eos", "length")]
    assert served, "no request completed"
    for c in served:
        assert c.tokens == want[c.rid], f"rid {c.rid} diverged"
    # under this plan nothing needed shedding: the survivor absorbed all
    assert all(c.status == "length" for c in comps)
    # failover re-prefills landed in the warmed bucket: zero new compiles
    assert router.compile_stats()[1] == warm[1]

    # the same plan replays bit-identically (chaos must be reproducible)
    router2 = make_router(
        model, params, 2, ENGINE_CFG, clock=FakeClock(step_s=0.01),
        max_queue=64, config=RouterConfig(max_retries=2, retry_jitter=0.0),
        fault_plan=FaultPlan.from_json(plan.to_json()),
    )
    router2.warmup()
    comps2 = {c.rid: c for c in _drive(router2, trace)}
    for rid, c in by_rid.items():
        assert comps2[rid].tokens == c.tokens
        assert comps2[rid].status == c.status
        assert comps2[rid].finish == c.finish


@pytest.mark.slow  # ~15 s: two engines (reference + single-replica fleet)
def test_nan_and_admit_faults_are_retried_to_identical_tokens(devices, lm):
    """A NaN in one slot's logits and an injected admission failure each
    poison ONE request, which the router retries to a completion that is
    token-identical to the fault-free run — the batch never notices."""
    model, params = lm
    cfg = EngineConfig(max_slots=2, max_len=96, prompt_buckets=(16,),
                       temperature=0.0)
    trace = _trace(4, rate_hz=1000.0, seed=3)  # all arrive ~immediately
    want = _reference_tokens(lm, trace, cfg)

    plan = FaultPlan([
        FaultSpec(kind="admit_fail", tick=1, replica=0),
        FaultSpec(kind="nan_logits", tick=4, replica=0, slot=0),
    ])
    router = make_router(
        model, params, 1, cfg, clock=FakeClock(step_s=0.01), max_queue=64,
        config=RouterConfig(max_retries=3, retry_base_s=0.01,
                            retry_jitter=0.0, trip_after=10),
        fault_plan=plan,
    )
    router.warmup()
    comps = _drive(router, trace)

    by_rid = {c.rid: c for c in comps}
    assert sorted(by_rid) == [0, 1, 2, 3]
    # both faults consumed a retry; the breaker never tripped
    assert router.metrics.retries.value >= 2
    assert router.metrics.breaker_trips.value == 0
    assert router.states()[0] == "healthy"
    # every request ends ok with the fault-free tokens — the NaN cost a
    # retry, not an answer, and not anyone else's answer
    for c in comps:
        assert c.status == "length"
        assert c.tokens == want[c.rid], f"rid {c.rid} diverged"


def test_faulted_fleet_loses_nothing_and_matches_a_clean_rerun(devices, lm):
    """The router's count gates in one tier-1 run (the two slow tests
    above hold them against a third, fault-free engine): an admission
    failure, a latency spike and a NaN-poisoned slot on replica 1, then
    replica 0 dead for good mid-decode. Every request ends `length`, the
    survivor compiles nothing new, and each one's tokens, migrated and
    retried ones included, are what the survivor gives the same prompt
    when nothing goes wrong."""
    model, params = lm
    trace = _trace(8)
    plan = FaultPlan([
        FaultSpec(kind="admit_fail", tick=1, replica=1),
        FaultSpec(kind="latency", tick=2, replica=1, delay_s=0.05),
        FaultSpec(kind="nan_logits", tick=4, replica=1, slot=0),
        FaultSpec(kind="crash", tick=6, replica=0),
    ])
    router = make_router(
        model, params, 2, ENGINE_CFG, clock=FakeClock(step_s=0.01),
        max_queue=64,
        config=RouterConfig(max_retries=3, retry_base_s=0.01,
                            retry_jitter=0.0, trip_after=10),
        fault_plan=plan,
    )
    router.warmup()
    warm = router.compile_stats()
    faulted = {c.rid: c for c in _drive(router, trace)}
    assert sorted(faulted) == [t["rid"] for t in trace]  # one end each
    assert all(c.status == "length" for c in faulted.values())
    assert router.metrics.failovers.value >= 1
    assert router.metrics.retries.value >= 1
    assert router.states() == {0: "dead", 1: "healthy"}
    # the plan is spent and replica 0 stays down: the same prompts again
    # run start to finish on the survivor
    for t in trace:
        router.submit(Request(rid=100 + t["rid"], prompt=t["prompt"],
                              max_new_tokens=t["max_new_tokens"]))
    router.run_until_idle()
    clean = {c.rid - 100: c for c in router.completions if c.rid >= 100}
    for rid, c in faulted.items():
        assert c.tokens == clean[rid].tokens, f"rid {rid} diverged"
    assert router.compile_stats()[1] == warm[1]


def test_brownout_sheds_low_priority_and_caps_budget(devices, lm):
    """Overload flips brown-out on: queued low-priority work is shed
    with reason=brownout, new low-priority arrivals shed at the door,
    new high-priority arrivals get a capped token budget, and the mode
    clears when pressure drains."""
    model, params = lm
    cfg = EngineConfig(max_slots=1, max_len=96, prompt_buckets=(8,),
                       temperature=0.0)
    router = make_router(
        model, params, 1, cfg, clock=FakeClock(step_s=0.01), max_queue=64,
        config=RouterConfig(brownout_on=2.0, brownout_off=0.5,
                            brownout_max_new=2, shed_priority=1,
                            retry_jitter=0.0),
    )
    router.warmup()
    pri = [0, 0, 0, 1, 1, 0]
    for rid, p in enumerate(pri):
        assert router.submit(Request(
            rid=rid, prompt=[1 + rid, 2], max_new_tokens=6, priority=p,
        ))
    router.step()  # pressure (5 queued + 1 active) / 1 slot >> 2.0
    assert router.brownout
    assert router.metrics.brownout_active.value == 1
    snap = router.metrics.registry.snapshot()
    assert snap["serve_sheds_total{reason=brownout}"] == 2  # rids 3, 4
    # door behavior while browned out
    assert not router.submit(Request(rid=6, prompt=[7, 2],
                                     max_new_tokens=6, priority=1))
    assert router.submit(Request(rid=7, prompt=[8, 2],
                                 max_new_tokens=6, priority=0))
    router.run_until_idle()
    by_rid = {c.rid: c for c in router.completions}
    assert {r: by_rid[r].status for r in (3, 4, 6)} == {
        3: "shed", 4: "shed", 6: "shed",
    }
    # pre-brown-out admissions keep their full budget; the brown-out-era
    # admission is capped at brownout_max_new
    for rid in (0, 1, 2, 5):
        assert by_rid[rid].status == "length"
        assert len(by_rid[rid].tokens) == 6
    assert by_rid[7].status == "length" and len(by_rid[7].tokens) == 2
    # drained: pressure back under the floor, mode cleared
    assert not router.brownout
    assert router.metrics.brownout_active.value == 0
    snap = router.metrics.registry.snapshot()
    assert snap["serve_sheds_total{reason=brownout}"] == 3


def test_slo_burn_trips_brownout_below_pressure_threshold(devices, lm):
    """THE SLO-brownout pin (ISSUE 5): the router browns out from SLO
    burn with fleet pressure far below `brownout_on`, behaves exactly
    like a pressure brown-out while engaged (door sheds, budget caps),
    and disengages with hysteresis only after the slow window clears —
    all under FakeClock."""
    from ddp_practice_tpu.serve.slo import SLOConfig, SLOWatchdog
    from ddp_practice_tpu.utils.trace import TraceRecorder

    model, params = lm
    cfg = EngineConfig(max_slots=4, max_len=96, prompt_buckets=(8,),
                       temperature=0.0)
    clock = FakeClock(step_s=0.01)
    tracer = TraceRecorder(clock=clock)
    watchdog = SLOWatchdog(
        SLOConfig(availability=0.9, fast_window_s=0.5, slow_window_s=2.0,
                  trip_burn=2.0, resolve_burn=1.0, min_events=3),
        clock=clock, tracer=tracer,
    )
    router = make_router(
        model, params, 1, cfg, clock=clock, max_queue=64,
        # brownout_on is unreachable: ONLY the SLO can trip the mode
        config=RouterConfig(brownout_on=50.0, brownout_off=0.4,
                            brownout_max_new=2, shed_priority=1,
                            retry_jitter=0.0),
        tracer=tracer, slo=watchdog,
    )
    router.warmup()
    tracer.clear()
    # five already-expired deadlines -> five "timeout" completions in
    # one tick: availability burn trips while the fleet sits idle
    for rid in range(5):
        router.submit(Request(rid=rid, prompt=[1 + rid, 2],
                              max_new_tokens=4, deadline=-1.0))
    router.step()
    assert watchdog.active
    assert router.brownout
    assert router.metrics.brownout_active.value == 1
    # the point: pressure is nowhere near the pressure trigger
    assert router.metrics.fleet_pressure.value < 50.0
    # engaged brown-out behaves identically to the pressure one
    assert not router.submit(Request(rid=10, prompt=[3, 2],
                                     max_new_tokens=6, priority=1))
    assert router.submit(Request(rid=11, prompt=[4, 2],
                                 max_new_tokens=6, priority=0))
    router.run_until_idle()
    by_rid = {c.rid: c for c in router.completions}
    assert by_rid[10].status == "shed"
    assert by_rid[11].status == "length" and len(by_rid[11].tokens) == 2
    # anti-windup: rid 10's shed was the BROWN-OUT's own doing — it
    # must not count as an availability failure, or the alert would
    # feed itself and the mode could never disengage under sustained
    # low-priority traffic. Bad events seen = the 5 original timeouts.
    assert sum(
        flags.get("availability", False)
        for _, flags in watchdog._events
    ) == 5
    # pressure is BELOW brownout_off already; the mode must still hold
    # until the SLO resolves (disengage needs both)
    assert router.metrics.fleet_pressure.value <= 0.4
    assert router.brownout
    # tick past the slow window: watchdog resolves, brown-out clears
    for _ in range(400):
        router.step()
        if not router.brownout:
            break
    assert not watchdog.active
    assert not router.brownout
    assert [e for _, e, _ in watchdog.alert_log] == ["trip", "resolve"]
    # the trace records the whole story: slo alert edges + a brownout_on
    # instant attributed to the SLO trigger, and it validates clean
    from tools.check_traces import validate

    trace = tracer.to_chrome_trace()
    assert validate(trace) == []
    by_name = {}
    for ev in trace["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    assert "slo_alert" in by_name and "slo_resolve" in by_name
    assert by_name["brownout_on"][0]["args"]["trigger"] == "slo"
    assert "brownout_off" in by_name


def test_permanently_dead_fleet_sheds_not_hangs(devices, lm):
    """The none-lost invariant with NOWHERE to fail over: a 1-replica
    fleet whose only replica dies for good must give every in-flight and
    queued request a terminal shed — not cycle the retry heap forever
    (run_until_idle would never drain and the bench loop would spin)."""
    model, params = lm
    cfg = EngineConfig(max_slots=2, max_len=96, prompt_buckets=(8,),
                       temperature=0.0)
    plan = FaultPlan([FaultSpec(kind="crash", tick=3, replica=0)])
    router = make_router(
        model, params, 1, cfg, clock=FakeClock(step_s=0.01), max_queue=64,
        config=RouterConfig(retry_jitter=0.0), fault_plan=plan,
    )
    router.warmup()
    for rid in range(4):
        router.submit(Request(rid=rid, prompt=[1 + rid, 2],
                              max_new_tokens=8))
    router.run_until_idle(max_ticks=500)  # must DRAIN, not raise
    assert router.idle
    by_rid = {c.rid: c for c in router.completions}
    assert sorted(by_rid) == [0, 1, 2, 3]
    assert all(c.status in ("length", "shed") for c in router.completions)
    assert any(c.status == "shed" for c in router.completions)
    snap = router.metrics.registry.snapshot()
    assert snap["serve_sheds_total{reason=no_replica}"] >= 1
    # and the front door gives the same fast no
    assert not router.submit(Request(rid=9, prompt=[3], max_new_tokens=2))
    assert router.completions[-1].status == "shed"


def test_replica_recovery_after_down_window(devices, lm):
    """A crash with down_s > 0: the breaker's half-open probe finds the
    replica alive after the window and it serves again (state returns
    to healthy, later requests complete on a 2-replica fleet)."""
    model, params = lm
    cfg = EngineConfig(max_slots=2, max_len=96, prompt_buckets=(8,),
                       temperature=0.0)
    plan = FaultPlan([
        FaultSpec(kind="crash", tick=2, replica=0, down_s=0.2),
    ])
    router = make_router(
        model, params, 2, cfg, clock=FakeClock(step_s=0.01), max_queue=64,
        config=RouterConfig(probe_base_s=0.05, probe_jitter=0.0,
                            retry_jitter=0.0),
        fault_plan=plan,
    )
    router.warmup()
    for rid in range(4):
        router.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                              max_new_tokens=4))
    router.run_until_idle()
    assert all(c.status == "length" for c in router.completions)
    assert router.metrics.breaker_trips.value == 1
    # keep ticking past the down window: a probe revives replica 0
    for _ in range(60):
        if router.states()[0] == "healthy":
            break
        router.step()
    assert router.states()[0] == "healthy"
    # and it actually serves again
    router.submit(Request(rid=99, prompt=[5, 6], max_new_tokens=3))
    router.run_until_idle()
    assert {c.rid: c.status for c in router.completions}[99] == "length"


# --------------------------------------------- one-way submit cast (PR 15)
def _cast_handle(drop_first_cast: bool):
    """Host-pure RemoteReplicaHandle over a scripted one-way wire: the
    stub client records every submit cast, optionally drops the first
    frame on the floor, and answers the reconcile poll's `confirm` ask
    from a worker-side dedup map keyed by rid — the exact seam the
    fire-and-forget path trusts."""
    from ddp_practice_tpu.serve.supervisor import (
        RemoteReplicaHandle,
        Supervisor,
        SupervisorConfig,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec

    wire = {"casts": [], "delivered": [], "drop": drop_first_cast,
            "seen": {}}

    class Client:
        def cast(self, op, **fields):
            assert op == "submit"
            req = fields["request"]
            wire["casts"].append(req["rid"])
            if wire["drop"]:
                wire["drop"] = False
                return                      # the frame never arrives
            # worker-side dedup by rid: a replayed cast is absorbed,
            # never double-admitted
            if req["rid"] not in wire["seen"]:
                wire["seen"][req["rid"]] = True
                wire["delivered"].append(req)

        def call(self, op, **fields):
            if op == "poll":
                reply = {
                    "completions": [], "watermark": 0, "inflight": [],
                    "stats": {"queue": 0,
                              "active": len(wire["delivered"]),
                              "max_slots": 2},
                    "version": 1,
                }
                if fields.get("confirm"):
                    # absent = never saw the rid (the lost-frame answer)
                    reply["confirmed"] = {
                        str(r): True for r in fields["confirm"]
                        if r in wire["seen"]
                    }
                return reply
            return {"ok": True}

        def close(self):
            pass

    class Worker:
        def __init__(self, spec):
            self.pid = 4242
            self.spec = spec
            self.client = Client()
            self.telemetry_port = 0

        def poll(self):
            return None

        def kill_signal(self, sig):
            pass

        def reap(self, timeout_s=5.0):
            pass

    spec = WorkerSpec(engine={"max_slots": 2, "prompt_buckets": [8]},
                      max_queue=4)
    clock = FakeClock(step_s=0.01)
    sup = Supervisor([spec], SupervisorConfig(), spawn_fn=Worker,
                     spawn_in_thread=False, clock=clock)
    sup.start()
    return RemoteReplicaHandle(0, sup, spec, clock=clock), clock, wire


def test_dropped_submit_cast_redispatches_exactly_once():
    """The PR-15 fire-and-forget seam: a submit cast lost on the wire
    is re-dispatched by confirm-on-poll reconciliation EXACTLY once —
    same rid (idempotent at the worker's dedup map), no further casts
    once the worker confirms, and the request never leaves
    `outstanding` (the salvage point failover needs)."""
    h, clock, wire = _cast_handle(drop_first_cast=True)
    h.submit(Request(rid=9, prompt=[1, 2], max_new_tokens=4,
                     arrival=0.0))
    assert wire["casts"] == [9] and wire["delivered"] == []
    assert 9 in h.outstanding

    clock.advance(10.0)            # past the poll throttle
    h.step()                       # confirm ask -> "never saw rid 9"
    assert wire["casts"] == [9, 9]             # re-cast, once
    assert [r["rid"] for r in wire["delivered"]] == [9]

    for _ in range(3):             # confirmed: reconciliation goes quiet
        clock.advance(10.0)
        h.step()
    assert wire["casts"] == [9, 9]             # no third dispatch
    assert [r["rid"] for r in wire["delivered"]] == [9]
    assert 9 in h.outstanding      # still inflight, awaiting completion


def test_duplicate_cast_is_absorbed_by_rid_dedup():
    """The other half of at-least-once delivery: when the first frame
    DID land but its confirmation hadn't yet, a conservative re-cast
    reaches the worker as a duplicate rid and must admit nothing new."""
    h, clock, wire = _cast_handle(drop_first_cast=False)
    h.submit(Request(rid=3, prompt=[1, 2, 3], max_new_tokens=4,
                     arrival=0.0))
    assert [r["rid"] for r in wire["delivered"]] == [3]
    # replay the same frame (the reconcile path's worst case)
    h._client().cast("submit", request=h._request_dict(
        h.outstanding[3]["req"]))
    assert wire["casts"] == [3, 3]
    assert [r["rid"] for r in wire["delivered"]] == [3]   # dedup held
    clock.advance(10.0)
    h.step()                       # poll confirms; unconfirmed clears
    assert wire["casts"] == [3, 3]
