"""serve/supervisor.py state machine + RemoteReplicaHandle — host-pure.

No processes are spawned here: `spawn_fn` is injected with fakes and
time is a FakeClock, so the restart-backoff schedule, the
restart-budget circuit breaker, the drain path, and the handle's
salvage/heartbeat accounting replay deterministically. The real-process
truth of the same machinery lives in tests/test_worker_fleet.py
(slow + chaos).
"""

import pytest

from ddp_practice_tpu.serve.faults import (
    FaultPlan,
    FaultSpec,
    FleetFaultDriver,
    ReplicaCrashed,
)
from ddp_practice_tpu.serve.rpc import RpcRemoteError, RpcTimeout
from ddp_practice_tpu.serve.scheduler import FakeClock, Request
from ddp_practice_tpu.serve.supervisor import (
    BACKOFF,
    DRAINING,
    FAILED,
    RUNNING,
    SPAWNING,
    STOPPED,
    RemoteReplicaHandle,
    Supervisor,
    SupervisorConfig,
    fleet_targets,
)
from ddp_practice_tpu.serve.worker import WorkerSpec
from ddp_practice_tpu.utils.backoff import backoff_delay


class FakeClient:
    """Scriptable RPC client: `handler(op, fields)` -> dict or raise."""

    def __init__(self, handler=None):
        self.handler = handler or (lambda op, fields: {})
        self.calls = []
        self.closed = False

    def call(self, op, **fields):
        self.calls.append((op, fields))
        return {"ok": True, **self.handler(op, fields)}

    def close(self):
        self.closed = True


class FakeWorker:
    _next_pid = [1000]

    def __init__(self, spec, handler=None):
        FakeWorker._next_pid[0] += 1
        self.pid = FakeWorker._next_pid[0]
        self.spec = spec
        self.rc = None
        self.signals = []
        self.reaped = False
        self.telemetry_port = 9000 + self.pid % 100
        self.client = FakeClient(handler)

    def poll(self):
        return self.rc

    def kill_signal(self, sig):
        self.signals.append(sig)
        if sig in ("SIGKILL", "SIGTERM"):
            self.rc = -9

    def die(self, rc=1):
        self.rc = rc

    def reap(self, timeout_s=5.0):
        self.reaped = True
        self.client.close()


SPEC = WorkerSpec(engine={"max_slots": 2, "prompt_buckets": [8, 16]},
                  max_queue=4)
CFG = SupervisorConfig(restart_base_s=0.2, restart_factor=2.0,
                       restart_max_s=10.0, restart_jitter=0.0,
                       restart_budget=3)


def make_sup(n=1, handler=None, cfg=CFG):
    spawned = []

    def spawn(spec):
        w = FakeWorker(spec, handler)
        spawned.append(w)
        return w

    clock = FakeClock(step_s=0.01)
    sup = Supervisor([SPEC] * n, cfg, spawn_fn=spawn,
                     spawn_in_thread=False, clock=clock)
    sup.start()
    return sup, clock, spawned


# ------------------------------------------------------------- supervisor
def test_restart_backoff_schedule_is_the_shared_backoff():
    """A dying worker respawns at exactly backoff_delay(k) after each
    death — the same utils/backoff.py schedule every other retry loop
    uses, per-slot seeded."""
    sup, clock, spawned = make_sup()
    assert sup.state(0) == RUNNING and len(spawned) == 1
    for k in range(3):
        spawned[-1].die()
        t_death = clock.now()
        sup.poll()
        assert sup.state(0) == BACKOFF
        assert spawned[-1].reaped          # the corpse was collected
        want = backoff_delay(k, base_s=0.2, factor=2.0, max_s=10.0,
                             jitter=0.0, seed=CFG.seed + 0)
        # one tick before due: nothing spawns
        clock.advance(want - 0.001 - (clock.now() - t_death))
        sup.poll()
        assert sup.state(0) == BACKOFF and len(spawned) == 1 + k
        clock.advance(0.002)
        sup.poll()
        assert sup.state(0) == RUNNING and len(spawned) == 2 + k
        assert sup.restarts[0] == k + 1
        # a restarted slot is a NEW process (new pid, new client)
        assert spawned[-1].pid != spawned[-2].pid


def test_restart_budget_circuit_breaker_goes_failed():
    sup, clock, spawned = make_sup()
    for _ in range(CFG.restart_budget):
        spawned[-1].die()
        sup.poll()
        clock.advance(60.0)  # well past any backoff
        sup.poll()
        assert sup.state(0) == RUNNING
    # one death past the budget: FAILED for good, no more spawns
    spawned[-1].die()
    sup.poll()
    assert sup.state(0) == FAILED
    clock.advance(3600.0)
    sup.poll()
    assert sup.state(0) == FAILED
    assert len(spawned) == 1 + CFG.restart_budget
    assert sup.worker(0) is None


def test_rolling_window_budget_half_closes_after_storm_ages_out():
    """With restart_window_s configured, FAILED is a cool-down, not a
    grave: only restarts inside the rolling window count against the
    budget, so once the crash storm ages out the breaker half-closes
    and the slot respawns on its own — no operator in the loop."""
    cfg = SupervisorConfig(restart_base_s=0.2, restart_factor=2.0,
                           restart_max_s=10.0, restart_jitter=0.0,
                           restart_budget=3, restart_window_s=300.0)
    sup, clock, spawned = make_sup(cfg=cfg)
    for _ in range(cfg.restart_budget):       # deaths at t=0, 60, 120
        spawned[-1].die()
        sup.poll()
        clock.advance(60.0)
        sup.poll()
        assert sup.state(0) == RUNNING
    spawned[-1].die()                         # 4th death inside window
    sup.poll()
    assert sup.state(0) == FAILED and sup.worker(0) is None
    # still inside the window: the breaker stays open, nothing spawns
    clock.advance(100.0)                      # t=280; oldest was t=0
    sup.poll()
    assert sup.state(0) == FAILED
    assert len(spawned) == 1 + cfg.restart_budget
    # the t=0 restart leaves the 300 s window: half-close and rejoin
    clock.advance(30.0)                       # t=310
    sup.poll()
    assert sup.state(0) == BACKOFF
    sup.poll()                                # due immediately
    assert sup.state(0) == RUNNING
    assert len(spawned) == 2 + cfg.restart_budget


def test_revive_escape_hatch_resets_budget_and_respawns():
    """revive(slot) is the operator's override for a lifetime-budget
    FAILED slot: back in play NOW with a FRESH budget (a revive that
    instantly re-tripped would be no escape), lifetime restart
    telemetry preserved. A no-op on any non-FAILED slot."""
    sup, clock, spawned = make_sup()
    sup.revive(0)                             # no-op on a live slot
    assert sup.state(0) == RUNNING
    for _ in range(CFG.restart_budget + 1):
        spawned[-1].die()
        sup.poll()
        clock.advance(60.0)
        sup.poll()
    assert sup.state(0) == FAILED
    lifetime = sup.restarts[0]
    assert lifetime == CFG.restart_budget
    clock.advance(3600.0)                     # no window: FAILED stays
    sup.poll()
    assert sup.state(0) == FAILED
    sup.revive(0)
    assert sup.state(0) == BACKOFF
    sup.poll()                                # due immediately
    assert sup.state(0) == RUNNING
    assert len(spawned) == 2 + CFG.restart_budget
    assert sup.restarts[0] == lifetime        # telemetry preserved
    # the budget really is fresh: the next death restarts, no re-trip
    spawned[-1].die()
    sup.poll()
    assert sup.state(0) == BACKOFF
    clock.advance(60.0)
    sup.poll()
    assert sup.state(0) == RUNNING


def test_spawn_failure_consumes_budget_and_reschedules():
    """A spec that cannot boot must walk the same backoff->budget->
    FAILED path as a crash loop, not spin forever."""
    boots = []

    def flaky_spawn(spec):
        boots.append(1)
        raise RuntimeError("no ready line")

    clock = FakeClock()
    sup = Supervisor([SPEC], CFG, spawn_fn=flaky_spawn,
                     spawn_in_thread=False, clock=clock)
    # start() itself failing is the caller's problem; enter the loop
    # with a worker that dies immediately instead
    ok = FakeWorker(SPEC)
    sup.workers[0] = ok
    sup.states[0] = RUNNING
    ok.die()
    while sup.state(0) not in (FAILED,):
        sup.poll()
        clock.advance(60.0)
    assert sup.state(0) == FAILED
    assert len(boots) == CFG.restart_budget


def test_stop_drains_gracefully_and_reaps():
    shutdowns = []

    def handler(op, fields):
        if op == "shutdown":
            shutdowns.append(1)
        return {}

    sup, clock, spawned = make_sup(n=2, handler=handler)

    # graceful workers exit when told to (rpc shutdown -> rc 0)
    def exiting_handler(op, fields):
        out = handler(op, fields)
        if op == "shutdown":
            for w in spawned:
                w.rc = 0
        return out

    for w in spawned:
        w.client.handler = exiting_handler
    sup.stop()
    assert all(w.reaped for w in spawned)
    assert all(sup.state(i) == STOPPED for i in range(2))
    assert len(shutdowns) == 2            # one graceful ask per worker
    assert all(not w.signals for w in spawned)   # never escalated
    assert all(w.client.closed for w in spawned)


# ------------------------------------------------------------- the handle
def make_handle(handler=None, heartbeat_timeout_s=2.0):
    sup, clock, spawned = make_sup(handler=handler)
    h = RemoteReplicaHandle(0, sup, SPEC, clock=clock,
                            heartbeat_timeout_s=heartbeat_timeout_s)
    return h, sup, clock, spawned


def _poll_reply(completions=(), inflight=(), queue=0, active=0):
    return {
        "completions": list(completions), "inflight": list(inflight),
        "watermark": len(completions),
        "stats": {"queue": queue, "active": active, "max_slots": 2,
                  "compile_stats": {"prefill": 1}},
    }


def test_handle_salvage_point_feeds_evacuate():
    """poll refreshes tokens-so-far; a later death evacuates exactly the
    last salvage — the cross-process mirror of Scheduler.evacuate."""
    state = {"inflight": []}

    def handler(op, fields):
        if op == "poll":
            return _poll_reply(inflight=state["inflight"])
        return {"accepted": True}

    h, sup, clock, spawned = make_handle(handler)
    req = Request(rid=7, prompt=[1, 2, 3], max_new_tokens=8,
                  arrival=0.0, trace_id="r7")
    h.submit(req)
    assert 7 in h.outstanding
    state["inflight"] = [{"rid": 7, "tokens": [5, 6], "ftt": 0.5,
                          "phases": {"queue_s": 0.1, "prefill_s": 0.2,
                                     "decode_s": 0.3}}]
    h.step()
    assert h.outstanding[7]["tokens"] == [5, 6]
    # the worker dies for real: step raises, evacuate hands back the
    # ORIGINAL request with the salvaged tokens
    spawned[-1].die()
    with pytest.raises(ReplicaCrashed):
        h.step()
    ev = h.evacuate()
    assert len(ev) == 1
    evreq, tokens, ftt, phases = ev[0]
    assert evreq is req and tokens == [5, 6] and ftt == 0.5
    assert phases["decode_s"] == 0.3
    assert h.outstanding == {}


def test_handle_completion_consumption_clears_outstanding():
    comp = {"rid": 3, "tokens": [9, 9], "status": "length",
            "arrival": 0.0, "finish": 1.0, "ttft": 0.1, "tpot": 0.05,
            "flight": None}
    replies = {"n": 0}

    def handler(op, fields):
        if op == "poll":
            replies["n"] += 1
            return _poll_reply(completions=[comp] if replies["n"] == 1
                               else [])
        return {"accepted": True}

    h, sup, clock, spawned = make_handle(handler)
    h.submit(Request(rid=3, prompt=[1], max_new_tokens=2, arrival=0.0))
    h.step()
    got = h.poll()
    assert [c.rid for c in got] == [3] and got[0].status == "length"
    assert h.outstanding == {}
    assert h.poll() == []  # consume-once


def test_handle_stale_heartbeat_sigkills_and_raises():
    """A worker alive by waitpid but silent on the wire (SIGSTOP) must
    be put down with a REAL SIGKILL once the heartbeat budget runs out
    — silence is death, but only after the budget, so one slow tick
    isn't a failover."""

    def handler(op, fields):
        if op == "poll":
            raise RpcTimeout("stalled")
        return {}

    h, sup, clock, spawned = make_handle(handler, heartbeat_timeout_s=1.0)
    h.step()      # first silent tick: starts the staleness clock
    assert spawned[-1].signals == []
    clock.advance(0.5)
    h.step()      # still inside the budget: no kill, no crash
    assert spawned[-1].signals == []
    clock.advance(0.6)
    with pytest.raises(ReplicaCrashed, match="stale"):
        h.step()
    assert spawned[-1].signals == ["SIGKILL"]


def test_handle_submit_failure_breaks_on_next_step_and_keeps_request():
    def handler(op, fields):
        if op == "submit":
            raise RpcTimeout("wire down")
        return _poll_reply()

    h, sup, clock, spawned = make_handle(handler)
    req = Request(rid=1, prompt=[1], max_new_tokens=2, arrival=0.0)
    h.submit(req)
    with pytest.raises(ReplicaCrashed):
        h.step()
    assert [t[0] for t in h.evacuate()] == [req]


def test_handle_probe_and_restart_resync():
    """probe_ok needs a RUNNING process that answers ping; restart()
    resets the watermark to the new process's empty completions."""
    h, sup, clock, spawned = make_handle(
        lambda op, fields: _poll_reply() if op == "poll" else {}
    )
    h.step()
    h.consumed = 17
    spawned[-1].die()
    with pytest.raises(ReplicaCrashed):
        h.step()
    assert not h.probe_ok(clock.now())     # corpse: no process
    # supervisor brings a replacement up after the backoff
    clock.advance(60.0)
    sup.poll()
    assert sup.state(0) == RUNNING
    assert h.probe_ok(clock.now())
    h.restart()
    assert h.consumed == 0 and h.heartbeat_age() == 0.0


def test_fleet_fault_driver_fires_each_kill_once_in_order():
    """`kill` specs fire at their at_s edge, exactly once, through the
    injected kill_fn — and never leak into the per-scheduler injector
    (they target processes, not schedulers)."""
    plan = FaultPlan([
        FaultSpec(kind="kill", at_s=2.0, replica=1, sig="SIGSTOP"),
        FaultSpec(kind="kill", at_s=1.0, replica=0),
    ])
    fired = []
    drv = FleetFaultDriver(plan, lambda r, s: fired.append((r, s)))
    drv.poll(0.5)
    assert fired == [] and not drv.done
    drv.poll(1.0)
    assert fired == [(0, "SIGKILL")]
    drv.poll(5.0)   # a LATE poll still fires everything due
    assert fired == [(0, "SIGKILL"), (1, "SIGSTOP")] and drv.done
    drv.poll(9.0)
    assert len(fired) == 2          # once means once
    # kill specs never reach a scheduler's fault hook
    assert plan.injector(0) is None and plan.injector(1) is None
    # and they survive the JSON round trip like every other fault kind
    plan2 = FaultPlan.from_json(plan.to_json())
    assert [(f.replica, f.sig) for f in plan2.kills()] \
        == [(0, "SIGKILL"), (1, "SIGSTOP")]
    with pytest.raises(ValueError, match="signal"):
        FaultSpec(kind="kill", sig="SIGWINCH")


def test_fleet_targets_shape():
    h, sup, clock, spawned = make_handle(
        lambda op, fields: _poll_reply() if op == "poll" else {}
    )
    h.step()
    t = fleet_targets(sup, [h])
    assert t[0]["up"] and t[0]["pid"] == spawned[-1].pid
    assert t[0]["port"] == spawned[-1].telemetry_port
    assert t[0]["heartbeat_age_s"] == 0.0
    spawned[-1].die()
    sup.poll()
    t = fleet_targets(sup, [h])
    assert not t[0]["up"] and t[0]["pid"] is None
    assert t[0]["state"] in (BACKOFF, SPAWNING)


# ------------------------------------------------- elastic actuators
class DrainingWorker(FakeWorker):
    """A FakeWorker that honors SIGTERM as a REQUEST, not a death:
    only SIGKILL fells it, so the DRAINING window is observable (the
    harness FakeWorker drops dead on SIGTERM, which pins the fast path
    but hides the deadline machinery)."""

    def kill_signal(self, sig):
        self.signals.append(sig)
        if sig == "SIGKILL":
            self.rc = -9


def make_sup_draining(n=1, handler=None, cfg=None):
    spawned = []

    def spawn(spec):
        w = DrainingWorker(spec, handler)
        spawned.append(w)
        return w

    clock = FakeClock(step_s=0.01)
    sup = Supervisor([SPEC] * n, cfg or CFG, spawn_fn=spawn,
                     spawn_in_thread=False, clock=clock)
    sup.start()
    return sup, clock, spawned


def test_shrink_running_drains_rpc_then_sigterm_no_budget():
    """shrink() of a RUNNING slot: drain rpc first (refusals start even
    if signal delivery lags), then SIGTERM -> DRAINING; the exit is
    retired to STOPPED with zero budget charge and zero respawn."""
    sup, clock, spawned = make_sup(n=2)
    assert sup.active_slots() == 2
    assert sup.shrink(1) == DRAINING
    w = spawned[1]
    assert ("drain", {"timeout_s": 1.0, "retries": 0}) in w.client.calls
    assert w.signals == ["SIGTERM"]
    # a DRAINING worker is still a live process to the handle's eyes
    assert sup.worker(1) is w and not sup.alive(1)
    assert sup.draining(1) and sup.active_slots() == 1
    # the harness FakeWorker exits on SIGTERM: next poll retires it
    sup.poll()
    assert sup.state(1) == STOPPED and w.reaped
    assert sup.worker(1) is None
    # an intentional goodbye is not a crash: no budget, no respawn
    assert sup.restarts[1] == 0 and sup._budget_used[1] == 0
    clock.advance(3600.0)
    sup.poll()
    assert sup.state(1) == STOPPED and len(spawned) == 2
    # slot 0 untouched throughout
    assert sup.state(0) == RUNNING


def test_shrink_draining_deadline_escalates_to_sigkill():
    """A drain that never converges is put down at shrink_kill_after_s
    — and the SIGKILLed corpse still retires to STOPPED, not BACKOFF."""
    cfg = SupervisorConfig(restart_base_s=0.2, restart_jitter=0.0,
                           restart_budget=3, shrink_kill_after_s=5.0)
    sup, clock, spawned = make_sup_draining(cfg=cfg)
    sup.shrink(0)
    w = spawned[0]
    assert sup.state(0) == DRAINING and w.signals == ["SIGTERM"]
    clock.advance(4.9)
    sup.poll()                      # inside the grace window: no kill
    assert sup.state(0) == DRAINING and w.signals == ["SIGTERM"]
    clock.advance(0.2)
    sup.poll()                      # past the deadline: SIGKILL
    assert w.signals == ["SIGTERM", "SIGKILL"]
    sup.poll()                      # corpse collected
    assert sup.state(0) == STOPPED and w.reaped
    assert sup.restarts[0] == 0 and sup._budget_used[0] == 0


def test_shrink_chaos_sigkill_mid_drain_is_not_a_crash():
    """Chaos SIGKILLs the worker WHILE it drains: the slot must retire
    to STOPPED — a draining slot that respawned would undo the
    scale-down, and a budget charge would punish an intentional act."""
    sup, clock, spawned = make_sup_draining()
    sup.shrink(0)
    assert sup.state(0) == DRAINING
    spawned[0].die(rc=-9)           # external SIGKILL, not ours
    sup.poll()
    assert sup.state(0) == STOPPED and spawned[0].reaped
    assert sup.restarts[0] == 0 and sup._budget_used[0] == 0
    clock.advance(3600.0)
    sup.poll()
    assert sup.state(0) == STOPPED and len(spawned) == 1


def test_shrink_backoff_cancels_pending_respawn_without_budget():
    """Satellite pin: shrink() of a slot sitting in BACKOFF cancels the
    scheduled respawn outright — the slot goes STOPPED, the backoff
    timer never fires, and the budget ledger is exactly what the crash
    alone made it."""
    sup, clock, spawned = make_sup()
    spawned[-1].die()
    sup.poll()
    assert sup.state(0) == BACKOFF
    used_before = sup._budget_used[0]
    restarts_before = sup.restarts[0]
    assert sup.shrink(0) == STOPPED
    clock.advance(3600.0)           # way past every backoff delay
    sup.poll()
    assert sup.state(0) == STOPPED and len(spawned) == 1
    assert sup._budget_used[0] == used_before
    assert sup.restarts[0] == restarts_before


def test_shrink_spawning_cancels_inflight_attempt():
    """Satellite pin: shrink() of a slot whose RESPAWN is in flight on
    a spawn thread flags the attempt; _collect_spawn reaps the fresh
    worker instead of seating it, and the cancellation itself charges
    no budget beyond what the original crash already did."""
    import threading
    import time as _time

    release = threading.Event()
    spawned = []

    def spawn(spec):
        if spawned:                     # first boot is synchronous
            release.wait(5.0)
        w = FakeWorker(spec)
        spawned.append(w)
        return w

    clock = FakeClock(step_s=0.01)
    sup = Supervisor([SPEC], CFG, spawn_fn=spawn,
                     spawn_in_thread=True, clock=clock)
    sup.start()
    assert sup.state(0) == RUNNING
    spawned[0].die()
    sup.poll()                          # death -> BACKOFF (1 budget)
    clock.advance(60.0)
    sup.poll()                          # due -> SPAWNING, blocked
    assert sup.state(0) == SPAWNING
    used = sup._budget_used[0]
    rest = sup.restarts[0]
    assert sup.shrink(0) == SPAWNING    # stays until the attempt lands
    assert sup.active_slots() == 1      # still in the pipeline... just
    release.set()
    deadline = _time.monotonic() + 5.0
    while sup.state(0) == SPAWNING and _time.monotonic() < deadline:
        sup.poll()
        _time.sleep(0.005)
    assert sup.state(0) == STOPPED
    assert len(spawned) == 2
    assert spawned[1].reaped            # born cancelled, reaped
    assert sup.worker(0) is None
    assert sup._budget_used[0] == used and sup.restarts[0] == rest
    assert sup.active_slots() == 0


def test_grow_appends_warm_and_cold_slots():
    """grow() is append-only: a warm standby seats RUNNING immediately
    (promotion is a list append, not a spawn); a cold grow rides the
    normal BACKOFF->spawn pipeline due NOW, with zero budget charge."""
    sup, clock, spawned = make_sup()
    warm = FakeWorker(SPEC)
    slot = sup.grow(SPEC, worker=warm)
    assert slot == 1
    assert sup.state(1) == RUNNING and sup.worker(1) is warm
    assert sup.active_slots() == 2 and len(spawned) == 1  # no spawn
    cold = sup.grow(SPEC)
    assert cold == 2 and sup.state(2) == BACKOFF
    sup.poll()                          # due immediately
    assert sup.state(2) == RUNNING and len(spawned) == 2
    assert sup.restarts[2] == 0 and sup._budget_used[2] == 0
    # slot ids are stable: shrink leaves a tombstone, never a hole
    sup.shrink(1)
    sup.poll()
    assert sup.state(1) in (DRAINING, STOPPED)
    assert sup.grow(SPEC, worker=FakeWorker(SPEC)) == 3


def test_shrink_out_of_range_raises():
    sup, clock, spawned = make_sup()
    with pytest.raises(ValueError, match="shrink targets slot 5"):
        sup.shrink(5)


def test_fleet_targets_reports_draining_and_kv():
    """Federated labels survive a scale-down: a DRAINING slot is still
    a target (its last heartbeats matter) but flagged so the verdict
    and tools/check_fleet.py can skip it; kv summaries ride along."""
    h, sup, clock, spawned = make_handle(
        lambda op, fields: _poll_reply() if op == "poll" else {}
    )
    h.step()
    t = fleet_targets(sup, [h])
    assert t[0]["draining"] is False
    assert "kv" in t[0]
    sup.shrink(0)
    t = fleet_targets(sup, [h])
    assert t[0]["draining"] is True


# ------------------------------------------------------------ chip lease
def test_worker_platform_is_named_or_resolved(monkeypatch):
    """The launcher pins every child to ONE platform: the spec's, else
    $JAX_PLATFORMS (conftest exports cpu, so the fleet tests stay on the
    CPU by name), else what this host gives a JAX process."""
    from ddp_practice_tpu.serve import supervisor

    assert supervisor.worker_platform(WorkerSpec()) == "cpu"
    assert supervisor.worker_platform(WorkerSpec(platform="tpu")) == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert supervisor.worker_platform(WorkerSpec()) == "cpu"  # this host
    # a CPU launcher holds no chip
    assert supervisor._chip_holder() is None


def test_spawn_refuses_a_chip_somebody_holds(monkeypatch):
    """A chip belongs to one process: a worker that needs the
    accelerator while the launcher (or another worker) holds it is
    refused before it boots — it must never come up on the CPU beside a
    launcher on the TPU and be measured against it."""
    import subprocess

    from ddp_practice_tpu.serve import supervisor

    def no_popen(*a, **kw):
        raise AssertionError("the worker process was started")

    monkeypatch.setattr(subprocess, "Popen", no_popen)
    monkeypatch.setattr(
        supervisor, "_chip_holder", lambda: "this launcher process (pid 1)"
    )
    with pytest.raises(RuntimeError, match="one process at a time"):
        supervisor.spawn_worker(WorkerSpec(platform="tpu"))
