"""Request-lifecycle tracing (utils/trace.py), the Chrome-trace
validator (tools/check_traces.py), the profile_region re-entrancy fix,
and the serve-stack instrumentation — including the ISSUE-4 acceptance
pin: a crash-migrated request's spans on the SURVIVOR replica carry the
original trace_id, and the exported trace is validator-clean.

Everything deterministic: recorder units run on hand-advanced clocks,
the serving integration runs FakeClock replicas with a seeded FaultPlan.
"""

import json
import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from tools.check_traces import validate  # noqa: E402

from ddp_practice_tpu.utils.trace import (  # noqa: E402
    ENGINE_LANE,
    ROUTER_PID,
    SLOT_LANE_BASE,
    TraceRecorder,
    label_replica,
)


class ManualClock:
    def __init__(self, start=0.0):
        self.t = start

    def now(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# --------------------------------------------------------------- recorder
@pytest.mark.fast
def test_recorder_spans_instants_and_export_validate():
    clk = ManualClock()
    r = TraceRecorder(clock=clk)
    r.set_process_name(0, "test")
    with r.span("outer", pid=0, tid=0, step=1):
        clk.advance(0.5)
        with r.span("inner", pid=0, tid=0):
            clk.advance(0.25)
        r.instant("tick", pid=0, tid=0, n=3)
        clk.advance(0.25)
    r.record_async("request", 0.0, 1.0, trace_id="r1", pid=0,
                   attrs={"status": "eos"})
    trace = r.to_chrome_trace()
    assert validate(trace) == []
    events = trace["traceEvents"]
    by = {(e["ph"], e["name"]): e for e in events}
    assert by[("B", "outer")]["ts"] == 0.0
    assert by[("E", "outer")]["ts"] == pytest.approx(1e6)
    assert by[("B", "inner")]["ts"] == pytest.approx(0.5e6)
    assert by[("B", "outer")]["args"]["step"] == 1
    assert by[("i", "tick")]["args"]["n"] == 3
    assert by[("b", "request")]["id"] == "r1"
    assert by[("e", "request")]["ts"] == pytest.approx(1e6)


@pytest.mark.fast
def test_recorder_ring_buffer_bounds_memory():
    r = TraceRecorder(clock=ManualClock(), max_events=16)
    for i in range(1000):
        r.instant(f"e{i}", pid=0)
    assert len(r) == 16
    # the ring keeps the most RECENT window (flight recorder, not archive)
    names = [e["name"] for e in r.to_chrome_trace()["traceEvents"]
             if e["ph"] == "i"]
    assert names == [f"e{i}" for i in range(984, 1000)]


@pytest.mark.fast
def test_disabled_recorder_is_noop():
    r = TraceRecorder(clock=ManualClock(), enabled=False)
    s1 = r.span("a", pid=0)
    s2 = r.span("b", pid=0)
    assert s1 is s2  # the shared null context — no per-span allocation
    with s1:
        pass
    r.instant("x", pid=0)
    r.record_async("request", 0.0, 1.0, trace_id="r0", pid=0)
    assert len(r) == 0
    r.enable()
    r.instant("y", pid=0)
    assert len(r) == 1


@pytest.mark.fast
def test_zero_duration_spans_still_nest_cleanly():
    """FakeClock spans can begin and end at the same instant, and one
    lane can host several of them back to back (slot freed and re-
    admitted inside one tick) — the exporter must still emit matched,
    ordered B/E pairs."""
    clk = ManualClock()
    r = TraceRecorder(clock=clk)
    r.set_process_name(0, "p")
    with r.span("a", pid=0, tid=1):
        pass
    with r.span("b", pid=0, tid=1):
        pass
    # and an enclosing + enclosed pair sharing both endpoints
    r.record_span("outer", 1.0, 1.0, pid=0, tid=2)
    r.record_span("inner", 1.0, 1.0, pid=0, tid=2)
    assert validate(r.to_chrome_trace()) == []


@pytest.mark.fast
def test_recorder_thread_safety_smoke():
    r = TraceRecorder(clock=ManualClock(), max_events=10_000)

    def worker(k):
        for i in range(500):
            with r.span(f"w{k}", pid=k):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(r) == 2000
    for k in range(4):
        r.set_process_name(k, f"w{k}")
    assert validate(r.to_chrome_trace()) == []


@pytest.mark.fast
def test_save_writes_loadable_json(tmp_path):
    r = TraceRecorder(clock=ManualClock())
    r.set_process_name(0, "p")
    with r.span("s", pid=0):
        pass
    path = tmp_path / "t.json"
    r.save(str(path))
    assert validate(json.loads(path.read_text())) == []


# -------------------------------------------------------------- validator
def _meta(pid):
    return {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"p{pid}"}}


@pytest.mark.fast
def test_validator_catches_corruptions():
    def ev(ph, name, ts, pid=0, tid=0, **kw):
        return {"ph": ph, "name": name, "ts": ts, "pid": pid,
                "tid": tid, **kw}

    assert validate([]) != []  # not even an object
    assert validate({"traceEvents": "nope"}) != []
    # unclosed B
    errs = validate({"traceEvents": [_meta(0), ev("B", "a", 1.0)]})
    assert any("unclosed" in e for e in errs)
    # E name mismatch
    errs = validate({"traceEvents": [
        _meta(0), ev("B", "a", 1.0), ev("E", "b", 2.0)]})
    assert any("mismatch" in e for e in errs)
    # unknown pid (no process_name metadata)
    errs = validate({"traceEvents": [
        ev("B", "a", 1.0, pid=7), ev("E", "a", 2.0, pid=7)]})
    assert any("process_name" in e for e in errs)
    # lane ts goes backwards (crossing intervals)
    errs = validate({"traceEvents": [
        _meta(0), ev("B", "a", 5.0), ev("E", "a", 4.0)]})
    assert any("backwards" in e for e in errs)
    # async e without b
    errs = validate({"traceEvents": [_meta(0), ev("e", "r", 1.0, id="x")]})
    assert any("no open b" in e for e in errs)
    # non-finite / negative ts
    errs = validate({"traceEvents": [_meta(0), ev("i", "x", float("nan"))]})
    assert any("finite" in e for e in errs)
    errs = validate({"traceEvents": [_meta(0), ev("i", "x", -1.0)]})
    assert any("negative" in e for e in errs)
    # a clean one for contrast
    assert validate({"traceEvents": [
        _meta(0), ev("B", "a", 1.0), ev("E", "a", 2.0),
        ev("b", "r", 1.0, id="x"), ev("e", "r", 3.0, id="x"),
    ]}) == []


# -------------------------------------------------- profile_region fix
@pytest.fixture
def fake_profiler(monkeypatch):
    """Stub jax.profiler start/stop so the re-entrancy/exception
    contract is testable CPU-safely (no real capture, no trace dirs)."""
    from ddp_practice_tpu.utils import profiling

    calls = {"start": [], "stop": 0, "stop_error": None}

    def start_trace(d):
        if calls["start"] and calls["stop"] < len(calls["start"]):
            raise RuntimeError("profiler already started")
        calls["start"].append(d)

    def stop_trace():
        calls["stop"] += 1
        if calls["stop_error"] is not None:
            raise calls["stop_error"]

    monkeypatch.setattr(profiling.jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(profiling.jax.profiler, "stop_trace", stop_trace)
    monkeypatch.setattr(profiling, "_trace_active", False)
    return calls


@pytest.mark.fast
def test_profile_region_nested_dirs_single_capture(fake_profiler):
    """Nested regions that BOTH pass profile_dir: one start, one stop,
    no 'profiler already started' crash (the inner annotates only)."""
    from ddp_practice_tpu.utils.profiling import profile_region

    with profile_region("outer", profile_dir="/tmp/a"):
        with profile_region("inner", profile_dir="/tmp/b"):
            pass
        with profile_region("inner2", profile_dir="/tmp/c"):
            pass
    assert fake_profiler["start"] == ["/tmp/a"]
    assert fake_profiler["stop"] == 1
    # and a later region can capture again
    with profile_region("next", profile_dir="/tmp/d"):
        pass
    assert fake_profiler["start"] == ["/tmp/a", "/tmp/d"]


@pytest.mark.fast
def test_profile_region_body_exception_not_masked(fake_profiler):
    """The body's exception propagates even when stop_trace ALSO fails
    on the way out (the old finally swallowed the real error)."""
    from ddp_practice_tpu.utils.profiling import profile_region

    fake_profiler["stop_error"] = RuntimeError("flush failed")
    with pytest.raises(ValueError, match="the real bug"):
        with profile_region("r", profile_dir="/tmp/a"):
            raise ValueError("the real bug")
    assert fake_profiler["stop"] == 1  # stop was attempted
    # the failed stop must not wedge later regions into annotate-only
    fake_profiler["stop_error"] = None
    with profile_region("again", profile_dir="/tmp/b"):
        pass
    assert fake_profiler["start"] == ["/tmp/a", "/tmp/b"]


@pytest.mark.fast
def test_profile_region_stop_failure_alone_raises(fake_profiler):
    """With a healthy body, a stop_trace failure is real signal."""
    from ddp_practice_tpu.utils.profiling import profile_region

    fake_profiler["stop_error"] = RuntimeError("flush failed")
    with pytest.raises(RuntimeError, match="flush failed"):
        with profile_region("r", profile_dir="/tmp/a"):
            pass


@pytest.mark.fast
def test_profile_region_externally_started_profiler(fake_profiler):
    """A region opened while something else (train/loop.py's epoch
    window) already drives the profiler annotates only — and does NOT
    stop the capture it doesn't own."""
    from ddp_practice_tpu.utils.profiling import profile_region

    fake_profiler["start"].append("/external")  # simulate foreign capture
    with profile_region("r", profile_dir="/tmp/a"):
        pass
    assert fake_profiler["start"] == ["/external"]
    assert fake_profiler["stop"] == 0


# ------------------------------------------- serving integration (engine)
VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.models import create_model

    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=96, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def test_scheduler_engine_spans_and_flight_records(lm):
    """One FakeClock replica: queued/request lifecycle spans, per-slot
    prefill lanes, decode-burst spans on the engine lane, flight records
    on every completion — and the export is validator-clean."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.scheduler import (
        FakeClock,
        Request,
        Scheduler,
    )

    model, params = lm
    clock = FakeClock(step_s=0.01)
    rec = TraceRecorder(clock=clock)
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=2, prompt_buckets=(4, 8), eos_id=None,
    ))
    engine.set_tracer(rec, 0)
    label_replica(rec, 0, 2)
    sched = Scheduler(engine, clock=clock, tracer=rec, replica=0)
    for rid in range(4):  # 4 requests on 2 slots: two must queue
        sched.submit(Request(rid=rid, prompt=[1, 2, 3],
                             max_new_tokens=4))
    comps = sched.run_until_idle()
    assert len(comps) == 4 and all(c.status == "length" for c in comps)

    # flight records: phases sum to (finish - arrival) by construction
    for c in comps:
        f = c.flight
        assert f is not None and f["retries"] == 0 and f["failovers"] == 0
        total = c.finish - c.arrival
        assert (f["queue_s"] + f["prefill_s"] + f["decode_s"]
                + f["stall_s"]) == pytest.approx(total)
        assert f["decode_s"] > 0
    # slots were contended: the late arrivals actually waited
    assert sum(c.flight["queue_s"] > 0 for c in comps) >= 2

    trace = rec.to_chrome_trace()
    assert validate(trace) == []
    events = trace["traceEvents"]
    prefills = [e for e in events if e["ph"] == "B"
                and e["name"] == "prefill"]
    bursts = [e for e in events if e["ph"] == "B"
              and e["name"] == "decode_burst"]
    assert len(prefills) == 4 and len(bursts) >= 8  # 4 tokens each, K=1
    # lane conventions: prefill on the slot lanes, bursts on the engine
    # lane, every span on this replica's pid
    assert {e["tid"] for e in prefills} <= {SLOT_LANE_BASE,
                                            SLOT_LANE_BASE + 1}
    assert all(e["tid"] == ENGINE_LANE for e in bursts)
    assert all(e["pid"] == 0 for e in prefills + bursts)
    # every request has its lifecycle async track
    req_ids = {e["id"] for e in events if e["ph"] == "b"
               and e["name"] == "request"}
    assert req_ids == {f"r{rid}" for rid in range(4)}
    # prefill spans carry the request's trace_id, and burst spans count
    # the batch occupancy they dispatched with
    assert {e["args"]["trace_id"] for e in prefills} == req_ids
    assert {e["args"]["active"] for e in bursts} <= {1, 2}


def test_tracer_off_records_nothing(lm):
    """tracer=None (the production default) leaves zero records and the
    engines' hot path un-annotated; flight records still attach."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.scheduler import (
        FakeClock,
        Request,
        Scheduler,
    )

    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=2, prompt_buckets=(4,), eos_id=None,
    ))
    sched = Scheduler(engine, clock=FakeClock(step_s=0.01))
    sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    comps = sched.run_until_idle()
    assert comps[0].flight is not None
    assert engine.tracer is None and not engine._slot_trace


def test_evacuate_reports_attempt_phases(lm):
    """The failover harvest carries each attempt's flight fragment —
    a crashed attempt never produces a Completion, so these phases are
    the ONLY record of its pre-crash queue/prefill/decode time (the
    router folds them in; without them the work would misreport as
    stall_s)."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.scheduler import (
        FakeClock,
        Request,
        Scheduler,
    )

    model, params = lm
    clock = FakeClock(step_s=0.01)
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=1, prompt_buckets=(4,), eos_id=None,
    ))
    sched = Scheduler(engine, clock=clock)
    sched.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=8))
    sched.submit(Request(rid=1, prompt=[3, 4], max_new_tokens=8))
    sched.step()  # admits rid 0 (1 slot); rid 1 waits in queue
    sched.step()
    clock.advance(0.05)
    ev = {req.rid: phases for req, _, _, phases in sched.evacuate()}
    assert set(ev) == {0, 1}
    # the running attempt: decoded for two ticks before the "crash"
    assert ev[0]["decode_s"] == pytest.approx(0.02 + 0.05)
    assert ev[0]["queue_s"] == 0.0 and ev[0]["prefill_s"] == 0.0
    # the queued attempt: all its time was queue wait
    assert ev[1]["decode_s"] == 0.0 and ev[1]["prefill_s"] == 0.0
    assert ev[1]["queue_s"] == pytest.approx(0.07)
    assert sched.idle


# --------------------------------- ISSUE-4 acceptance: failover linkage
@pytest.mark.chaos
def test_crash_migrated_request_keeps_trace_id_on_survivor(lm):
    """THE acceptance pin: under a chaos plan that kills replica 0
    mid-decode, the migrated requests' spans on the surviving replica
    carry the ORIGINAL trace_id — one request, one timeline across the
    crash — and the exported Chrome trace is validator-clean."""
    from ddp_practice_tpu.serve import (
        EngineConfig,
        FakeClock,
        FaultPlan,
        FaultSpec,
        Request,
        RouterConfig,
        make_router,
    )

    model, params = lm
    clock = FakeClock(step_s=0.01)
    rec = TraceRecorder(clock=clock)
    plan = FaultPlan([FaultSpec(kind="crash", tick=4, replica=0)])
    router = make_router(
        model, params, 2,
        EngineConfig(max_slots=2, prompt_buckets=(4, 8), eos_id=None),
        clock=clock, config=RouterConfig(seed=5), fault_plan=plan,
        tracer=rec,
    )
    for rid in range(4):
        router.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                              max_new_tokens=8))
    comps = router.run_until_idle()
    assert len(comps) == 4
    assert all(c.status == "length" for c in comps)  # none lost
    migrated = [c for c in comps if c.flight["failovers"] >= 1]
    assert migrated, "the crash must have migrated at least one request"

    trace = rec.to_chrome_trace()
    assert validate(trace) == []
    events = trace["traceEvents"]

    dead = [e["args"]["replica"] for e in events
            if e["ph"] == "i" and e["name"] == "replica_dead"]
    assert dead == [0]
    survivor = 1
    for c in migrated:
        tid = f"r{c.rid}"
        # a failover instant on the router lane names this trace
        fo = [e for e in events if e["ph"] == "i" and e["name"] == "failover"
              and e["args"].get("trace_id") == tid]
        assert fo and all(e["pid"] == ROUTER_PID for e in fo)
        # and the SURVIVOR's prefill + request spans carry the original
        # trace_id: the re-admission joined the same timeline
        surv_prefills = [
            e for e in events if e["ph"] == "B" and e["name"] == "prefill"
            and e["pid"] == survivor
            and e["args"].get("trace_id") == tid
        ]
        assert surv_prefills, f"{tid}: no prefill span on the survivor"
        surv_request = [
            e for e in events if e["ph"] == "b" and e["name"] == "request"
            and e["pid"] == survivor and e["id"] == tid
        ]
        assert surv_request, f"{tid}: no request track on the survivor"
        # the flight record accounts the hop too
        assert c.flight["stall_s"] >= 0.0
    # router dispatch instants recorded the re-placements (>= one per
    # original placement plus one per migration)
    dispatches = [e for e in events
                  if e["ph"] == "i" and e["name"] == "dispatch"]
    assert len(dispatches) >= 4 + len(migrated)
    # token identity with a fault-free run is pinned in
    # tests/test_serve_router.py; here the TRACE is the contract


@pytest.mark.slow
def test_train_trace_out_records_step_phases(tmp_path):
    """`cli.py ... --trace-out`: the training driver's host-side phases
    (data / dispatch / block / checkpoint) land in a validator-clean
    Chrome trace."""
    from ddp_practice_tpu import cli

    out = tmp_path / "train.json"
    assert cli.main([
        "--model", "lm_tiny", "--dataset", "synthetic_tokens",
        "--seq_len", "48", "-e", "1", "-b", "4", "--max_steps", "6",
        "--log_every", "3", "--ckpt_dir", str(tmp_path / "ck"),
        "--trace-out", str(out),
    ]) == 0
    trace = json.loads(out.read_text())
    assert validate(trace) == []
    spans = [e["name"] for e in trace["traceEvents"] if e["ph"] == "B"]
    assert spans.count("dispatch") == 6 and spans.count("data") == 6
    assert "block" in spans and "checkpoint" in spans
