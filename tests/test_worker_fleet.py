"""Cross-process fleet e2e: REAL worker OS processes, REAL signals.

These are the teeth of the chaos story: everything the in-process
router proved against simulated crashes (serve/faults.py `crash`) is
re-proven here against actual process death — a SIGKILL mid-decode and
a SIGSTOP that leaves the process alive but silent. Every test spawns
real workers (jax import + engine warmup each, ~15 s/worker on this
one-core image), so everything here is `slow`; the signal-delivering
ones are `chaos` too. The host-pure halves of the same machinery live
in tests/test_worker_supervisor.py / test_worker_rpc.py.

Token-identity pins use one retry (`_tolerate_load_flake` idiom,
tests/test_serve_equivalence.py): this image's XLA CPU can flip a
near-tied greedy argmax between process runs under load — a real
divergence bug fails both attempts.
"""

import time

import numpy as np
import pytest

from ddp_practice_tpu.serve.engine import EngineConfig
from ddp_practice_tpu.serve.router import RouterConfig, make_router
from ddp_practice_tpu.serve.scheduler import (
    MonotonicClock,
    Request,
    Scheduler,
)
from ddp_practice_tpu.serve.supervisor import (
    RUNNING,
    SupervisorConfig,
    live_worker_pids,
    make_fleet_router,
)
from ddp_practice_tpu.serve.worker import WorkerSpec, build_model
from ddp_practice_tpu.utils.trace import ROUTER_PID, TraceRecorder

pytestmark = pytest.mark.slow

MODEL_KW = {"vocab_size": 64, "max_len": 64, "hidden_dim": 64,
            "depth": 2, "num_heads": 4, "mlp_dim": 128,
            "pos_emb": "rope"}
ENGINE_KW = {"max_slots": 2, "max_len": 64, "prompt_buckets": [8, 16],
             "temperature": 0.0, "decode_burst": 4, "eos_id": None}
SPEC = WorkerSpec(model=MODEL_KW, engine=ENGINE_KW, max_queue=64)
SUP_CFG = SupervisorConfig(restart_base_s=0.25, restart_budget=5,
                           ready_timeout_s=300.0)


def _trace(n=6, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(3, 9))
        out.append({
            "rid": i,
            "prompt": rng.integers(1, 64, plen).tolist(),
            "max_new_tokens": int(rng.integers(5, 9)),
        })
    return out


def _expected_tokens(trace):
    """Greedy oracle: the same model served by one in-process scheduler
    (token identity is slot/batch-composition independent — pinned
    since PR 1)."""
    model, params = build_model(MODEL_KW)
    eng_kw = dict(ENGINE_KW)
    eng_kw["prompt_buckets"] = tuple(eng_kw["prompt_buckets"])
    from ddp_practice_tpu.serve.engine import PagedEngine

    engine = PagedEngine(model, params, EngineConfig(**eng_kw))
    sched = Scheduler(engine, max_queue=64)
    for t in trace:
        sched.submit(Request(**t))
    comps = sched.run_until_idle()
    assert all(c.status == "length" for c in comps)
    return {c.rid: list(c.tokens) for c in comps}, (model, params)


def _tolerate_load_flake(attempt, tries=2):
    for i in range(tries):
        try:
            return attempt()
        except AssertionError:
            if i == tries - 1:
                raise


# --------------------------------------------------- identity, no faults
def test_fleet_matches_inprocess_router_token_identity():
    """The RPC seam must be invisible to results: the same trace through
    2 worker PROCESSES and through the in-process 2-replica router
    yields identical greedy tokens, every request terminal."""

    def attempt():
        trace = _trace()
        expected, (model, params) = _expected_tokens(trace)
        router, sup, handles = make_fleet_router(
            SPEC, 2, sup_config=SUP_CFG
        )
        try:
            for t in trace:
                router.submit(Request(**t))
            comps = router.run_until_idle()
        finally:
            sup.stop()
        by_rid = {c.rid: c for c in comps}
        assert set(by_rid) == {t["rid"] for t in trace}
        assert all(c.status == "length" for c in by_rid.values())
        for rid, want in expected.items():
            assert by_rid[rid].tokens == want, f"rid {rid} diverged"
        # the work actually spread over both processes (least-loaded)
        dispatched = [len(h._stats) > 0 for h in handles]
        assert all(dispatched)
        # in-process router agreement rides the same oracle: both equal
        # `expected` => equal to each other
        eng_kw = dict(ENGINE_KW)
        eng_kw["prompt_buckets"] = tuple(eng_kw["prompt_buckets"])
        inproc = make_router(model, params, 2, EngineConfig(**eng_kw),
                             clock=MonotonicClock(), max_queue=64,
                             config=RouterConfig())
        inproc.warmup()
        for t in trace:
            inproc.submit(Request(**t))
        in_comps = inproc.run_until_idle()
        assert {c.rid: c.tokens for c in in_comps
                if c.status == "length"} == expected

    _tolerate_load_flake(attempt)


# --------------------------------------------- THE acceptance: SIGKILL
@pytest.mark.chaos
def test_sigkill_mid_decode_failover_restart_and_readmission():
    """ISSUE 7 acceptance: SIGKILL one of two workers mid-decode —
    zero lost requests, survivor output greedy token-identical to the
    fault-free oracle with the ORIGINAL trace_id on the failover
    timeline, and the killed worker is respawned by the supervisor
    (backoff) and readmitted to dispatch only after a passing health
    probe."""

    def attempt():
        trace = _trace(n=6, seed=5)
        expected, _ = _expected_tokens(trace)
        tracer = TraceRecorder()
        router, sup, handles = make_fleet_router(
            SPEC, 2, sup_config=SUP_CFG, tracer=tracer
        )
        try:
            for t in trace:
                router.submit(Request(**t))
            # run until worker 0 is observably MID-DECODE: its salvage
            # point (tokens-so-far from the heartbeat poll) is non-empty
            deadline = time.monotonic() + 60
            while not any(st["tokens"]
                          for st in handles[0].outstanding.values()):
                assert time.monotonic() < deadline, "never saw decode"
                router.step()
            victim_rids = sorted(handles[0].outstanding)
            assert victim_rids, "nothing in flight on worker 0"
            pid0 = sup.worker(0).pid
            sup.kill(0, "SIGKILL")                 # the real thing
            comps = router.run_until_idle()
            # ---- zero lost, token-identical, original trace_id
            by_rid = {c.rid: c for c in comps}
            assert set(by_rid) == {t["rid"] for t in trace}
            assert all(c.status == "length" for c in by_rid.values())
            for rid, want in expected.items():
                assert by_rid[rid].tokens == want, f"rid {rid} diverged"
            migrated = [rid for rid in victim_rids
                        if by_rid[rid].flight["failovers"] >= 1]
            assert migrated, "the kill migrated nothing"
            events = tracer.to_chrome_trace()["traceEvents"]
            for rid in migrated:
                fo = [e for e in events
                      if e["ph"] == "i" and e["name"] == "failover"
                      and e["args"].get("trace_id") == f"r{rid}"]
                assert fo and all(e["pid"] == ROUTER_PID for e in fo)
            # ---- supervisor restart with backoff + health-probe gate
            deadline = time.monotonic() + 240
            while router.states()[0] != "healthy":
                assert time.monotonic() < deadline, (
                    f"worker 0 never readmitted: sup={sup.state(0)} "
                    f"router={router.states()}"
                )
                router.step()
                time.sleep(0.05)
            assert sup.restarts[0] >= 1
            assert sup.state(0) == RUNNING
            assert sup.worker(0).pid != pid0       # a NEW process
            # ---- readmitted to dispatch: healthy + least-loaded wins
            router.submit(Request(rid=999, prompt=[1, 2, 3],
                                  max_new_tokens=4))
            assert 999 in handles[0].outstanding   # it went to worker 0
            tail = router.run_until_idle()
            assert {c.rid: c.status for c in tail}[999] == "length"
        finally:
            sup.stop()

    _tolerate_load_flake(attempt)


# ------------------------------------------------------------- SIGSTOP
@pytest.mark.chaos
def test_sigstop_stale_heartbeat_put_down_and_failover():
    """SIGSTOP leaves the process alive by waitpid but silent on the
    wire: the handle's heartbeat budget must detect the zombie, SIGKILL
    it for real, fail its work over, and let the supervisor restart it
    — with every request still terminal."""
    trace = _trace(n=4, seed=9)
    router, sup, handles = make_fleet_router(
        SPEC, 2, sup_config=SUP_CFG, heartbeat_timeout_s=1.0
    )
    try:
        for t in trace:
            router.submit(Request(**t))
        deadline = time.monotonic() + 60
        while not handles[0].outstanding:
            assert time.monotonic() < deadline
            router.step()
        pid0 = sup.worker(0).pid
        sup.kill(0, "SIGSTOP")
        comps = router.run_until_idle()
        by_rid = {c.rid: c for c in comps}
        assert set(by_rid) == {t["rid"] for t in trace}
        assert all(c.status == "length" for c in by_rid.values())
        # the zombie was put down with a REAL kill: the pid is gone
        # (reaped by the supervisor), not just suspended
        deadline = time.monotonic() + 30
        while sup.workers[0] is not None \
                and getattr(sup.workers[0], "pid", None) == pid0:
            assert time.monotonic() < deadline
            sup.poll()
            time.sleep(0.05)
        assert pid0 not in live_worker_pids()
    finally:
        sup.stop()
    assert live_worker_pids() == []   # the reaper fixture's invariant,
    #                                   asserted eagerly per test too
