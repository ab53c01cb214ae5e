"""Streaming delivery across REAL worker processes and REAL signals.

The ISSUE-10 acceptance teeth: chunks ride the worker push stream
(inside `pub` frames, atomically with the inflight salvage point), the
router splices them into per-request TokenStreams, and a SIGKILL
mid-stream produces a `resumed` marker — never a duplicated and never
a missing token. The host-pure halves (dedup cursor, typed ends,
check_stream) live in tests/test_zstream.py; this file proves the
same contract against actual process death, plus the graceful-SIGTERM
drain (satellite: a draining worker finishes its in-flight streams
with NO resume marker while refusing new submits).

Everything spawns real workers (~15 s each on this one-core image):
all `slow`, signal-delivering tests also `chaos`.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from ddp_practice_tpu.serve.engine import EngineConfig
from ddp_practice_tpu.serve.scheduler import Request, Scheduler
from ddp_practice_tpu.serve.supervisor import (
    SupervisorConfig,
    live_worker_pids,
    make_fleet_router,
)
from ddp_practice_tpu.serve.worker import WorkerSpec, build_model
from ddp_practice_tpu.utils.telemetry import TelemetryExporter

pytestmark = pytest.mark.slow

MODEL_KW = {"vocab_size": 64, "max_len": 64, "hidden_dim": 64,
            "depth": 2, "num_heads": 4, "mlp_dim": 128,
            "pos_emb": "rope"}
ENGINE_KW = {"max_slots": 2, "max_len": 64, "prompt_buckets": [8, 16],
             "temperature": 0.0, "decode_burst": 4, "eos_id": None}
SPEC = WorkerSpec(model=MODEL_KW, engine=ENGINE_KW, max_queue=64)
SUP_CFG = SupervisorConfig(restart_base_s=0.25, restart_budget=5,
                           ready_timeout_s=300.0)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    """Fault-free greedy oracle: one in-process scheduler, same model."""
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
    return {c.rid: list(c.tokens) for c in comps}


def _tolerate_load_flake(attempt, tries=2):
    for i in range(tries):
        try:
            return attempt()
        except AssertionError:
            if i == tries - 1:
                raise


def _recount(stream):
    """Consumer-side recount, independent of the router's cursors:
    (dupes, gaps) over the delivered token offsets."""
    dupes = gaps = delivered = 0
    for ev in stream.events:
        if ev.kind != "tokens" or not ev.tokens:
            continue
        if ev.start < delivered:
            dupes += delivered - ev.start
        elif ev.start > delivered:
            gaps += ev.start - delivered
        delivered = ev.start + len(ev.tokens)
    return dupes, gaps


# --------------------------------------------- THE acceptance: SIGKILL
@pytest.mark.chaos
def test_sigkill_mid_stream_exactly_once(tmp_path):
    """SIGKILL one of two workers while its streams are mid-flight:
    every stream's concatenation is token-identical to the fault-free
    greedy oracle, seq is contiguous, the recounted duplicate/missing
    token totals are zero, resumed markers carry the ORIGINAL trace_id,
    and tools/check_stream.py passes the run's telemetry (and fails a
    corrupted copy)."""

    def attempt():
        trace = _trace(n=6, seed=5)
        expected = _expected_tokens(trace)
        tpath = str(tmp_path / "stream_run.jsonl")
        exporter = TelemetryExporter(tpath, start=False)
        router, sup, handles = make_fleet_router(
            SPEC, 2, sup_config=SUP_CFG, telemetry=exporter
        )
        try:
            for t in trace:
                router.submit(Request(**t))
            # mid-STREAM, observably: worker 0 holds in-flight work AND
            # some consumer stream has already delivered tokens
            deadline = time.monotonic() + 60
            while not (any(st["tokens"]
                           for st in handles[0].outstanding.values())
                       and any(s.delivered
                               for s in router.streams.values())):
                assert time.monotonic() < deadline, "never saw decode"
                router.step()
            victim_rids = sorted(handles[0].outstanding)
            sup.kill(0, "SIGKILL")                 # the real thing
            comps = router.run_until_idle()
            by_rid = {c.rid: c for c in comps}
            assert set(by_rid) == {t["rid"] for t in trace}
            assert all(c.status == "length" for c in by_rid.values())
            migrated = [rid for rid in victim_rids
                        if by_rid[rid].flight["failovers"] >= 1]
            assert migrated, "the kill migrated nothing"
            for rid, want in expected.items():
                c = by_rid[rid]
                st = router.stream(rid)
                assert c.tokens == want, f"rid {rid} diverged"
                # the CONSUMER's spliced view equals the oracle too
                assert st.tokens() == want, f"stream {rid} diverged"
                assert st.closed and st.status == "length"
                assert [ev.seq for ev in st.events] \
                    == list(range(len(st.events)))
                dupes, gaps = _recount(st)
                assert dupes == 0 and gaps == 0
                # every event (incl. resumed) keeps the original
                # trace_id — the splice joins ONE timeline
                assert all(ev.trace_id == c.trace_id
                           for ev in st.events)
            resumed = [rid for rid in migrated
                       if any(ev.kind == "resumed"
                              for ev in router.stream(rid).events)]
            assert resumed == migrated, (
                "a migrated stream must carry its resume marker"
            )
            for rid in resumed:
                evs = [ev for ev in router.stream(rid).events
                       if ev.kind == "resumed"]
                assert all(ev.attrs["reason"] == "failover"
                           and ev.attrs["from_replica"] == 0
                           for ev in evs)
        finally:
            sup.stop()
            exporter.pump()
            exporter.close()
        # ---- the offline audit, both ways (the acceptance's last leg)
        r = subprocess.run(
            [sys.executable, "tools/check_stream.py", tpath],
            capture_output=True, text=True, cwd=ROOT, timeout=120,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        lines = [json.loads(x) for x in open(tpath) if x.strip()]
        out, dup = [], None
        for ln in lines:
            out.append(json.dumps(ln))
            if (dup is None and ln.get("kind") == "chunk"
                    and ln.get("event") == "tokens" and ln.get("n")):
                dup = json.dumps(ln)
                out.append(dup)
        assert dup is not None
        bad = tmp_path / "corrupt.jsonl"
        bad.write_text("\n".join(out) + "\n")
        r = subprocess.run(
            [sys.executable, "tools/check_stream.py", str(bad)],
            capture_output=True, text=True, cwd=ROOT, timeout=120,
        )
        assert r.returncode == 1 and "duplicate" in r.stdout

    _tolerate_load_flake(attempt)


# --------------------------------------- graceful drain: real SIGTERM
@pytest.mark.chaos
def test_sigterm_drain_finishes_streams_without_resume():
    """SIGTERM is the GRACEFUL edge: the worker flips to draining —
    refuses new submits at the door (typed, the router just routes
    around it) but finishes its in-flight requests, pushes their final
    chunks, and exits 0. The consumer must see those streams complete
    WITHOUT any resume marker (nothing migrated, nothing re-decoded),
    and later requests land on the survivor."""

    def attempt():
        trace = _trace(n=4, seed=11)
        expected = _expected_tokens(trace)
        router, sup, handles = make_fleet_router(
            SPEC, 2, sup_config=SUP_CFG
        )
        try:
            for t in trace:
                router.submit(Request(**t))
            deadline = time.monotonic() + 60
            while not any(st["tokens"]
                          for st in handles[0].outstanding.values()):
                assert time.monotonic() < deadline, "never saw decode"
                router.step()
            drained_rids = sorted(handles[0].outstanding)
            pid0 = sup.worker(0).pid
            os.kill(pid0, signal.SIGTERM)          # graceful, for real
            # new work while draining: refused at worker 0's door,
            # routed to the survivor, still terminal
            router.submit(Request(rid=100, prompt=[1, 2, 3, 4],
                                  max_new_tokens=5))
            comps = router.run_until_idle()
            by_rid = {c.rid: c for c in comps}
            assert set(by_rid) == {t["rid"] for t in trace} | {100}
            assert all(c.status == "length" for c in by_rid.values())
            for rid, want in expected.items():
                assert by_rid[rid].tokens == want, f"rid {rid} diverged"
                assert router.stream(rid).tokens() == want
            # the drained worker FINISHED its streams in place: closed,
            # token-identical, and no resume marker anywhere on them
            for rid in drained_rids:
                st = router.stream(rid)
                assert st.closed and st.status == "length"
                kinds = [ev.kind for ev in st.events]
                assert "resumed" not in kinds, (
                    f"rid {rid} shows a resume — drain must finish "
                    f"in place, not migrate"
                )
                assert by_rid[rid].flight["failovers"] == 0
            # the refused request never ran on the draining worker
            assert 100 not in drained_rids
            st100 = router.stream(100)
            assert st100.closed and "resumed" not in [
                ev.kind for ev in st100.events]
            # the SIGTERMed process exited of its own accord (exit 0 —
            # drain complete), and is really gone
            deadline = time.monotonic() + 60
            while pid0 in live_worker_pids():
                assert time.monotonic() < deadline, (
                    "drained worker never exited"
                )
                time.sleep(0.1)
        finally:
            sup.stop()
        assert live_worker_pids() == []

    _tolerate_load_flake(attempt)
