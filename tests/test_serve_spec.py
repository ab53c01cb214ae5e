"""Speculative decoding (serve/spec.py + PagedEngine.step_verify).

The ISSUE-13 acceptance teeth, in three tiers:

- host-pure drafter units (tier-1 fast): the PromptLookupDraft index —
  longest-n priority, recency-wins, truncation at the context end, the
  trailing gram never matching itself — plus the config validations and
  the metrics/telemetry surfaces, none of which need a device;
- engine/scheduler pins (slow): greedy token-IDENTITY spec-vs-plain on
  a shared trace with zero new compiles under churn (the compile_guard
  fixture pins all five jitted-program counters, `verify_compiles`
  included), identity through block-aware preemption on an undersized
  pool, the PR-9 stream contract (a verified run = ONE seq-numbered
  chunk, tools/check_stream verdict clean), and per-request accept
  stats in the flight record;
- chaos (slow+chaos): SIGKILL a spec-enabled worker mid-stream and the
  spliced consumer streams still equal the fault-free plain oracle with
  zero duplicated / zero missing tokens.

Cross-run greedy identity on this image's XLA CPU inherits the
documented near-tie argmax flakiness (see test_serve_equivalence.py
_tolerate_load_flake) — identity pins retry the same trace: a real
verify/rollback bug diverges on every attempt.
"""

import importlib
import json
import types

import numpy as np
import pytest

from ddp_practice_tpu.serve.spec import DraftSource, PromptLookupDraft

VOCAB = 32


# ---------------------------------------------------------------- drafter
def test_drafter_validates_ngram_bounds():
    with pytest.raises(ValueError):
        PromptLookupDraft(ngram_max=0)
    with pytest.raises(ValueError):
        PromptLookupDraft(ngram_max=2, ngram_min=3)
    with pytest.raises(ValueError):
        PromptLookupDraft(ngram_max=3, ngram_min=0)


def test_drafter_basic_lookup_and_trailing_gram_never_self_matches():
    d = PromptLookupDraft(ngram_max=3, ngram_min=1)
    d.begin(0, [5, 6, 7, 5, 6, 7, 5, 6])
    # trailing (7, 5, 6) has one EARLIER occurrence at positions 2..4,
    # whose continuation starts at position 5 — [7, 5, 6] — and the
    # chained re-lookup of the new tail (7, 5, 6) fills the 4th token
    assert d.propose(0, 4) == [7, 5, 6, 7]
    # a context whose trailing gram appears nowhere earlier: no proposal
    d.begin(1, [1, 2, 3, 4])
    assert d.propose(1, 4) == []
    d.end(0)
    d.end(1)
    assert d.propose(0, 4) == []  # unknown slot: hint, not an error


def test_drafter_longest_ngram_wins():
    # trailing 2-gram (9, 2) matches position 2's occurrence; the
    # trailing 1-gram (2) alone would match a later, different spot —
    # the longer context must win
    d = PromptLookupDraft(ngram_max=3, ngram_min=1)
    d.begin(0, [9, 2, 8, 8, 2, 1, 9, 2])
    assert d.propose(0, 2) == [8, 8]


def test_drafter_recency_wins_between_equal_length_matches():
    # (4, 4) occurs twice with different continuations: 0->[7...] and
    # 4->[1...]; the index keeps the most recent, so the draft is [1, 5]
    d = PromptLookupDraft(ngram_max=2, ngram_min=1)
    d.begin(0, [4, 4, 7, 3, 4, 4, 1, 5, 4, 4])
    assert d.propose(0, 2) == [1, 5]


def test_drafter_chains_through_the_context_end():
    # the most recent earlier (5,6,7) match yields only 3 KNOWN
    # continuation tokens before the context ends — chaining re-matches
    # the draft's own tail and keeps going, so a k=4 ask is filled on
    # cyclic text instead of truncating (without chaining a period-p
    # cycle caps every draft at p tokens, and verify's fixed two-apply
    # dispatch never amortizes)
    d = PromptLookupDraft(ngram_max=3, ngram_min=1)
    d.begin(0, [5, 6, 7, 5, 6, 7, 5, 6, 7])
    assert d.propose(0, 4) == [5, 6, 7, 5]
    # no match at all still means no draft — chaining never invents one
    d.begin(1, [1, 2, 3])
    assert d.propose(1, 4) == []


def test_drafter_incremental_extend_equals_bulk_begin():
    rng = np.random.default_rng(0)
    ctx = rng.integers(0, 8, 40).tolist()
    bulk = PromptLookupDraft(3, 1)
    bulk.begin(0, ctx)
    inc = PromptLookupDraft(3, 1)
    inc.begin(0, ctx[:5])
    for t in ctx[5:]:
        inc.extend(0, [t])
    assert inc.snapshot(0) == bulk.snapshot(0) == ctx
    for k in (1, 3, 6):
        assert inc.propose(0, k) == bulk.propose(0, k)


def test_drafter_begin_resets_and_snapshot_tracks():
    d = PromptLookupDraft(2, 1)
    d.begin(0, [1, 2, 1])
    assert d.context_len(0) == 3
    d.begin(0, [7, 7])   # readmission: a fresh context, no stale grams
    assert d.snapshot(0) == [7, 7]
    # only the new context's (7)->7 gram exists; chaining rides it to k
    assert d.propose(0, 3) == [7, 7, 7]
    assert d.context_len(1) == -1
    # the DraftSource default snapshot (cold fork sibling) is empty
    assert DraftSource.snapshot(d, 0) == []


# ----------------------------------------------------- config validations
def _stub_model():
    return types.SimpleNamespace(pos_emb="rope", max_len=128)


@pytest.mark.parametrize("what", ["worker_spec_paged", "import_kv_slots"])
def test_the_removed_engine_is_refused_by_name(what):
    """There is one engine: the switch that chose between two is refused
    where it can still arrive from outside (a fleet launcher's JSON), and
    the removed pool's module is gone, not left behind as a shim."""
    if what == "worker_spec_paged":
        from ddp_practice_tpu.serve.worker import WorkerSpec

        text = json.dumps({"engine": {"paged": True, "spec_decode": True}})
        with pytest.raises(ValueError, match='"paged"'):
            WorkerSpec.from_json(text)
        assert WorkerSpec.from_json(
            json.dumps({"engine": {"spec_decode": True}})).engine
    else:
        with pytest.raises(ImportError, match="kv_slots"):
            importlib.import_module("ddp_practice_tpu.serve.kv_slots")


def test_paged_engine_validates_spec_config():
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    with pytest.raises(ValueError, match="temperature"):
        PagedEngine(_stub_model(), None,
                    EngineConfig(spec_decode=True, temperature=0.7))
    with pytest.raises(ValueError, match="spec_k"):
        PagedEngine(_stub_model(), None,
                    EngineConfig(spec_decode=True, spec_k=0))


# ----------------------------------------------- metrics/telemetry surface
def test_serve_metrics_export_spec_counters_as_deltas(engine_at_rest):
    from ddp_practice_tpu.serve.metrics import ServeMetrics

    eng = engine_at_rest(spec_drafted_tokens=10, spec_accepted_tokens=6)
    sched = types.SimpleNamespace(queue=[], engine=eng)
    m = ServeMetrics()
    m.on_tick(sched)
    eng.spec_drafted_tokens, eng.spec_accepted_tokens = 25, 14
    m.on_tick(sched)
    snap = m.report()
    assert snap["spec_drafted_tokens_total"] == 25
    assert snap["spec_accepted_tokens_total"] == 14
    # engines without speculation keep the counters at zero, not absent
    plain = engine_at_rest()
    m2 = ServeMetrics()
    m2.on_tick(types.SimpleNamespace(queue=[], engine=plain))
    assert m2.report()["spec_drafted_tokens_total"] == 0


def test_flight_stats_surface_spec_accept_rate():
    from ddp_practice_tpu.utils.telemetry import FlightStats

    fs = FlightStats()
    base = {"queue_s": 0.0, "prefill_s": 0.1, "decode_s": 0.4,
            "stall_s": 0.0}
    comp = types.SimpleNamespace(
        flight=dict(base, spec_drafted=8, spec_accepted=6,
                    spec_accept_rate=0.75),
        ttft=0.2, tpot=0.05, trace_id=None)
    fs.on_completion(comp)
    # mixed window: a non-spec flight lacks the key and must not break
    fs.on_completion(types.SimpleNamespace(
        flight=dict(base), ttft=0.3, tpot=0.06, trace_id=None))
    rep = fs.report()
    assert rep["spec_accept_rate"]["p50"] == 0.75
    assert rep["samples"]["spec_accept_rate"] == [0.75]


# ====================================================== engine-level pins
# everything below compiles real jitted programs; the identity and
# zero-recompile pin is tier-1, the rest full-suite only
slow = pytest.mark.slow


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.models import create_model

    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=128, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _tolerate_load_flake(attempt, args_per_try):
    """Same contract as test_serve_equivalence.py: a deterministic
    verify/rollback bug fails every attempt; only the documented
    XLA-CPU near-tie argmax transient passes a replay."""
    for i, args in enumerate(args_per_try):
        try:
            return attempt(*args)
        except AssertionError:
            if i == len(args_per_try) - 1:
                raise


def _lookup_friendly_trace(rng, n=10):
    """Prompts with internal repetition (the prompt-lookup sweet spot):
    a short motif repeated with noise, so drafts actually fire."""
    out = []
    for i in range(n):
        motif = rng.integers(0, VOCAB, int(rng.integers(2, 4))).tolist()
        reps = int(rng.integers(2, 4))
        prompt = (motif * reps)[: int(rng.integers(4, 9))]
        out.append({
            "rid": i,
            "prompt": prompt,
            "max_new_tokens": int(rng.integers(2, 16)),
        })
    return out


def _run_trace(engine, trace, **sched_kw):
    from ddp_practice_tpu.serve.scheduler import (
        FakeClock,
        Request,
        Scheduler,
    )

    sched = Scheduler(engine, clock=FakeClock(), max_queue=len(trace),
                      **sched_kw)
    for t in trace:
        sched.submit(Request(**t))
    sched.run_until_idle()
    return sched


def _warm(eng):
    from ddp_practice_tpu.serve.engine import warm_engine

    warm_engine(eng)
    return eng


def test_spec_token_identity_and_zero_recompiles(devices, lm,
                                                 compile_guard):
    """THE tentpole pin: the spec-enabled paged engine is greedy
    token-identical to the plain paged engine on a shared scheduler
    trace (churn, EOS releases, verify dispatches and all), with zero
    new compiles after warmup — `verify_compiles` is pinned by the same
    compile_guard as every other program counter."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    model, params = lm

    def attempt(seed):
        trace = _lookup_friendly_trace(np.random.default_rng(seed))
        kw = dict(max_slots=3, prompt_buckets=(8,), eos_id=5,
                  block_size=8, max_blocks_per_slot=6)
        plain = _warm(PagedEngine(model, params, EngineConfig(**kw)))
        spec = _warm(PagedEngine(model, params, EngineConfig(
            spec_decode=True, spec_k=4, **kw)))
        assert spec.compile_stats()["verify_compiles"] == 1
        with compile_guard(plain, spec):
            got_plain = {
                c.rid: (c.status, tuple(c.tokens))
                for c in _run_trace(plain, trace).completions
            }
            got_spec = {
                c.rid: (c.status, tuple(c.tokens))
                for c in _run_trace(spec, trace).completions
            }
        assert got_spec == got_plain
        # the run really speculated: drafts fired and some were accepted
        assert spec.spec_dispatches > 0
        assert spec.spec_drafted_tokens > 0
        assert spec.spec_accepted_tokens > 0
        assert spec.spec_accepted_tokens <= spec.spec_drafted_tokens
        # rejected tails gave their blocks back: pool fully drained
        assert spec.blocks.num_free == spec.blocks.num_blocks - 1

    _tolerate_load_flake(attempt, [(11,), (11,)])


@slow
def test_spec_token_identity_through_preemption(devices, lm):
    """Speculation x block-aware preemption: an UNDERSIZED pool forces
    evictions mid-request; readmission re-prefills prompt + salvaged
    tokens (rebuilding drafter context from scratch) and the final
    streams still match a plain engine with an ample pool."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    model, params = lm

    def attempt(seed):
        trace = _lookup_friendly_trace(np.random.default_rng(seed), n=8)
        plain = _warm(PagedEngine(model, params, EngineConfig(
            max_slots=3, prompt_buckets=(8,), eos_id=5,
            block_size=8, max_blocks_per_slot=6)))
        spec = _warm(PagedEngine(model, params, EngineConfig(
            max_slots=3, prompt_buckets=(8,), eos_id=5,
            block_size=8, max_blocks_per_slot=6,
            # 6 real blocks for 3 slots x 6: growth (and the verify
            # program's k+1 up-front grow) must preempt under load —
            # chained drafts drain requests fast enough that a merely
            # snug pool never tightens
            num_blocks=7,
            spec_decode=True, spec_k=4)))
        got_plain = {
            c.rid: (c.status, tuple(c.tokens))
            for c in _run_trace(plain, trace).completions
        }
        got_spec = {
            c.rid: (c.status, tuple(c.tokens))
            for c in _run_trace(spec, trace).completions
        }
        assert got_spec == got_plain
        assert spec.preemptions > 0, "pool never tightened — dead pin"
        assert spec.spec_accepted_tokens > 0
        assert spec.blocks.num_free == spec.blocks.num_blocks - 1

    _tolerate_load_flake(attempt, [(7,), (7,)])


@slow
def test_spec_stream_contract_and_flight_records(devices, lm):
    """PR-9 contract with speculation on: a verified run reaches the
    stream as ONE seq-numbered TokenChunk (never one chunk per drafted
    token), offsets are contiguous, exactly one final chunk — the
    tools/check_stream verdict is clean — and every completion that
    drafted carries spec_drafted / spec_accepted / spec_accept_rate in
    its flight record."""
    from tools.check_stream import stream_verdict

    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    model, params = lm
    trace = _lookup_friendly_trace(np.random.default_rng(3), n=8)
    for t in trace:
        t["trace_id"] = f"tid-{t['rid']}"
    spec = _warm(PagedEngine(model, params, EngineConfig(
        max_slots=3, prompt_buckets=(8,), eos_id=5,
        block_size=8, max_blocks_per_slot=6,
        spec_decode=True, spec_k=4)))
    sched = _run_trace(spec, trace, stream=True)

    lines = [{
        "kind": "chunk", "trace_id": c.trace_id, "rid": c.rid,
        "seq": c.seq, "start": c.start, "n": len(c.tokens),
        "final": c.final,
    } for c in sched.chunks]
    ok, report = stream_verdict(lines)
    assert ok, report["violations"]
    assert report["streams"] == len(trace)
    # chunks reassemble to exactly the completion tokens (offset-keyed)
    by_rid = {c.rid: c for c in sched.completions}
    for rid, comp in by_rid.items():
        toks = []
        for ch in sched.chunks:
            if ch.rid == rid:
                assert ch.start == len(toks)
                toks.extend(ch.tokens)
        assert toks == list(comp.tokens)
    # a verified run rode ONE chunk: some chunk carries >1 token even
    # though decode_burst=1 would emit singletons without speculation
    assert spec.config.decode_burst == 1
    assert any(len(c.tokens) > 1 and not c.final for c in sched.chunks)
    # flight records: accept stats present, sane, and consistent with
    # the engine's cumulative counters
    flights = [c.flight for c in sched.completions]
    drafted = sum(f.get("spec_drafted", 0) for f in flights)
    accepted = sum(f.get("spec_accepted", 0) for f in flights)
    assert drafted == spec.spec_drafted_tokens
    assert accepted == spec.spec_accepted_tokens
    assert any("spec_accept_rate" in f for f in flights)
    for f in flights:
        if "spec_accept_rate" in f:
            assert 0.0 <= f["spec_accept_rate"] <= 1.0
            assert f["spec_accepted"] <= f["spec_drafted"]


@slow
def test_spec_respects_eos_inside_verified_run(devices, lm):
    """A verified run that crosses EOS must cut AT the EOS token, same
    as a plain burst: the scheduler walks verify rows through the same
    row loop, so acceptance never overshoots a request's end."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    model, params = lm

    def attempt(seed):
        rng = np.random.default_rng(seed)
        trace = _lookup_friendly_trace(rng, n=10)
        kw = dict(max_slots=3, prompt_buckets=(8,), eos_id=5,
                  block_size=8, max_blocks_per_slot=6)
        plain = _warm(PagedEngine(model, params, EngineConfig(**kw)))
        spec = _warm(PagedEngine(model, params, EngineConfig(
            spec_decode=True, spec_k=4, **kw)))
        got_plain = {c.rid: (c.status, tuple(c.tokens))
                     for c in _run_trace(plain, trace).completions}
        got_spec = {c.rid: (c.status, tuple(c.tokens))
                    for c in _run_trace(spec, trace).completions}
        assert got_spec == got_plain
        assert any(s == "eos" for s, _ in got_plain.values()), \
            "no request hit EOS — the pin pinned nothing"
        for rid, (status, toks) in got_spec.items():
            if status == "eos":
                assert toks[-1] == 5 and 5 not in toks[:-1]

    _tolerate_load_flake(attempt, [(23,), (23,)])


# ================================================= chaos: real SIGKILL
# speculation x process death: spawns real spec-enabled workers
# (test_worker_stream.py idiom) — slow + chaos.

WORKER_MODEL_KW = {"vocab_size": 64, "max_len": 64, "hidden_dim": 64,
                   "depth": 2, "num_heads": 4, "mlp_dim": 128,
                   "pos_emb": "rope"}
WORKER_ENGINE_KW = {"max_slots": 2,
                    "prompt_buckets": [8, 16], "temperature": 0.0,
                    "eos_id": None, "block_size": 8,
                    "max_blocks_per_slot": 8, "decode_burst": 4}


def _worker_trace(n=6, seed=5):
    """Lookup-friendly prompts (repeated motifs) in the worker vocab."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        motif = rng.integers(1, 64, int(rng.integers(2, 4))).tolist()
        prompt = (motif * 3)[: int(rng.integers(5, 9))]
        out.append({
            "rid": i,
            "prompt": prompt,
            "max_new_tokens": int(rng.integers(6, 10)),
        })
    return out


def _plain_oracle(trace):
    """Fault-free PLAIN (non-speculative) greedy oracle, in-process."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler
    from ddp_practice_tpu.serve.worker import build_model

    model, params = build_model(WORKER_MODEL_KW)
    kw = dict(WORKER_ENGINE_KW)
    kw["prompt_buckets"] = tuple(kw["prompt_buckets"])
    engine = PagedEngine(model, params, EngineConfig(**kw))
    sched = Scheduler(engine, max_queue=64)
    for t in trace:
        sched.submit(Request(**t))
    comps = sched.run_until_idle()
    assert all(c.status == "length" for c in comps)
    return {c.rid: list(c.tokens) for c in comps}


@slow
@pytest.mark.chaos
def test_spec_sigkill_failover_exactly_once(tmp_path):
    """SIGKILL a spec-enabled worker mid-stream: every request finishes
    token-identical to the fault-free PLAIN oracle (speculation plus
    crash-migration are both invisible in the stream), consumer splices
    carry zero duplicated / zero missing tokens, migrated requests'
    merged flight records keep their accept stats, and the offline
    tools/check_stream.py audit passes the run's telemetry."""
    import json
    import os
    import subprocess
    import sys
    import time

    from ddp_practice_tpu.serve.scheduler import Request
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec
    from ddp_practice_tpu.utils.telemetry import TelemetryExporter

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wspec = WorkerSpec(model=WORKER_MODEL_KW, engine=WORKER_ENGINE_KW,
                       max_queue=64, spec_decode=True, spec_k=4)
    sup_cfg = SupervisorConfig(restart_base_s=0.25, restart_budget=5,
                               ready_timeout_s=300.0)

    def attempt():
        trace = _worker_trace()
        expected = _plain_oracle(trace)
        tpath = str(tmp_path / "spec_stream.jsonl")
        exporter = TelemetryExporter(tpath, start=False)
        router, sup, handles = make_fleet_router(
            wspec, 2, sup_config=sup_cfg, telemetry=exporter
        )
        try:
            for t in trace:
                router.submit(Request(**t))
            deadline = time.monotonic() + 60
            while not (any(st["tokens"]
                           for st in handles[0].outstanding.values())
                       and any(s.delivered
                               for s in router.streams.values())):
                assert time.monotonic() < deadline, "never saw decode"
                router.step()
            victim_rids = sorted(handles[0].outstanding)
            sup.kill(0, "SIGKILL")
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
                assert st.tokens() == want, f"stream {rid} diverged"
                assert st.closed and st.status == "length"
                assert st.suppressed >= 0 and st.gaps == 0
            # the fleet really speculated: the router-merged flight
            # records carry accept stats home over RPC
            drafted = sum(c.flight.get("spec_drafted", 0)
                          for c in by_rid.values())
            assert drafted > 0, "no worker drafted — dead chaos pin"
            for c in by_rid.values():
                if c.flight.get("spec_drafted", 0):
                    assert 0.0 <= c.flight["spec_accept_rate"] <= 1.0
        finally:
            sup.stop()
            exporter.pump()
            exporter.close()
        r = subprocess.run(
            [sys.executable, "tools/check_stream.py", tpath],
            capture_output=True, text=True, cwd=root, timeout=120,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        report = [json.loads(x) for x in open(tpath) if x.strip()]
        assert any(ln.get("kind") == "chunk" for ln in report)

    _tolerate_load_flake(attempt, [(), ()])
