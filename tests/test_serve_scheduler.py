"""Scheduler policy (serve/scheduler.py) under a deterministic fake clock.

The acceptance trace: 20+ requests with mixed prompt lengths and an
early-EOS sequence, replayed on virtual time. Pinned: slot REUSE (a
later request occupies a slot an earlier one freed), zero
recompilation churn (jit cache sizes constant after warmup), bounded-
queue shedding, deadline timeouts (queued and running), impossible-
request rejection, and a block pool too small for two requests at once,
which queues with a slot free and still serves everyone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.serve import (
    EngineConfig,
    FakeClock,
    PagedEngine,
    Request,
    Scheduler,
    ServeMetrics,
)

VOCAB = 32
# one prefill bucket and the decode program, nothing else ever compiled
TWO_PROGRAMS = {"prefill_compiles": 1, "decode_compiles": 1,
                "prefix_prefill_compiles": 0, "verify_compiles": 0}


def own_programs(engine) -> dict:
    """The engine's compile counters without `cow_compiles`, which counts
    one jitted function shared by every engine of the process."""
    stats = engine.compile_stats()
    del stats["cow_compiles"]
    return stats


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


def _greedy_eos(lm, prompt, steps=12):
    """Token the one-shot greedy path emits first — used as the trace's
    EOS id so at least one request genuinely stops early."""
    from ddp_practice_tpu.inference import make_generate_fn

    model, params = lm
    gen = jax.jit(make_generate_fn(model, max_new_tokens=steps,
                                   temperature=0.0))
    out = np.asarray(gen(params, jnp.asarray([prompt], jnp.int32)))
    return int(out[0, len(prompt)])


@pytest.mark.slow  # ~18 s: replays the 22-request trace twice
def test_fake_clock_trace_20_requests(devices, lm):
    """The headline trace: deterministic, slot-reusing, compile-stable."""
    model, params = lm
    prompt0 = [3, 1, 4, 1, 5]
    eos = _greedy_eos(lm, prompt0)
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=3, max_len=32, prompt_buckets=(8,), eos_id=eos,
    ))
    metrics = ServeMetrics()
    clock = FakeClock(step_s=0.01)
    sched = Scheduler(engine, clock=clock, max_queue=64, metrics=metrics)

    rng = np.random.default_rng(7)
    n_req = 22
    # request 0 hits EOS on its first decode step (prompt0's greedy
    # continuation IS the eos token); the rest are random mixed lengths
    reqs = [Request(rid=0, prompt=prompt0, max_new_tokens=10)]
    for i in range(1, n_req):
        plen = int(rng.integers(1, 9))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, VOCAB, plen).tolist(),
            max_new_tokens=int(rng.integers(2, 9)),
        ))

    admitted_slots = {}
    orig_admit = engine.admit

    def tracking_admit(prompt, **kw):
        slot = orig_admit(prompt, **kw)
        admitted_slots.setdefault(slot, []).append(clock.now())
        return slot

    engine.admit = tracking_admit

    # feed two requests per tick — arrival interleaves with decode
    i = 0
    warm_stats = None
    while not (i >= n_req and sched.idle):
        for _ in range(2):
            if i < n_req:
                assert sched.submit(reqs[i])
                i += 1
        sched.step()
        if warm_stats is None and len(sched.completions) >= 3:
            warm_stats = engine.compile_stats()  # after warmup

    comps = {c.rid: c for c in sched.completions}
    assert len(comps) == n_req
    # the early-EOS request stopped at one token (the EOS itself)
    assert comps[0].status == "eos" and len(comps[0].tokens) == 1
    assert comps[0].tokens[0] == eos
    # everyone else ran to their own cap or a genuine EOS
    for c in comps.values():
        assert c.status in ("eos", "length")
        assert c.ttft is not None and c.ttft >= 0
    # slot reuse: 22 requests through 3 slots — some slot served many
    assert max(len(v) for v in admitted_slots.values()) >= 2
    assert sum(len(v) for v in admitted_slots.values()) == n_req
    # no recompilation churn: cache sizes after warmup == at the end
    assert warm_stats == engine.compile_stats()
    assert own_programs(engine) == TWO_PROGRAMS
    # replaying the same trace on a fresh engine is bit-identical
    engine2 = PagedEngine(model, params, EngineConfig(
        max_slots=3, max_len=32, prompt_buckets=(8,), eos_id=eos,
    ))
    sched2 = Scheduler(engine2, clock=FakeClock(step_s=0.01), max_queue=64)
    i = 0
    while not (i >= n_req and sched2.idle):
        for _ in range(2):
            if i < n_req:
                sched2.submit(Request(
                    rid=reqs[i].rid, prompt=reqs[i].prompt,
                    max_new_tokens=reqs[i].max_new_tokens,
                ))
                i += 1
        sched2.step()
    comps2 = {c.rid: c for c in sched2.completions}
    for rid in comps:
        assert comps[rid].tokens == comps2[rid].tokens
        assert comps[rid].finish == comps2[rid].finish


def test_queue_bound_sheds(devices, lm):
    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=1, max_len=32, prompt_buckets=(8,),
    ))
    sched = Scheduler(engine, clock=FakeClock(), max_queue=2)
    results = [
        sched.submit(Request(rid=i, prompt=[1, 2], max_new_tokens=4))
        for i in range(5)
    ]
    assert results == [True, True, False, False, False]
    shed = [c for c in sched.completions if c.status == "shed"]
    assert [c.rid for c in shed] == [2, 3, 4]
    sched.run_until_idle()
    ok = [c for c in sched.completions if c.status == "length"]
    assert sorted(c.rid for c in ok) == [0, 1]


def test_deadlines_queued_and_running(devices, lm):
    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=1, max_len=64, prompt_buckets=(8,),
    ))
    clock = FakeClock(step_s=0.01)
    sched = Scheduler(engine, clock=clock, max_queue=8)
    # r0 occupies the single slot for a while; r1's deadline expires in
    # the queue; r2 starts but can't finish before its deadline
    sched.submit(Request(rid=0, prompt=[1], max_new_tokens=30))
    sched.submit(Request(rid=1, prompt=[2], max_new_tokens=4,
                         deadline=clock.now() + 0.05))
    sched.submit(Request(rid=2, prompt=[3], max_new_tokens=50,
                         deadline=clock.now() + 0.35))
    sched.run_until_idle()
    by_rid = {c.rid: c for c in sched.completions}
    assert by_rid[0].status == "length" and len(by_rid[0].tokens) == 30
    assert by_rid[1].status == "timeout" and by_rid[1].tokens == []
    assert by_rid[2].status == "timeout" and 0 < len(by_rid[2].tokens) < 50


def test_impossible_requests_rejected(devices, lm):
    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=1, max_len=24, prompt_buckets=(8,),
    ))
    sched = Scheduler(engine, clock=FakeClock(), max_queue=8)
    sched.submit(Request(rid=0, prompt=list(range(1, 10)),  # > bucket 8
                         max_new_tokens=4))
    sched.submit(Request(rid=1, prompt=[1],
                         max_new_tokens=99))  # > a slot's capacity 32
    sched.submit(Request(rid=2, prompt=[1], max_new_tokens=4))
    # zero/negative token budgets reject at the door (needed=0 would
    # bypass every headroom guard downstream)
    assert not sched.submit(Request(rid=3, prompt=[1], max_new_tokens=0))
    sched.run_until_idle()
    by_rid = {c.rid: c for c in sched.completions}
    assert by_rid[0].status == "rejected"
    assert by_rid[1].status == "rejected"
    assert by_rid[2].status == "length"
    assert by_rid[3].status == "rejected"


def test_exhausted_block_pool_queues_then_serves_every_request(devices, lm):
    """A pool of two blocks holds ONE request's 18 positions: the next
    one waits in the queue although a slot is free (the gate says
    "later": a prompt block and a decode block are not there), nobody is
    preempted, and every request completes correctly as blocks return."""
    from ddp_practice_tpu.inference import make_generate_fn

    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=2, prompt_buckets=(8,),
        block_size=16, max_blocks_per_slot=2, num_blocks=1 + 2,
    ))
    sched = Scheduler(engine, clock=FakeClock(), max_queue=16)
    prompts = [[1 + i, 2, 3] for i in range(6)]
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=10))
    waited_with_a_free_slot = 0
    while not sched.idle:
        sched.step()
        waited_with_a_free_slot += bool(sched.queue and engine.num_free)
    assert waited_with_a_free_slot and engine.preemptions == 0
    assert engine.blocks.num_free == 2 and engine.num_active == 0
    assert len(sched.completions) == 6
    gen = jax.jit(make_generate_fn(model, max_new_tokens=10, temperature=0.0))
    for c in sched.completions:
        assert c.status == "length"
        want = np.asarray(gen(
            params, jnp.asarray([prompts[c.rid]], jnp.int32)
        ))
        assert c.tokens == want[0, len(prompts[c.rid]):].tolist()
    # churn through 6 requests across the waits: still just two programs
    assert own_programs(engine) == TWO_PROGRAMS


# --------------------------------------------------- preemption policy
# Host-pure: the staging/requeue logic runs entirely scheduler-side, so
# a stub engine that always gates "later" exercises it without a
# compile. The engine-side preemption mechanics (blocks actually
# freeing, token identity across evict/readmit) are pinned with real
# engines in tests/test_kv_pages.py and test_serve_equivalence.py.

class _BlockedEngine:
    """Minimal PagedEngine protocol surface for the admit loop: every
    gate says "later", every fair victim can be preempted."""

    class config:
        decode_burst = 1

    num_free = 1
    drafter = None

    def __init__(self, feasible=True):
        self.feasible = feasible
        self.preempts = []

    def admit_gate(self, prompt_len, needed, prompt=None):
        return "later"

    def make_room(self, *a, **k):
        return False

    def preempt_headroom(self, slots, prompt_len, prompt=None):
        return self.feasible and len(slots) > 0

    def preempt(self, slot):
        self.preempts.append(slot)

    def take_preempted(self):
        return []


def _blocked_sched(feasible=True):
    from ddp_practice_tpu.serve.scheduler import _Running

    eng = _BlockedEngine(feasible)
    sched = Scheduler(eng, clock=FakeClock())
    sched.queue.append(
        Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4, arrival=0.0))
    # two running victims, both strictly younger by arrival; slot 7
    # (seq 11) is the youngest-ADMITTED and must be evicted first
    for slot, (rid, arr, seq) in {5: (1, 1.0, 10), 7: (2, 2.0, 11)}.items():
        sched.running[slot] = _Running(
            req=Request(rid=rid, prompt=[rid, rid], max_new_tokens=4,
                        arrival=arr),
            slot=slot, seq=seq)
    return eng, sched


def test_preempted_victims_requeue_in_arrival_order(devices):
    """Multi-victim preemption requeues victims behind the blocked head
    in ARRIVAL order — the older victim readmits first, so it can never
    turn around and (fairly) re-preempt the younger one it now leads."""
    eng, sched = _blocked_sched()
    sched._admit()
    assert eng.preempts == [7, 5]          # youngest-admitted evicts first
    assert not sched.running
    assert [r.rid for r in sched.queue] == [0, 1, 2]   # arrival order


def test_no_preemption_when_it_cannot_admit_the_head(devices):
    """Feasibility gate: when even evicting EVERY fair victim cannot
    surface enough blocks, nobody is preempted — the victims keep their
    decode progress and the head waits for releases."""
    eng, sched = _blocked_sched(feasible=False)
    sched._admit()
    assert eng.preempts == []
    assert sorted(sched.running) == [5, 7]             # untouched
    assert [r.rid for r in sched.queue] == [0]


def test_unfair_high_seq_runner_does_not_shield_fair_victims(devices):
    """A readmitted continuation (fresh high admission seq, ORIGINAL old
    arrival) is skipped, not a reason to bail: the youngest FAIR victim
    behind it is still evicted for an older blocked head."""
    from ddp_practice_tpu.serve.scheduler import _Running

    eng = _BlockedEngine()
    sched = Scheduler(eng, clock=FakeClock())
    sched.queue.append(
        Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4, arrival=2.0))
    # slot 5: continuation of an OLD request (arrival 1.0) readmitted
    # after a preemption — highest seq, but unfair for this head
    sched.running[5] = _Running(
        req=Request(rid=1, prompt=[1, 1], max_new_tokens=4, arrival=1.0),
        slot=5, seq=9)
    sched.running[7] = _Running(
        req=Request(rid=2, prompt=[2, 2], max_new_tokens=4, arrival=3.0),
        slot=7, seq=4)
    sched._admit()
    assert eng.preempts == [7]             # the fair victim, despite seq 4
    assert sorted(sched.running) == [5]    # the old continuation survives
    assert [r.rid for r in sched.queue] == [0, 2]


def test_stale_continuation_falls_back_to_original_prompt(devices):
    """A continuation whose warm prefix aged out of the cache while it
    queued (prompt+prefix no longer fits a bucket -> gate "never") is
    retried from the ORIGINAL prompt instead of being rejected."""

    class _Eng(_BlockedEngine):
        def admit_gate(self, prompt_len, needed, prompt=None):
            return "never" if prompt_len > 4 else "later"

    eng = _Eng()
    sched = Scheduler(eng, clock=FakeClock())
    orig = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=6, arrival=0.0,
                   trace_id="t0")
    sched._resume[0] = {"orig": orig, "prefix": [9, 9], "ftt": 0.5}
    sched.queue.append(Request(          # the stale continuation
        rid=0, prompt=[1, 2, 3, 9, 9], max_new_tokens=4, arrival=0.0,
        trace_id="t0"))
    sched._admit()
    assert sched.completions == []       # NOT rejected
    assert len(sched.queue) == 1
    retry = sched.queue[0]
    assert list(retry.prompt) == [1, 2, 3]         # original prompt
    assert retry.max_new_tokens == 6               # full budget restored
    assert retry.trace_id == "t0" and retry.arrival == 0.0
    assert retry.submitted is not None   # prior attempt not booked as queue_s
    assert 0 not in sched._resume        # prefix dropped: regenerated


def test_continuation_victims_requeue_by_arrival_not_seq(devices):
    """A readmitted continuation carries a fresh HIGH admission seq but
    its ORIGINAL arrival — staged eviction order (descending seq) must
    not leak into the queue, or the younger victim readmits first and
    gets fairly re-preempted by the older one: churn the sort by
    arrival prevents."""
    from ddp_practice_tpu.serve.scheduler import _Running

    eng = _BlockedEngine()
    sched = Scheduler(eng, clock=FakeClock())
    sched.queue.append(
        Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4, arrival=0.5))
    # slot 5: continuation (arrival 1.0, readmitted -> seq 100); slot 7:
    # plain younger runner (arrival 2.0, seq 50). Both fair for the head.
    sched.running[5] = _Running(
        req=Request(rid=1, prompt=[1, 1], max_new_tokens=4, arrival=1.0),
        slot=5, seq=100)
    sched.running[7] = _Running(
        req=Request(rid=2, prompt=[2, 2], max_new_tokens=4, arrival=2.0),
        slot=7, seq=50)
    sched._admit()
    assert eng.preempts == [5, 7]          # evicted in seq order (LIFO)
    assert [r.rid for r in sched.queue] == [0, 1, 2]   # ARRIVAL order


# ------------------------------------------------------ token streaming
def test_stream_chunks_match_completions(devices, lm):
    """TokenChunk emission (the streaming side channel): per rid the
    chunks' concatenated tokens ARE the completion's tokens, offsets
    and seq are contiguous, and exactly one final chunk carries the
    terminal status — chunk delivery is complete exactly when the
    completion exists. stream=False (the control arm) builds none."""
    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=2, max_len=32, prompt_buckets=(8,),
    ))
    sched = Scheduler(engine, clock=FakeClock(step_s=0.01), max_queue=8)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=4 + i)
            for i in range(4)]
    for r in reqs:
        sched.submit(r)
    sched.run_until_idle()
    by_rid = {c.rid: c for c in sched.completions}
    assert len(by_rid) == 4

    per_rid = {}
    for ch in sched.chunks:
        per_rid.setdefault(ch.rid, []).append(ch)
    assert set(per_rid) == set(by_rid)
    for rid, chunks in per_rid.items():
        c = by_rid[rid]
        assert [ch.seq for ch in chunks] == list(range(len(chunks)))
        toks, offset = [], 0
        for ch in chunks:
            assert ch.start == offset        # offset-contiguous
            toks.extend(ch.tokens)
            offset += len(ch.tokens)
            assert ch.trace_id == c.trace_id
        assert toks == c.tokens
        finals = [ch for ch in chunks if ch.final]
        assert len(finals) == 1 and finals[0] is chunks[-1]
        assert finals[0].status == c.status
    # seq counters retire with their rid: live state stays O(in-flight)
    assert sched._chunk_seq == {}

    # control arm: stream=False emits nothing (end-of-request delivery)
    sched2 = Scheduler(engine, clock=FakeClock(step_s=0.01),
                       max_queue=8, stream=False)
    sched2.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    sched2.run_until_idle()
    assert sched2.chunks == []
    assert sched2.completions[0].tokens == by_rid[0].tokens
