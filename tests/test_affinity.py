"""Cache-aware routing (serve/affinity.py): the host-pure half.

The contract under test, in layers:

- the HASH NAMESPACE: worker (radix-tree walk) and router (prompt
  walk) must compute identical names for identical block-aligned
  prefixes, or the whole scheme silently scores zero;
- the DIGEST WIRE: delta frames apply in order, a broken chain marks
  the view stale-until-full (never wrong), a worker restart's new
  epoch drops the dead tree's fingerprint, and freshness decays;
- the POLICY: affinity wins when a digest says a replica is warm,
  load wins outright past the imbalance cap, rendezvous homes
  first-seen families stably across membership churn, and with no
  usable digest the order is BYTE-IDENTICAL to the classic
  least-loaded sort — cache-awareness must be a strict overlay;
- the ROUTER: a reconciled "refused" completion (the one-way submit's
  draining-worker answer, serve/supervisor.py) re-dispatches with no
  breaker mark and no retry charge.

Everything above runs in milliseconds with no fleet. The one chaos
test at the bottom (slow) is the ISSUE-15 acceptance: SIGKILL the
affinity-preferred worker mid-run — zero lost, greedy identity holds,
the dead worker's digest is invalidated, and the merged fleet
timeline validates clean.
"""

import types

import pytest

from ddp_practice_tpu.serve.affinity import (
    DIGEST_MAX_DEPTH,
    DigestPublisher,
    DigestView,
    AffinityPolicy,
    LeastLoadedPolicy,
    hash_extend,
    kv_summary,
    least_loaded_key,
    prompt_prefix_hashes,
    rendezvous_pick,
)
from ddp_practice_tpu.serve.health import HealthState

BS = 4  # block size for the host-pure tests: small trees, deep paths


# -------------------------------------------------------- hash namespace
def test_prompt_hashes_extend_blockwise():
    """out[d] names prompt[:(d+1)*bs]: each level extends the previous
    via hash_extend, and a one-token change at depth d perturbs every
    level >= d and none below."""
    prompt = list(range(1, 13))  # 3 full blocks
    hs = prompt_prefix_hashes(prompt, BS)
    assert len(hs) == 3
    h = prompt_prefix_hashes(prompt, BS)[0]
    assert hash_extend(h, prompt[BS:2 * BS]) == hs[1]
    other = list(prompt)
    other[BS] += 1  # first token of block 1
    hs2 = prompt_prefix_hashes(other, BS)
    assert hs2[0] == hs[0]
    assert hs2[1] != hs[1] and hs2[2] != hs[2]
    # partial trailing block contributes nothing; sub-block prompts none
    assert prompt_prefix_hashes(prompt + [99], BS) == hs
    assert prompt_prefix_hashes([1, 2], BS) == []
    assert prompt_prefix_hashes(prompt, 0) == []
    # depth cap bounds the walk
    assert len(prompt_prefix_hashes(list(range(64)), 1, max_depth=5)) == 5
    assert len(prompt_prefix_hashes(list(range(400)), 1)) \
        == DIGEST_MAX_DEPTH


def test_rendezvous_sticky_under_grow_and_shrink():
    """Membership churn moves ONLY the families that re-home onto (or
    off) the changed replica — everything else keeps its placement.
    This is the property that makes first-seen placement survive
    autoscaler grow/shrink without any shared ledger."""
    families = [hash_extend(0xABCDEF, (f,)) for f in range(200)]
    before = {f: rendezvous_pick(f, [0, 1, 2]) for f in families}
    assert set(before.values()) == {0, 1, 2}  # all replicas own some

    grown = {f: rendezvous_pick(f, [0, 1, 2, 3]) for f in families}
    moved = [f for f in families if grown[f] != before[f]]
    assert moved, "a new replica must claim some families"
    assert all(grown[f] == 3 for f in moved)

    shrunk = {f: rendezvous_pick(f, [0, 2]) for f in families}
    for f in families:
        if before[f] != 1:
            assert shrunk[f] == before[f]  # survivors keep theirs
        else:
            assert shrunk[f] in (0, 2)     # orphans re-home


# ------------------------------------------------------------- publisher
def _warm_radix(n_blocks=32, bs=BS):
    from ddp_practice_tpu.serve.kv_pages import (
        BlockAllocator,
        RadixPrefixCache,
    )

    alloc = BlockAllocator(n_blocks)
    return RadixPrefixCache(alloc, bs), alloc


def _insert(radix, alloc, tokens):
    n = len(tokens) // radix.block_size
    blocks = alloc.alloc(n)
    radix.insert(tokens, blocks)
    alloc.free(blocks)  # drop the caller ref: the tree's ref remains
    return tokens


def test_publisher_full_then_delta_then_resync_beat():
    radix, alloc = _warm_radix()
    fam_a = _insert(radix, alloc, list(range(8)))
    pub = DigestPublisher(radix, full_every=3)
    f1 = pub.frame()
    # first frame is always FULL, and its hashes are exactly the
    # prompt-side names for the cached path (the namespace contract)
    assert f1["v"] == 1 and f1["bs"] == BS
    assert sorted(prompt_prefix_hashes(fam_a, BS)) == f1["full"]
    # a second family arrives: the next frame is a DELTA from v1
    fam_b = _insert(radix, alloc, [70 + i for i in range(8)])
    f2 = pub.frame()
    assert f2["v"] == 2 and f2["base"] == 1 and f2["dels"] == []
    assert set(f2["adds"]) == set(prompt_prefix_hashes(fam_b, BS))
    # no tree edit -> version holds (re-emit is a freshness touch)
    assert pub.frame()["v"] == 2
    # the resync beat: every full_every-th call is full again
    f4 = pub.frame()
    assert "full" in f4 and sorted(f4["full"]) \
        == sorted(set(f1["full"]) | set(f2["adds"]))
    # eviction shows up as dels on the next frame
    assert radix.evict(2) == 2
    f5 = pub.frame()
    assert f5["v"] == 3 and f5["base"] == 2 and f5["dels"]


def test_publisher_depth_cap_mru_bound_and_epochs():
    radix, alloc = _warm_radix()
    old = _insert(radix, alloc, list(range(8)))        # 2 levels
    new = _insert(radix, alloc, [40 + i for i in range(8)])
    # depth cap: only the first-block names survive a max_depth=1 walk
    shallow = DigestPublisher(radix, max_depth=1).frame()
    assert set(shallow["full"]) == {
        prompt_prefix_hashes(old, BS)[0],
        prompt_prefix_hashes(new, BS)[0],
    }
    # MRU bound: with room for one entry, the LAST-touched path's
    # deepest node wins (hot families, not history)
    radix.match(new)  # touch
    tight = DigestPublisher(radix, max_entries=1).frame()
    assert tight["n"] == 1
    assert tight["full"][0] in prompt_prefix_hashes(new, BS)
    # two publisher incarnations never share an epoch (restart = new
    # tree = new namespace lifetime)
    assert DigestPublisher(radix).epoch != DigestPublisher(radix).epoch


# ------------------------------------------------------------------ view
def _full(hashes, v=1, epoch="e1", bs=BS):
    return {"v": v, "epoch": epoch, "bs": bs, "n": len(hashes),
            "full": sorted(hashes)}


def _delta(v, adds=(), dels=(), epoch="e1", bs=BS):
    return {"v": v, "epoch": epoch, "bs": bs, "n": 0,
            "base": v - 1, "adds": sorted(adds), "dels": sorted(dels)}


def test_view_apply_rules_and_decay():
    view = DigestView()
    assert not view.usable(0.0, 10.0)          # cold = unusable
    view.apply(_full([10, 20]), now=0.0)
    assert view.usable(0.0, 10.0) and view.hashes == {10, 20}
    # in-order delta applies
    view.apply(_delta(2, adds=[30], dels=[10]), now=1.0)
    assert view.hashes == {20, 30} and view.version == 2
    # same-version re-emit refreshes the clock, nothing else
    view.apply(_delta(2, adds=[30], dels=[10]), now=8.0)
    assert view.updated_at == 8.0 and view.hashes == {20, 30}
    # a SKIPPED delta (base 3 != version 2) = stale-until-full: the
    # view refuses to guess — stale costs a miss, never a wrong score
    view.apply(_delta(4, adds=[40]), now=9.0)
    assert view.stale and not view.usable(9.0, 10.0)
    view.apply(_full([40, 50], v=4), now=9.5)   # the resync beat lands
    assert view.usable(9.5, 10.0) and view.hashes == {40, 50}
    # freshness decays on the receiver's clock
    assert view.usable(19.5, 10.0)
    assert not view.usable(19.6, 10.0)
    # epoch change (worker restart) drops the dead tree's fingerprint
    view.apply(_delta(5, adds=[60], epoch="e2"), now=10.0)
    assert view.stale and view.hashes == set()
    view.apply(_full([60], v=5, epoch="e2"), now=10.5)
    assert view.usable(10.5, 10.0)
    # a None payload (digest vanished from the heartbeat) resets
    view.apply(None, now=11.0)
    assert not view.usable(11.0, 10.0)


def test_view_expected_hit_stops_at_first_gap():
    prompt = list(range(16))                    # 4 blocks
    hs = prompt_prefix_hashes(prompt, BS)
    view = DigestView()
    view.apply(_full([hs[0], hs[1], hs[3]]), now=0.0)  # hole at depth 2
    # prefix-closure: the walk stops at the gap even though a deeper
    # level is (spuriously) present
    assert view.expected_hit_tokens(hs) == 2 * BS
    assert view.expected_hit_tokens(prompt_prefix_hashes(
        [99] * 16, BS)) == 0


# ---------------------------------------------------------------- policy
def _cand(hid, load=0.0, state=HealthState.HEALTHY, kv=None):
    return types.SimpleNamespace(
        id=hid, load=load, health=types.SimpleNamespace(state=state),
        kv_summary=kv,
    )


def _kv(hashes, **kw):
    return {"block_size": BS, "digest": _full(hashes, **kw)}


def test_policy_fallback_is_byte_identical_without_digests():
    """No usable digest anywhere -> EXACTLY the least-loaded order, all
    decisions 'fallback', no expectations. Cache-awareness must cost
    nothing when it has nothing to say."""
    cands = [_cand(0, load=2.0), _cand(2, load=1.0),
             _cand(1, load=1.0, state=HealthState.DEGRADED)]
    pol = AffinityPolicy()
    ordered, decisions, exp = pol.order(cands, list(range(8)), now=0.0)
    want, want_d, want_e = LeastLoadedPolicy().order(
        cands, list(range(8)), now=0.0)
    assert [h.id for h in ordered] == [h.id for h in want] == [2, 0, 1]
    assert decisions == want_d == {0: "fallback", 2: "fallback",
                                   1: "fallback"}
    assert exp == want_e == {}
    assert least_loaded_key(cands[0]) < least_loaded_key(cands[2])


def test_policy_affinity_beats_load_when_warm():
    prompt = list(range(16))
    hs = prompt_prefix_hashes(prompt, BS)
    warm = _cand(1, load=1.0, kv=_kv(hs, epoch="w1"))
    cold = _cand(0, load=0.0, kv=_kv([777], epoch="w0"))
    pol = AffinityPolicy()  # load_penalty 32: 16 warm tokens > 1 load
    ordered, decisions, exp = pol.order([cold, warm], prompt, now=0.0)
    assert [h.id for h in ordered] == [1, 0]
    assert decisions == {1: "affinity", 0: "load"}
    assert exp == {1: 16, 0: 0}


def test_policy_load_wins_past_imbalance_cap():
    """A warm-but-swamped replica loses to the least-loaded order: the
    cap bounds how much queueing a hot family can buy."""
    prompt = list(range(16))
    hs = prompt_prefix_hashes(prompt, BS)
    warm = _cand(1, load=5.0, kv=_kv(hs, epoch="w1"))   # gap 5 > cap 4
    cold = _cand(0, load=0.0, kv=_kv([777], epoch="w0"))
    ordered, decisions, _ = AffinityPolicy().order(
        [cold, warm], prompt, now=0.0)
    assert [h.id for h in ordered] == [0, 1]
    assert decisions == {0: "load", 1: "load"}
    # ... but inside the cap, warmth still wins
    warm.load = 4.0
    ordered, decisions, _ = AffinityPolicy().order(
        [cold, warm], prompt, now=0.0)
    assert [h.id for h in ordered] == [1, 0]
    assert decisions[1] == "affinity"


def test_policy_first_seen_family_goes_to_rendezvous_home():
    """Digests warm, prompt unknown to all: the winner is the family's
    rendezvous home (so the cache warms where repeats will land), not
    simply the least-loaded replica."""
    prompt = list(range(16))
    home = rendezvous_pick(prompt_prefix_hashes(prompt, BS)[0], [0, 1])
    cands = [_cand(i, load=float(i == home), kv=_kv([777 + i]))
             for i in (0, 1)]  # bias load AGAINST the home replica
    ordered, decisions, exp = AffinityPolicy().order(
        cands, prompt, now=0.0)
    assert ordered[0].id == home
    assert decisions[home] == "affinity"
    assert exp == {0: 0, 1: 0}
    # a sub-block prompt has no family: nothing to be sticky about
    ordered, decisions, _ = AffinityPolicy().order(
        cands, [1, 2], now=0.0)
    assert [h.id for h in ordered] == [0, 1]   # plain least-loaded
    assert decisions == {0: "load", 1: "load"}


def test_policy_stale_digest_costs_a_miss_never_an_error():
    """A replica whose delta chain broke drops out of scoring (its
    requests fall back); the periodic full frame brings it back. The
    failure mode is a cache miss — never a misroute on stale truth."""
    prompt = list(range(16))
    hs = prompt_prefix_hashes(prompt, BS)
    pol = AffinityPolicy()
    a = _cand(0, load=0.0, kv=_kv(hs, epoch="a"))
    b = _cand(1, load=0.0, kv=_kv([777], epoch="b"))
    assert pol.order([a, b], prompt, 0.0)[1][0] == "affinity"
    # a's publisher moves on; the router misses frames v2..v4 and then
    # sees a delta it cannot apply -> view stale -> fallback order
    a.kv_summary = {"block_size": BS,
                    "digest": _delta(5, adds=[42], epoch="a")}
    b.kv_summary = None
    ordered, decisions, exp = pol.order([a, b], prompt, 1.0)
    assert decisions == {0: "fallback", 1: "fallback"}
    # the resync full frame restores scoring
    a.kv_summary = _kv(hs, v=5, epoch="a")
    assert pol.order([a, b], prompt, 2.0)[1][0] == "affinity"
    # forget() (kill/restart/retire) drops the view entirely
    pol.forget(0)
    assert 0 not in pol.views


def test_policy_decayed_digest_falls_back():
    prompt = list(range(16))
    hs = prompt_prefix_hashes(prompt, BS)
    pol = AffinityPolicy(max_age_s=10.0)
    a = _cand(0, kv=_kv(hs))
    assert pol.order([a], prompt, 0.0)[1][0] == "affinity"
    # heartbeats stop (digest still cached on the handle): the view
    # ages out on the router's clock and scoring declines to guess
    a.kv_summary = None
    assert pol.order([a], prompt, 11.0)[1][0] == "fallback"


# ------------------------------------------------- kv summary one-shape
def test_kv_summary_without_a_prefix_cache_carries_no_digest():
    """An engine without a prefix cache (`radix` None) publishes its
    blocks, honest zeroes for the cache and NO digest — the shape the
    router's fallback expects."""
    _, alloc = _warm_radix()
    held = alloc.alloc(3)
    out = kv_summary(types.SimpleNamespace(blocks=alloc, radix=None))
    assert out["blocks_used"] == len(held)
    assert out["blocks_total"] == alloc.num_blocks - 1
    assert out["evictable"] == out["hit_tokens"] == 0
    assert out["prefix_hit_rate"] == 0.0
    assert "digest" not in out and "block_size" not in out


def test_kv_summary_carries_digest_with_publisher():
    radix, alloc = _warm_radix()
    fam = _insert(radix, alloc, list(range(8)))
    eng = types.SimpleNamespace(blocks=alloc, radix=radix)
    out = kv_summary(eng, DigestPublisher(radix))
    assert out["block_size"] == BS
    assert sorted(out["digest"]["full"]) \
        == sorted(prompt_prefix_hashes(fam, BS))
    # blocks_total excludes the garbage block, matching the gauges
    assert out["blocks_total"] == alloc.num_blocks - 1


# ------------------------------------------- router: refused re-dispatch
class _FakeReplica:
    """The narrow ReplicaHandle interface, scripted: completions are
    injected by the test, submits recorded (or refused while
    'draining'), no engine anywhere."""

    def __init__(self, hid):
        self.id = hid
        self.submitted = []
        self.comps = []
        self.refuse = False
        self.last_submit_refused = False
        self.kv_summary = None
        self.has_queue_space = True
        self.max_slots = 4
        self.queue_len = 0
        self.active = 0

    def submit(self, req):
        if self.refuse:
            self.last_submit_refused = True
            return
        self.last_submit_refused = False
        self.submitted.append(req)

    def step(self):
        pass

    def poll(self):
        out, self.comps = self.comps, []
        return out

    def poll_chunks(self):
        return []

    def evacuate(self):
        return []

    def shed_queued(self, min_priority):
        return []

    @property
    def load(self):
        return float(len(self.submitted))

    def fits_prompt(self, n):
        return True

    def probe_ok(self, now):
        return True

    def restart(self):
        pass


def test_refused_completion_redispatches_without_penalty():
    """The one-way submit's reconcile path (supervisor): a worker that
    was draining answers the confirm poll with a refusal, which
    surfaces as a typed 'refused' completion. The router re-dispatches
    on the next candidate with NO breaker mark and NO retry charge —
    refusal is certain and typed, not a fault."""
    from ddp_practice_tpu.serve import FakeClock, Request, RouterConfig
    from ddp_practice_tpu.serve.router import Router
    from ddp_practice_tpu.serve.scheduler import Completion

    clock = FakeClock(step_s=0.01)
    h0, h1 = _FakeReplica(0), _FakeReplica(1)
    router = Router([h0, h1], clock=clock,
                    config=RouterConfig(retry_jitter=0.0))
    assert router.submit(Request(rid=7, prompt=[1, 2, 3],
                                 max_new_tokens=4))
    assert [r.rid for r in h0.submitted] == [7]  # least-loaded tie -> 0
    # worker 0 went draining AFTER the cast was sent: the reconcile
    # verdict comes back as a refusal, and the door stays shut
    h0.refuse = True
    h0.comps.append(Completion(
        rid=7, tokens=[], status="refused", arrival=0.0,
        finish=clock.now(), trace_id="r7",
    ))
    router.step()
    assert [r.rid for r in h1.submitted] == [7]  # re-homed, same rid
    # no penalty anywhere: healthy breaker, zero retries charged
    assert h0.health.state is HealthState.HEALTHY
    assert router.metrics.retries.value == 0
    # the re-dispatched attempt finishes normally
    h1.comps.append(Completion(
        rid=7, tokens=[9, 9, 9, 9], status="length", arrival=0.0,
        finish=clock.now(), trace_id="r7",
    ))
    (done,) = router.step()
    assert done.status == "length" and done.tokens == [9, 9, 9, 9]
    assert done.flight["retries"] == 0 and done.flight["failovers"] == 0
    assert done.flight["route"] == "fallback"
    assert done.flight["prefix_hit_tokens"] == 0


# ------------------------------------------------- chaos acceptance (slow)
@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_affinity_preferred_worker_failover_and_invalidate():
    """ISSUE-15 acceptance: 2 REAL paged worker processes, one shared
    prefix family homed by affinity, its preferred worker SIGKILLed
    mid-decode. Zero lost, greedy tokens identical to a fault-free
    single-replica run, the dead worker's digest view is invalidated
    (stale digest = a miss, and here not even that), and the merged
    fleet timeline validates clean."""
    import time

    import numpy as np

    from ddp_practice_tpu.serve.workload import build_shared_prefix_trace
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.router import RouterConfig
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler
    from ddp_practice_tpu.serve.supervisor import (
        SupervisorConfig,
        make_fleet_router,
    )
    from ddp_practice_tpu.serve.worker import WorkerSpec, build_model
    from ddp_practice_tpu.utils.trace import TraceRecorder
    from tools.check_traces import validate, validate_fleet

    model_kw = {"vocab_size": 64, "max_len": 96, "hidden_dim": 64,
                "depth": 2, "num_heads": 4, "mlp_dim": 128,
                "pos_emb": "rope"}
    engine_kw = {"prefix_cache": True, "num_blocks": 48,
                 "block_size": 16, "max_slots": 2, "max_len": 96,
                 "prompt_buckets": [16, 32, 48, 64],
                 "temperature": 0.0, "decode_burst": 4, "eos_id": None}
    trace = build_shared_prefix_trace(
        n_requests=10, rate_hz=100.0, vocab=64, k_prefixes=1,
        prefix_len=32, tail_range=(1, 8), max_new_range=(5, 9), seed=9,
    )

    # fault-free greedy oracle: one in-process paged replica
    model, params = build_model(model_kw)
    eng_kw = dict(engine_kw)
    eng_kw["prompt_buckets"] = tuple(eng_kw["prompt_buckets"])
    oracle = Scheduler(PagedEngine(model, params, EngineConfig(**eng_kw)),
                       max_queue=64)
    for t in trace:
        oracle.submit(Request(rid=t["rid"], prompt=t["prompt"],
                              max_new_tokens=t["max_new_tokens"]))
    expected = {c.rid: list(c.tokens)
                for c in oracle.run_until_idle()}
    assert all(expected.values())

    tracer = TraceRecorder()
    spec = WorkerSpec(model=model_kw, engine=engine_kw, max_queue=64,
                      trace=True)
    router, sup, handles = make_fleet_router(
        spec, 2, tracer=tracer, config=RouterConfig(cache_aware=True),
        sup_config=SupervisorConfig(restart_base_s=0.25,
                                    restart_budget=5,
                                    ready_timeout_s=300.0),
    )
    try:
        # warm round: the family's FIRST request lands on its
        # rendezvous home and warms that worker's radix tree
        warm = trace[:2]
        for t in warm:
            router.submit(Request(rid=t["rid"], prompt=t["prompt"],
                                  max_new_tokens=t["max_new_tokens"]))
        warm_comps = router.run_until_idle()
        assert all(c.status == "length" for c in warm_comps)
        from ddp_practice_tpu.serve.affinity import (
            prompt_prefix_hashes as pph,
            rendezvous_pick as rvp,
        )
        home = rvp(pph(trace[0]["prompt"], 16)[0], [0, 1])

        # wait for the home's heartbeat to carry a non-empty digest
        # (the policy applies it at the next dispatch); remember its
        # epoch so invalidation is observable after the kill
        def home_digest():
            kv = handles[home].kv_summary
            dg = (kv or {}).get("digest")
            return dg if dg and dg.get("n") else None

        deadline = time.monotonic() + 60
        while home_digest() is None:
            assert time.monotonic() < deadline, "digest never arrived"
            router.step()
            time.sleep(0.02)
        pre_epoch = home_digest()["epoch"]

        # mid-run: the rest of the family, then kill its home while it
        # is observably decoding
        rest = trace[2:]
        for t in rest:
            router.submit(Request(rid=t["rid"], prompt=t["prompt"],
                                  max_new_tokens=t["max_new_tokens"]))

        def home_busy():
            w = sup.worker(home)
            if w is None:
                return False
            try:
                st = w.client.call("ping", timeout_s=2.0)["stats"]
                return st["active"] > 0
            except Exception:
                return False

        deadline = time.monotonic() + 60
        while not home_busy():
            assert time.monotonic() < deadline, \
                "family traffic never reached its affinity home"
            router.step()
        victim_rids = sorted(handles[home].outstanding)
        assert victim_rids, "nothing in flight on the affinity home"
        sup.kill(home, "SIGKILL")
        comps = router.run_until_idle()

        # ---- zero lost, all terminal, greedy identity holds
        by_rid = {c.rid: c for c in comps}
        by_rid.update({c.rid: c for c in warm_comps})
        assert set(by_rid) == {t["rid"] for t in trace}
        assert all(c.status == "length" for c in by_rid.values())
        for rid, want in expected.items():
            assert list(by_rid[rid].tokens) == want, f"rid {rid} diverged"
        migrated = [rid for rid in victim_rids
                    if by_rid[rid].flight["failovers"] >= 1]
        assert migrated, "the kill migrated nothing"

        # ---- the dead home's digest was invalidated: either the view
        # is gone (_kill -> policy.forget) or it was rebuilt from the
        # RESPAWNED worker's new epoch — never the dead tree's
        view = router.policy.views.get(home)
        assert view is None or view.epoch != pre_epoch

        # ---- requests kept flowing: the survivor (and any respawn)
        # carried hit tokens; flights expose the routing decision
        routes = {c.flight.get("route") for c in by_rid.values()
                  if c.flight}
        assert routes <= {"affinity", "load", "fallback"}
        assert "affinity" in routes, "affinity never engaged"

        # ---- one validator-clean merged fleet timeline
        chrome = tracer.to_chrome_trace()
        assert validate(chrome) == []
        assert validate_fleet(chrome) == []
    finally:
        sup.stop()
