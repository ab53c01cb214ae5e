"""Tenant QoS plane units (serve/fairshare.py + the seams it drives):
Jain's index math, VTC floor-lift/weights/enforcement queries, the
scheduler's weighted-fair head rotation (and its byte-identical-FIFO
off switch), the admission door's typed "fairness" refusal, per-tenant
cost metering + fleet federation, the per-tenant SLO registry's
isolation/overflow semantics, and the tenant-scoped brown-out shed
seam (in-process predicate + remote name-list wire form).

Everything but the last test is host-pure — fake engines, fake
completions, fake RPC clients; no jax compile. The last one replays a
hostile flood through one real engine, FIFO then fair: the same tokens
a request, nothing lost, the compliant tenant served sooner. The
SIGKILL-mid-flood story is judged by tools/check_qos.py over its
checked-in artifacts (tests/test_tools_artifacts.py)."""

import json
import re
import urllib.request

import pytest

from ddp_practice_tpu.serve import FakeClock, Request, Scheduler
from ddp_practice_tpu.serve.admission import (
    AdmissionController,
    TenantPolicy,
)
from ddp_practice_tpu.serve.fairshare import (
    DEFAULT_TENANT,
    TenantLedger,
    VirtualTokenCounter,
    federate_tenant_reports,
    jains_index,
    tenant_name,
)
from ddp_practice_tpu.serve.slo import SLOConfig, TenantSLORegistry
from ddp_practice_tpu.utils.metrics import (
    MetricsRegistry,
    percentile_summary,
    reset_label_guard,
    set_label_limit,
)


class _C:
    """Completion stand-in: just the attributes TenantLedger and the
    SLO registry read (tenant, tokens, status, ttft/tpot, flight)."""

    def __init__(self, tenant=None, tokens=(1, 2), status="eos",
                 ttft=0.05, tpot=0.01, flight=None):
        self.tenant = tenant
        self.tokens = list(tokens)
        self.status = status
        self.ttft = ttft
        self.tpot = tpot
        self.flight = flight if flight is not None else {}


# ------------------------------------------------------------ jains_index
def test_jains_index_math_and_edges():
    assert jains_index([]) == 1.0            # nobody served, nobody starved
    assert jains_index([0.0, 0.0]) == 1.0
    assert jains_index([5.0, 5.0, 5.0]) == 1.0
    # one tenant takes everything: 1/n exactly
    assert jains_index([10.0, 0.0]) == pytest.approx(0.5)
    assert jains_index([10.0, 0.0, 0.0, 0.0]) == pytest.approx(0.25)
    # monotone: a more even split scores higher
    assert jains_index([8.0, 2.0]) < jains_index([6.0, 4.0]) < 1.0


def test_tenant_name_folds_none_to_default():
    assert tenant_name(None) == DEFAULT_TENANT == "default"
    assert tenant_name("acme") == "acme"


# ------------------------------------------------- VirtualTokenCounter
def test_vtc_charges_weighted_service():
    vtc = VirtualTokenCounter(prefill_weight=0.5)
    # decode tokens at full price, prefill discounted
    assert vtc.charge("a", decode=10) == pytest.approx(10.0)
    assert vtc.charge("a", prefill=8) == pytest.approx(14.0)
    assert vtc.service("a") == pytest.approx(14.0)
    assert vtc.service("missing") == 0.0
    # None folds to the default tenant everywhere (fresh counter so the
    # floor lift does not muddy the arithmetic)
    vtc2 = VirtualTokenCounter()
    vtc2.charge(None, decode=3)
    assert vtc2.service(None) == vtc2.service("default") \
        == pytest.approx(3.0)


def test_vtc_floor_lift_on_late_registration():
    """A tenant arriving after others have accrued service starts at
    the current FLOOR, not zero — idle hours must not bank a credit
    that lets it monopolize the fleet until the books catch up."""
    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=100)
    vtc.touch("late")
    assert vtc.service("late") == pytest.approx(100.0)
    # the floor is the MINIMUM live counter, not the max
    vtc.charge("late", decode=20)
    vtc.touch("later-still")
    assert vtc.service("later-still") == pytest.approx(100.0)
    # touch() never charges: repeated sightings are free
    vtc.touch("late")
    assert vtc.service("late") == pytest.approx(120.0)


def test_vtc_weights_scale_accrual():
    """A weight-2 tenant accrues at half rate: fair ordering then
    grants it twice the tokens — paid tiers without a second knob."""
    vtc = VirtualTokenCounter(weights={"paid": 2.0})
    vtc.touch("paid")    # register both before charging: otherwise the
    vtc.touch("free")    # second inherits the first's floor lift
    vtc.charge("paid", decode=100)
    vtc.charge("free", decode=100)
    assert vtc.service("paid") == pytest.approx(50.0)
    assert vtc.service("free") == pytest.approx(100.0)
    assert vtc.least_served(["paid", "free"]) == "paid"
    with pytest.raises(ValueError):
        VirtualTokenCounter(weights={"bad": 0.0})
    with pytest.raises(ValueError):
        VirtualTokenCounter(prefill_weight=-0.1)


def test_vtc_enforcement_queries_and_tie_break():
    vtc = VirtualTokenCounter()
    vtc.charge("a", decode=5)
    vtc.charge("b", decode=50)
    vtc.touch("c")   # floor-lifted to 5
    assert vtc.least_served(["a", "b", "c"]) == "a"    # 5 ties 5: name
    assert vtc.most_over_served(["a", "b", "c"]) == "b"
    # None candidates stay None so callers can match raw labels
    assert vtc.least_served([None]) is None
    snap = vtc.snapshot()
    assert set(snap) == {"service", "share", "fairness_index"}
    assert sum(snap["share"].values()) == pytest.approx(1.0)
    assert snap["fairness_index"] == pytest.approx(
        jains_index(snap["service"].values()))


# ------------------------------------------- scheduler fair head rotate
class _IdleEngine:
    """Minimal engine surface for queue-only Scheduler tests: no free
    slots, so _admit never dispatches and the queue is observable."""

    class config:
        decode_burst = 1

    num_free = 0
    drafter = None


def _queued_sched(vtc):
    sched = Scheduler(_IdleEngine(), clock=FakeClock(), max_queue=16,
                      vtc=vtc)
    for rid, tenant in enumerate(["a", "b", "a", "b"]):
        sched.submit(Request(rid=rid, prompt=[1, 2], max_new_tokens=4,
                             tenant=tenant))
    return sched


def test_fair_head_rotates_least_served_tenants_earliest_request():
    vtc = VirtualTokenCounter()
    sched = _queued_sched(vtc)
    vtc.charge("a", decode=100)      # b is now starved
    sched._rotate_fair_head()
    # b's EARLIEST request comes to the head; within-tenant FIFO holds
    assert [r.rid for r in sched.queue] == [1, 0, 2, 3]
    # idempotent while the service picture is unchanged
    sched._rotate_fair_head()
    assert [r.rid for r in sched.queue] == [1, 0, 2, 3]


def test_fair_head_service_tie_degrades_to_arrival_order():
    vtc = VirtualTokenCounter()
    sched = _queued_sched(vtc)       # submit touched both at floor 0
    sched._rotate_fair_head()
    assert [r.rid for r in sched.queue] == [0, 1, 2, 3]


def test_no_vtc_is_byte_identical_fifo():
    """The off switch: without a vtc the rotation is a no-op and
    submit never touches any counter — the default path is FIFO."""
    sched = _queued_sched(None)
    sched._rotate_fair_head()
    assert [r.rid for r in sched.queue] == [0, 1, 2, 3]


def test_fair_head_single_tenant_queue_is_untouched():
    vtc = VirtualTokenCounter()
    sched = Scheduler(_IdleEngine(), clock=FakeClock(), max_queue=16,
                      vtc=vtc)
    for rid in range(3):
        sched.submit(Request(rid=rid, prompt=[1], max_new_tokens=4,
                             tenant="only"))
    vtc.charge("only", decode=10)
    sched._rotate_fair_head()
    assert [r.rid for r in sched.queue] == [0, 1, 2]


def test_scheduler_submit_registers_tenant_at_floor():
    vtc = VirtualTokenCounter()
    vtc.charge("old", decode=40)
    sched = Scheduler(_IdleEngine(), clock=FakeClock(), max_queue=16,
                      vtc=vtc)
    sched.submit(Request(rid=0, prompt=[1], max_new_tokens=4,
                         tenant="new"))
    assert vtc.service("new") == pytest.approx(40.0)


# --------------------------------------------- admission: fairness gate
def test_admission_refuses_most_over_served_under_pressure():
    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=100)
    vtc.touch("acme")
    ac = AdmissionController(vtc=vtc, fair_max_inflight=2)
    assert ac.try_acquire("bulk") == (True, None)   # below pressure
    assert ac.try_acquire("acme") == (True, None)
    # at pressure, two tenants competing: the over-served one is
    # refused with the TYPED reason, the starved one still gets in
    assert ac.try_acquire("bulk") == (False, "fairness")
    assert ac.refused["fairness"] == 1
    assert ac.try_acquire("acme") == (True, None)
    # releases relieve the pressure and the gate opens again
    ac.release("acme")
    ac.release("acme")
    assert ac.try_acquire("bulk") == (True, None)


def test_admission_fairness_needs_two_competing_tenants():
    """One tenant alone poses a capacity question, not a fairness one —
    that is the rate/concurrency envelopes' job."""
    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=100)
    ac = AdmissionController(vtc=vtc, fair_max_inflight=2)
    assert ac.try_acquire("bulk") == (True, None)
    assert ac.try_acquire("bulk") == (True, None)
    assert ac.try_acquire("bulk") == (True, None)   # pressure, no rival
    assert ac.refused["fairness"] == 0


def test_admission_fairness_off_without_vtc_or_pressure_knob():
    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=100)
    for ac in (AdmissionController(fair_max_inflight=2),
               AdmissionController(vtc=vtc)):
        assert ac.try_acquire("bulk") == (True, None)
        assert ac.try_acquire("acme") == (True, None)
        assert ac.try_acquire("bulk") == (True, None)
        assert ac.refused["fairness"] == 0


def test_admission_concurrency_checked_before_fairness():
    """A tenant over its own cap must not also burn a fairness refusal
    (or a rate token) for a request that was never going to run."""
    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=100)
    vtc.touch("acme")
    ac = AdmissionController(
        {"bulk": TenantPolicy(max_concurrent=1)},
        vtc=vtc, fair_max_inflight=1)
    assert ac.try_acquire("bulk") == (True, None)
    assert ac.try_acquire("acme") == (True, None)
    assert ac.try_acquire("bulk") == (False, "concurrency")
    assert ac.refused == {"rate": 0, "concurrency": 1, "fairness": 0}


def test_admission_acquire_touches_vtc_floor():
    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=30)
    ac = AdmissionController(vtc=vtc, fair_max_inflight=8)
    ac.try_acquire("fresh")
    assert vtc.service("fresh") == pytest.approx(30.0)


# ------------------------------------------------------- TenantLedger
def test_ledger_meters_cost_per_tenant():
    ledger = TenantLedger()
    flight = {"queue_s": 0.1, "prefill_s": 0.2, "decode_s": 0.3,
              "stall_s": 0.0, "prefix_hit_tokens": 4}
    ledger.on_completion(_C(tenant="acme", tokens=[1, 2, 3],
                            flight=flight), prompt_tokens=10)
    ledger.on_completion(_C(tenant="acme", status="error", tokens=[],
                            ttft=None, tpot=None), prompt_tokens=5)
    ledger.on_completion(_C(tenant=None, tokens=[1]), prompt_tokens=2)
    rep = ledger.report()
    acme = rep["tenants"]["acme"]
    assert acme["requests"] == {"eos": 1, "error": 1}
    assert acme["prompt_tokens"] == 15
    assert acme["output_tokens"] == 3
    assert acme["prefix_hit_tokens"] == 4
    assert acme["seconds"]["decode_s"] == pytest.approx(0.3)
    assert acme["ttft_s"] == percentile_summary([0.05])
    # raw tails ride along for fleet pooling (never p-of-p)
    assert rep["samples"]["acme"]["ttft_s"] == [0.05]
    # the unlabeled tenant is one named tenant, not a None key
    assert rep["tenants"]["default"]["output_tokens"] == 1


def test_ledger_bills_prefill_from_flight_stamp_fallback():
    """A worker-side ledger has no request back-pointer: the flight
    record's prompt_tokens stamp (scheduler _finish) still bills it."""
    ledger = TenantLedger()
    ledger.on_completion(_C(tenant="t", flight={"prompt_tokens": 7}))
    assert ledger.report()["tenants"]["t"]["prompt_tokens"] == 7
    # an explicit caller value wins over the stamp
    ledger.on_completion(_C(tenant="t", flight={"prompt_tokens": 7}),
                         prompt_tokens=3)
    assert ledger.report()["tenants"]["t"]["prompt_tokens"] == 10


def test_ledger_report_shares_with_and_without_vtc():
    vtc = VirtualTokenCounter()
    vtc.touch("a")
    vtc.touch("b")
    vtc.charge("a", decode=30)
    vtc.charge("b", decode=10)
    rep = TenantLedger(vtc=vtc).report()
    assert rep["share"]["a"] == pytest.approx(0.75)
    assert rep["fairness_index"] == pytest.approx(
        jains_index([30.0, 10.0]))
    # fair mode off: metering still answers, over raw output tokens
    ledger = TenantLedger()
    ledger.on_completion(_C(tenant="a", tokens=[1, 2, 3]))
    ledger.on_completion(_C(tenant="b", tokens=[1]))
    rep = ledger.report()
    assert rep["service"] == {"a": 3.0, "b": 1.0}
    assert rep["fairness_index"] == pytest.approx(jains_index([3, 1]))


def test_ledger_exports_tenant_counters_to_registry():
    reg = MetricsRegistry()
    vtc = VirtualTokenCounter()
    vtc.charge("acme", decode=2)
    ledger = TenantLedger(registry=reg, vtc=vtc)
    ledger.on_completion(
        _C(tenant="acme", tokens=[1, 2],
           flight={"decode_s": 0.5}), prompt_tokens=6)
    snap = reg.snapshot()
    assert snap["tenant_requests_total{status=eos,tenant=acme}"] == 1
    assert snap["tenant_prompt_tokens_total{tenant=acme}"] == 6
    assert snap["tenant_output_tokens_total{tenant=acme}"] == 2
    assert snap["tenant_cost_seconds_total{phase=decode_s,tenant=acme}"] \
        == pytest.approx(0.5)
    assert snap["tenant_fairness_index"] == pytest.approx(1.0)


# ------------------------------------------- fleet federation (rollup)
def test_federate_tenant_reports_sums_pools_and_rederives():
    def _rep(ttft, out_tokens, service):
        return {
            "tenants": {"t": {
                "requests": {"eos": 1}, "prompt_tokens": 2,
                "output_tokens": out_tokens, "prefix_hit_tokens": 0,
                "seconds": {"queue_s": 0.1, "prefill_s": 0.0,
                            "decode_s": 0.0, "stall_s": 0.0},
            }},
            "samples": {"t": {"ttft_s": ttft, "tpot_s": []}},
            "service": {"t": service},
        }

    out = federate_tenant_reports([
        _rep([0.01, 0.02], 3, 5.0), _rep([0.5], 4, 7.0),
        "not-a-dict",   # a worker that answered garbage is skipped
    ])
    t = out["tenants"]["t"]
    assert t["requests"] == {"eos": 2}
    assert t["output_tokens"] == 7
    assert t["seconds"]["queue_s"] == pytest.approx(0.2)
    # pooled percentiles over the union, never p-of-p
    assert t["ttft_s"] == percentile_summary([0.01, 0.02, 0.5])
    assert out["service"]["t"] == pytest.approx(12.0)
    assert out["share"]["t"] == pytest.approx(1.0)
    assert out["fairness_index"] == pytest.approx(1.0)
    # empty input is a valid (vacuously fair) fleet
    empty = federate_tenant_reports([])
    assert empty["tenants"] == {} and empty["fairness_index"] == 1.0


# --------------------------------------------------- TenantSLORegistry
SLO_CFG = SLOConfig(
    error_rate=0.1, fast_window_s=1.0, slow_window_s=5.0,
    trip_burn=2.0, resolve_burn=1.0, min_events=3,
)


def _burn(reg, tenant, n=5, status="error", t0=0.0):
    for i in range(n):
        reg.observe_event(tenant=tenant, t=t0 + i * 0.01, status=status)


def test_tenant_slo_isolation_one_budget_each():
    """The whole point of the registry: the hostile tenant's burn trips
    ITS alert; the compliant tenant's budget never notices."""
    mreg = MetricsRegistry()
    reg = TenantSLORegistry(SLO_CFG, registry=mreg)
    _burn(reg, "bulk", status="error")
    _burn(reg, "acme", status="length")
    reg.evaluate(0.1)
    assert reg.is_burning("bulk")
    assert not reg.is_burning("acme")
    assert reg.burning_tenants() == ["bulk"]
    assert reg.active   # the router's single-watchdog view still works
    # alert history carries the tenant as a 4th element
    assert [(e, o, t) for _, e, o, t in reg.alert_log] \
        == [("trip", "error_rate", "bulk")]
    # burn gauges are tenant-labelled
    snap = mreg.snapshot()
    assert snap[
        "slo_burn_rate{objective=error_rate,tenant=bulk,window=fast}"] \
        == 10.0
    assert snap[
        "slo_burn_rate{objective=error_rate,tenant=acme,window=fast}"] \
        == 0.0


def test_tenant_slo_burn_signal_is_worst_across_tenants():
    reg = TenantSLORegistry(SLO_CFG)
    _burn(reg, "bulk", status="error")
    _burn(reg, "acme", status="length")
    reg.evaluate(0.1)
    sig = reg.burn_signal()
    assert sig["burn_fast"] == 10.0      # bulk's, not an average
    assert sig["active"] and not sig["resolved"]
    # empty registry: quiet signal, vacuously resolved
    empty = TenantSLORegistry(SLO_CFG).burn_signal()
    assert empty == {"burn_fast": 0.0, "burn_slow": 0.0,
                     "active": False, "resolved": True}


def test_tenant_slo_none_folds_to_default_tenant():
    reg = TenantSLORegistry(SLO_CFG)
    _burn(reg, None, status="error")
    reg.evaluate(0.1)
    assert reg.burning_tenants() == ["default"]
    assert reg.is_burning(None) and reg.is_burning("default")


def test_tenant_slo_overflow_shares_one_watchdog():
    """Past max_tenants, newcomers share the "other" dog — bounded
    cardinality; over-cap tenants answer for (and to) each other."""
    reg = TenantSLORegistry(SLO_CFG, max_tenants=2)
    reg.watchdog("a")
    reg.watchdog("b")
    assert reg.watchdog("c") is reg.watchdog("d")
    assert reg.watchdog("c").tenant == "other"
    assert reg.watchdog("a") is not reg.watchdog("b")
    _burn(reg, "c", status="error")
    reg.evaluate(0.1)
    assert reg.burning_tenants() == ["other"]
    # is_burning maps unseen names through the fold (price of the cap)
    assert reg.is_burning("c") and reg.is_burning("zzz")
    assert not reg.is_burning("a")


def test_tenant_slo_is_burning_never_creates_a_watchdog():
    reg = TenantSLORegistry(SLO_CFG)
    assert not reg.is_burning("ghost")
    assert reg.evaluate(0.1) == {}


def test_tenant_slo_per_tenant_overrides():
    reg = TenantSLORegistry(
        SLO_CFG, overrides={"batch": SLOConfig(
            error_rate=0.5, min_events=3)})
    assert reg.watchdog("batch").config.error_rate == 0.5
    assert reg.watchdog("acme").config.error_rate == 0.1


# ------------------------------------- tenant-scoped brown-out shedding
def test_replica_handle_shed_covers_only_named_tenants():
    from ddp_practice_tpu.serve.router import ReplicaHandle

    sched = Scheduler(_IdleEngine(), clock=FakeClock(), max_queue=16)
    h = ReplicaHandle(0, sched)
    specs = [  # (rid, tenant, priority)
        (0, "bulk", 1), (1, "acme", 1), (2, "bulk", 0), (3, "bulk", 2),
    ]
    for rid, tenant, prio in specs:
        sched.submit(Request(rid=rid, prompt=[1], max_new_tokens=4,
                             tenant=tenant, priority=prio))
    rids = h.shed_queued(1, covers=lambda t: t == "bulk")
    # only the burning tenant's SHEDDABLE work goes: acme keeps its
    # slot, bulk's priority-0 interactive request is never shed
    assert rids == [0, 3]
    assert [r.rid for r in sched.queue] == [1, 2]
    # the shed sub-completions are consumed here (watermark advanced):
    # the router finalizes from the rids, not from poll()
    assert h.consumed == len(sched.completions) == 2
    assert all(c.status == "shed" for c in sched.completions)
    # covers=None is the global brown-out: everything eligible goes
    assert h.shed_queued(1, covers=None) == [1]


def test_remote_shed_ships_tenant_names_not_the_predicate():
    """A callable cannot cross the RPC wire: the remote form of a
    scoped shed is the tenants name-list kw, and only when scoped —
    a global shed stays byte-compatible with pre-QoS workers."""
    from ddp_practice_tpu.serve.supervisor import RemoteReplicaHandle

    class _FakeClient:
        def __init__(self):
            self.calls = []

        def call(self, op, **kw):
            self.calls.append((op, kw))
            return {"rids": [7]}

    h = RemoteReplicaHandle.__new__(RemoteReplicaHandle)
    h.outstanding = {7: {}}
    h._shed_skip = set()
    fake = _FakeClient()
    h._client = lambda: fake
    rids = h.shed_queued(1, covers=lambda t: t == "bulk",
                         tenants=["bulk"])
    assert fake.calls == [("shed", {"min_priority": 1,
                                    "tenants": ["bulk"]})]
    assert rids == [7]
    assert 7 in h._shed_skip and 7 not in h.outstanding
    fake.calls.clear()
    h.shed_queued(2, covers=None, tenants=None)
    assert fake.calls == [("shed", {"min_priority": 2})]


# --------------------- cardinality cap end-to-end (worker -> federated)
def test_tenant_label_cardinality_folds_to_other_fleet_wide():
    """>64 distinct tenants on one worker: the 65th+ tenant's METRICS
    fold to tenant=other at the label guard, and the fold survives the
    worker /metrics -> ScrapeFederator relabel into the fleet page.
    The /tenants rollup keeps raw names (bounded by the ledger window,
    not the metric plane's cardinality cap)."""
    from ddp_practice_tpu.utils.telemetry import (
        ScrapeFederator,
        TelemetryServer,
    )

    reset_label_guard()
    srv = None
    try:
        reg = MetricsRegistry()
        ledger = TenantLedger(registry=reg)
        for i in range(70):
            ledger.on_completion(_C(tenant=f"t{i:03d}", tokens=[1]),
                                 prompt_tokens=1)
        srv = TelemetryServer(registry=reg, tenants_fn=ledger.report,
                              port=0)
        targets = {0: {"host": "127.0.0.1", "port": srv.port,
                       "up": True, "pid": 1, "state": "running",
                       "restarts": 0, "heartbeat_age_s": 0.0}}
        fed = ScrapeFederator(lambda: targets)

        def _tenants_in(text):
            return set(re.findall(
                r'tenant_requests_total\{[^}]*tenant="([^"]+)"', text))

        worker_text = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=2
        ).read().decode()
        seen = _tenants_in(worker_text)
        assert len(seen) == 65 and "other" in seen   # 64 named + fold
        # the overflow bucket pools everyone past the cap
        assert 'tenant_requests_total{status="eos",tenant="other"} 6' \
            in worker_text
        fleet_text = fed.render_text()
        fleet_seen = _tenants_in(fleet_text)
        assert fleet_seen == seen                    # relabel preserves
        assert 'worker="0"' in fleet_text
        # the QoS rollup is NOT folded: all 70 raw names federate
        rollup = fed.tenants()
        assert len(rollup["tenants"]) == 70
        assert rollup["fairness_index"] == pytest.approx(1.0)
        assert rollup["workers"]["0"]["fairness_index"] \
            == pytest.approx(1.0)
    finally:
        if srv is not None:
            srv.close()
        reset_label_guard()


def test_slo_registry_tenant_gauges_respect_label_guard():
    """A hostile tenant-id space must not mint unbounded gauge
    families even below the registry's own max_tenants cap."""
    reset_label_guard()
    old = set_label_limit(3)
    try:
        mreg = MetricsRegistry()
        reg = TenantSLORegistry(SLO_CFG, registry=mreg, max_tenants=64)
        for i in range(6):
            _burn(reg, f"t{i}", status="error")
        reg.evaluate(0.1)
        burn_keys = [k for k in mreg.snapshot()
                     if k.startswith("slo_burn_rate{")
                     and "window=fast" in k]
        values = {re.search(r"tenant=([^,}]+)", k).group(1)
                  for k in burn_keys}
        assert len(values) == 4 and "other" in values   # 3 named + fold
    finally:
        set_label_limit(old)
        reset_label_guard()


# ------------------------------------- fair vs FIFO through a real engine
def test_fair_head_reorders_who_runs_never_what_they_decode(devices):
    """A hostile tenant's six requests are queued ahead of a compliant
    tenant's two, one slot. Weighted-fair admission moves the compliant
    tenant forward; every request still ends once, with the tokens FIFO
    gave it (greedy: scheduling picks WHO decodes next, never WHAT)."""
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.serve import EngineConfig, PagedEngine

    model = create_model(
        "lm_tiny", vocab_size=32, max_len=96, hidden_dim=64, depth=2,
        num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=1, max_len=32, prompt_buckets=(8,), temperature=0.0))
    flood = [("bulk", [1 + i, 2, 3]) for i in range(6)] \
        + [("acme", [9, 8 + i]) for i in range(2)]

    def replay(vtc):
        sched = Scheduler(engine, clock=FakeClock(step_s=0.01),
                          max_queue=16, vtc=vtc)
        for rid, (tenant, prompt) in enumerate(flood):
            sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=5,
                                 tenant=tenant))
        return sched.run_until_idle()

    fifo, fair = replay(None), replay(VirtualTokenCounter())
    for comps in (fifo, fair):
        assert sorted(c.rid for c in comps) == list(range(8))  # none lost
        assert all(c.status == "length" for c in comps)
    assert {c.rid: c.tokens for c in fair} == {c.rid: c.tokens for c in fifo}
    first_acme = [[c.tenant for c in comps].index("acme")
                  for comps in (fifo, fair)]
    assert first_acme[0] == 6 and first_acme[1] < 3
