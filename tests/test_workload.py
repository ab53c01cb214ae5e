"""Synthetic traffic (serve/workload.py): the single-tenant trace
builders, spec validation, deterministic plan expansion, the
add-a-tenant prefix-stability contract, arrival shaping (bursty/diurnal
via Lewis thinning), multi-turn session prompt growth, and the JSON
round-trip. All host math — no engines, no clocks."""

import json
import math

import pytest

from ddp_practice_tpu.serve.workload import (
    TenantSpec,
    WorkloadPlan,
    build_shared_prefix_trace,
    build_trace,
)

VOCAB = 32


def _plan(*tenants, duration_s=20.0):
    return WorkloadPlan(list(tenants), duration_s=duration_s)


# ------------------------------------------------------------ validation
def test_tenant_spec_validates_each_knob():
    with pytest.raises(ValueError):
        TenantSpec(name="")
    with pytest.raises(ValueError):
        TenantSpec(name="t", rate_rps=0.0)
    with pytest.raises(ValueError):
        TenantSpec(name="t", arrivals="lumpy")
    with pytest.raises(ValueError):  # burst window longer than period
        TenantSpec(name="t", arrivals="bursty", burst_every_s=1.0,
                   burst_len_s=2.0)
    with pytest.raises(ValueError):  # a burst must not SLOW the tenant
        TenantSpec(name="t", arrivals="bursty", burst_mult=0.5)
    with pytest.raises(ValueError):  # depth 1 would cross zero rate
        TenantSpec(name="t", arrivals="diurnal", diurnal_depth=1.0)
    with pytest.raises(ValueError):
        TenantSpec(name="t", prompt_len_cap=0)
    with pytest.raises(ValueError):
        TenantSpec(name="t", max_new_sigma=-0.1)
    with pytest.raises(ValueError):
        TenantSpec(name="t", sessions=2, turns_per_session=0)
    with pytest.raises(ValueError):
        TenantSpec(name="t", sessions=2, session_prefix_len=0)


def test_plan_validates_shape():
    with pytest.raises(ValueError):
        WorkloadPlan([])
    with pytest.raises(ValueError):
        WorkloadPlan([TenantSpec(name="a"), TenantSpec(name="a")])
    with pytest.raises(ValueError):
        WorkloadPlan([TenantSpec(name="a")], duration_s=0.0)
    with pytest.raises(ValueError):
        _plan(TenantSpec(name="a")).build(vocab=1)


# ----------------------------------------------------------- determinism
def test_build_is_deterministic_and_arrival_sorted():
    plan = _plan(TenantSpec(name="acme", rate_rps=3.0),
                 TenantSpec(name="bulk", rate_rps=8.0, priority=2,
                            hostile=True))
    a = plan.build(vocab=VOCAB, seed=7)
    b = plan.build(vocab=VOCAB, seed=7)
    assert a == b
    assert len(a) > 50
    # rid order == arrival order (what replay harnesses assume)
    assert [r["rid"] for r in a] == list(range(len(a)))
    assert all(x["arrival"] <= y["arrival"] for x, y in zip(a, a[1:]))
    # every row is replayable as-is and attributed
    for r in a:
        assert set(r) == {"rid", "arrival", "prompt", "max_new_tokens",
                          "tenant", "priority"}
        assert 0.0 <= r["arrival"] < plan.duration_s
        assert 1 <= len(r["prompt"]) <= 96
        assert 1 <= r["max_new_tokens"] <= 48
        assert all(0 <= t < VOCAB for t in r["prompt"])
    assert {r["tenant"] for r in a} == {"acme", "bulk"}
    assert all(r["priority"] == 2 for r in a if r["tenant"] == "bulk")
    # a different seed is a different draw
    assert plan.build(vocab=VOCAB, seed=8) != a


def test_adding_a_tenant_never_perturbs_existing_traffic():
    """Child generators spawn off the plan seed by tenant INDEX, so
    extending a plan leaves the original tenants' rows byte-stable —
    the property that makes A/B runs of grown plans comparable."""
    base = _plan(TenantSpec(name="acme", rate_rps=5.0))
    grown = _plan(TenantSpec(name="acme", rate_rps=5.0),
                  TenantSpec(name="new", rate_rps=5.0))

    def _rows(plan, tenant):
        return [
            {k: v for k, v in r.items() if k != "rid"}
            for r in plan.build(vocab=VOCAB, seed=3)
            if r["tenant"] == tenant
        ]

    assert _rows(base, "acme") == _rows(grown, "acme")


# ------------------------------------------------------ arrival shaping
def test_bursty_rates_and_arrival_concentration():
    spec = TenantSpec(name="t", rate_rps=2.0, arrivals="bursty",
                      burst_every_s=10.0, burst_len_s=1.0,
                      burst_mult=8.0)
    assert spec.peak_rate() == 16.0
    assert spec.rate_at(0.5) == 16.0      # inside the window
    assert spec.rate_at(5.0) == 2.0       # between windows
    rows = _plan(spec, duration_s=100.0).build(vocab=VOCAB, seed=0)
    in_burst = [r for r in rows if (r["arrival"] % 10.0) < 1.0]
    # 10% of the clock carries the 8x windows: expect roughly
    # 8/(8+9) ~ 47% of arrivals in-burst; far above the 10% a
    # homogeneous stream would put there
    assert len(in_burst) / len(rows) > 0.3


def test_diurnal_rates_follow_the_sinusoid():
    spec = TenantSpec(name="t", rate_rps=4.0, arrivals="diurnal",
                      diurnal_period_s=60.0, diurnal_depth=0.8)
    assert spec.peak_rate() == pytest.approx(4.0 * 1.8)
    assert spec.rate_at(15.0) == pytest.approx(4.0 * 1.8)   # crest
    assert spec.rate_at(45.0) == pytest.approx(4.0 * 0.2)   # trough
    assert spec.rate_at(0.0) == pytest.approx(4.0)
    rows = _plan(spec, duration_s=120.0).build(vocab=VOCAB, seed=1)
    crest = sum(1 for r in rows
                if math.sin(2 * math.pi * r["arrival"] / 60.0) > 0)
    assert crest / len(rows) > 0.6   # most arrivals ride the crest


def test_heavy_tailed_lengths_are_capped_and_spread():
    spec = TenantSpec(name="t", rate_rps=20.0, prompt_len_mean=8.0,
                      prompt_len_sigma=1.0, prompt_len_cap=32)
    rows = _plan(spec, duration_s=20.0).build(vocab=VOCAB, seed=2)
    lens = [len(r["prompt"]) for r in rows]
    assert max(lens) <= 32 and min(lens) >= 1
    assert len(set(lens)) > 5            # a distribution, not a constant
    # sigma 0 degenerates to the constant median
    flat = TenantSpec(name="t", rate_rps=20.0, prompt_len_mean=8.0,
                      prompt_len_sigma=0.0)
    rows = _plan(flat, duration_s=5.0).build(vocab=VOCAB, seed=2)
    assert {len(r["prompt"]) for r in rows} == {8}


# ------------------------------------------------------------- sessions
def test_session_turns_refeed_the_whole_conversation():
    spec = TenantSpec(name="chat", rate_rps=6.0, sessions=2,
                      turns_per_session=3, session_prefix_len=10)
    rows = _plan(spec, duration_s=10.0).build(vocab=VOCAB, seed=4)
    by_arrival = sorted(rows, key=lambda r: r["arrival"])
    # arrivals round-robin the sessions: chains[s] is session s's turns
    chains = [by_arrival[s::2] for s in range(2)]
    for chain in chains:
        for prev, cur in zip(chain, chain[1:3]):
            # turn N's prompt extends turn N-1's whole prompt — the
            # re-fed history the radix prefix cache exists for
            assert cur["prompt"][:len(prev["prompt"])] == prev["prompt"]
            assert len(cur["prompt"]) > len(prev["prompt"])
        # turn 4 starts a NEW chat on the same shared prefix
        if len(chain) > 3:
            assert chain[3]["prompt"][:10] == chain[0]["prompt"][:10]
            assert len(chain[3]["prompt"]) < len(chain[2]["prompt"])
    # the two sessions have distinct prefixes
    assert chains[0][0]["prompt"][:10] != chains[1][0]["prompt"][:10]


# ------------------------------------------------------------ json seam
def test_plan_json_roundtrip_and_hostile_marking():
    plan = _plan(
        TenantSpec(name="acme", rate_rps=3.0),
        TenantSpec(name="bulk", rate_rps=50.0, hostile=True,
                   arrivals="bursty"),
        duration_s=12.0)
    back = WorkloadPlan.from_json(plan.to_json())
    assert back.duration_s == 12.0
    assert back.tenants == plan.tenants
    assert back.hostile_tenants() == ["bulk"]
    assert back.build(vocab=VOCAB, seed=5) \
        == plan.build(vocab=VOCAB, seed=5)
    # a bare list of tenant objects is a plan with default duration
    bare = WorkloadPlan.from_json(json.dumps([{"name": "solo"}]))
    assert bare.duration_s == 10.0 and bare.tenants[0].name == "solo"


def test_plan_from_json_path_and_error_shapes(tmp_path):
    p = tmp_path / "plan.json"
    p.write_text(_plan(TenantSpec(name="a")).to_json())
    assert WorkloadPlan.from_json(str(p)).tenants[0].name == "a"
    # a mistyped path fails as a missing FILE, not a JSON decode error
    with pytest.raises(FileNotFoundError):
        WorkloadPlan.from_json("no/such/plan.json")
    with pytest.raises(TypeError):  # unknown keys are typos, not config
        WorkloadPlan.from_json('[{"name": "a", "rps": 3}]')


# ------------------------------------------- single-tenant trace builders
@pytest.mark.parametrize("build, kw, prompt_range", [
    (build_trace,
     dict(prompt_len_range=(2, 16), max_new_range=(4, 32)), (2, 16)),
    (build_shared_prefix_trace,
     dict(k_prefixes=3, prefix_len=12, tail_range=(1, 8),
          max_new_range=(4, 32)), (13, 20)),
], ids=["poisson", "shared-prefix"])
def test_trace_builders_are_seeded_and_in_range(build, kw, prompt_range):
    """What the router and affinity tests lean on: the same seed gives
    the same trace (two arms replay identical load), another seed another
    one, lengths and budgets stay inside their ranges, arrivals rise."""
    def make(seed):
        return build(n_requests=40, rate_hz=20.0, vocab=VOCAB, seed=seed,
                     **kw)

    trace = make(5)
    assert trace == make(5) and trace != make(6)
    assert [r["rid"] for r in trace] == list(range(40))
    arrivals = [r["arrival"] for r in trace]
    assert arrivals == sorted(arrivals) and arrivals[0] > 0.0
    for r in trace:
        assert prompt_range[0] <= len(r["prompt"]) <= prompt_range[1]
        assert all(0 <= t < VOCAB for t in r["prompt"])
        assert 4 <= r["max_new_tokens"] <= 32
    if build is build_shared_prefix_trace:
        # every prompt opens with one of the k system prompts
        assert len({tuple(r["prompt"][:12]) for r in trace}) == 3
