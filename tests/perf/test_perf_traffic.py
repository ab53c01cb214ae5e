"""The traffic generator: one seed one schedule, every seed the same work."""

import numpy as np
import pytest

import perf_toy
from perf.lib import traffic


def chat():
    return perf_toy.load("perf/traffic/chat_poisson.json")


def test_one_seed_gives_one_schedule():
    a = traffic.build_schedule(chat(), seed=3_000_000_019, duration_s=20.0,
                               vocab=256)
    b = traffic.build_schedule(chat(), seed=3_000_000_019, duration_s=20.0,
                               vocab=256)
    assert a == b and len(a) > 100
    assert [r["rid"] for r in a] == list(range(len(a)))
    assert all(0 <= r["due_s"] < 20.0 for r in a)


def test_two_seeds_differ_in_order_and_not_in_work():
    a = traffic.build_schedule(chat(), seed=1, duration_s=20.0, vocab=256)
    b = traffic.build_schedule(chat(), seed=2, duration_s=20.0, vocab=256)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    size = lambda rows: sorted((len(r["prompt"]), r["max_new"]) for r in rows)
    assert size(a) == size(b)                       # the same lengths
    gaps = lambda rows: np.sort(np.diff([0.0] + [r["due_s"] for r in rows]))
    assert np.allclose(gaps(a), gaps(b))            # the same gaps
    assert a[-1]["due_s"] == pytest.approx(b[-1]["due_s"])


def test_lengths_keep_to_the_caps_of_the_file():
    spec = chat()["tenants"][0]
    rows = traffic.build_schedule(chat(), seed=5, duration_s=40.0, vocab=256)
    assert max(len(r["prompt"]) for r in rows) <= spec["prompt_len_cap"]
    assert max(r["max_new"] for r in rows) <= spec["max_new_cap"]
    assert max(len(r["prompt"]) + r["max_new"] for r in rows) <= 1024
    assert min(r["max_new"] for r in rows) >= 2
    assert all(0 <= t < 256 for r in rows for t in r["prompt"])
    s = traffic.offered_summary(rows, 40.0)
    assert s["requests"] == len(rows)
    assert s["rate_rps"] == pytest.approx(spec["rate_rps"], rel=0.25)


def test_bursts_and_sessions_are_data_not_code():
    tr = chat()
    tr["tenants"][0].update(arrivals="bursty", burst_every_s=10.0,
                            burst_len_s=1.0, burst_mult=8.0, rate_rps=4.0)
    rows = traffic.build_schedule(tr, seed=1, duration_s=40.0, vocab=256)
    inside = sum(1 for r in rows if r["due_s"] % 10.0 < 1.0)
    assert inside / len(rows) > 0.3       # a tenth of the time, x8 the rate
    tr = chat()
    tr["tenants"][0].update(sessions=3, turns_per_session=4,
                            session_prefix_len=32, prompt_len_median=8)
    rows = traffic.build_schedule(tr, seed=1, duration_s=5.0, vocab=256)
    first = [r["prompt"] for r in rows[:6]]
    assert first[0][:32] == first[3][:32]  # a session's turns share a prefix
    assert first[0][:32] != first[1][:32]
    with pytest.raises(ValueError):
        tr["tenants"][0]["arrivals"] = "diurnal"
        traffic.build_schedule(tr, seed=1, duration_s=5.0, vocab=256)
