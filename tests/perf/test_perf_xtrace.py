"""The reduction from a profiler trace to numbers: on a small trace recorded
on the chip (tests/perf/data/) and on a hand-made one whose answers are
known."""

import json
import os

import pytest

from perf.lib import readers, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "xplane_flood_head.json")) as f:
        return json.load(f)


def test_recorded_trace_one_prefill_is_a_copy_of_the_pool(recorded):
    t0, t1 = xtrace.window_of(recorded)
    assert t1 - t0 == pytest.approx(0.019072984)
    busy = xtrace.busy(recorded, t0, t1)
    assert busy["busy_s"] == pytest.approx(0.015125738, rel=1e-6)
    assert busy["busy_s"] < busy["window_s"]
    ops = xtrace.op_seconds(recorded, t0, t1)
    assert max(ops, key=ops.get) == "copy"       # names lose "%" and ".N"
    assert ops["copy"] == pytest.approx(0.014788029, rel=1e-6)
    assert sum(ops.values()) <= busy["busy_s"] * (1 + 1e-9)
    assert xtrace.module_runs(recorded, "prefill", t0, t1) \
        == [pytest.approx(0.015234276)]
    assert xtrace.module_runs(recorded, "decode_burst", t0, t1) == []


def test_recorded_trace_idle_goes_to_the_span_that_covered_it(recorded):
    t0, t1 = xtrace.window_of(recorded)
    spans = [("prefill", e[1], e[1] + e[2])
             for e in xtrace.host_events(recorded, "serve:prefill")]
    assert len(spans) == 3
    idle = xtrace.idle_gaps_by_span(recorded, spans, t0, t1)
    busy = xtrace.busy(recorded, t0, t1)
    assert sum(idle.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"])
    assert idle["prefill"] > idle["no_span"] > 0


def hand_made():
    dev = lambda n, ops, mods: {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}
    ops0 = [["%while.1 = (s32[]) while(...)", 1.0, 4.0],     # a parent
            ["%fusion.3 = bf16[8] fusion(...)", 1.0, 1.0],
            ["%all-reduce.7 = f32[4] all-reduce(...)", 2.0, 1.0],
            ["%attn._paged_decode.2 = custom-call", 3.5, 1.5],
            ["%fusion.9 = ...", 7.0, 1.0]]
    ops1 = [["%fusion.3 = ...", 1.0, 2.5],                   # hides a part
            ["%all-reduce.7 = ...", 2.0, 2.0]]
    return {"planes": [
        dev(0, ops0, [["jit_step(123)", 1.0, 4.0], ["jit_step(123)", 7.0,
                                                     1.0]]),
        dev(1, ops1, []),
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["perf:traced", 0.0, 10.0], ["serve:decode[r1]", 5.0, 2.5]]}]},
    ]}


def test_hand_made_trace():
    tr = hand_made()
    assert xtrace.window_of(tr) == (0.0, 10.0)
    b = xtrace.busy(tr, 0.0, 10.0)
    assert b["per_chip_s"] == [5.0, 3.0] and b["busy_s"] == 4.0
    ops = xtrace.op_seconds(tr, 0.0, 10.0)
    assert ops == {"fusion": 2.0, "all-reduce": 1.0,
                   "attn._paged_decode": 1.5}   # the `while` is no leaf
    # chip 0: the all-reduce runs alone for 1.0; chip 1: alone for 0.5
    assert xtrace.exposed_collective_seconds(tr, 0.0, 10.0) \
        == pytest.approx(0.75)
    idle = xtrace.idle_gaps_by_span(
        tr, [("decode_burst", 5.0, 7.5), ("tick", 4.0, 8.5)], 0.0, 10.0)
    assert idle == {"no_span": 3.0, "decode_burst": 2.0}
    assert xtrace.module_runs(tr, "step", 0.0, 10.0) == [4.0, 1.0]
    assert xtrace.op_name("%slice-start.48 = ((bf16[3072,768]") \
        == "slice-start"
    assert xtrace.top({"a": 1.0, "b": 3.0}, 1) == [["b", 3.0]]


def test_readers_on_the_hand_made_trace():
    obs = {"kind": "serve", "trace": hand_made(), "traced": (100.0, 110.0),
           "window": (95.0, 140.0), "spans": [["decode_burst", 105.0, 107.5]],
           "compiles_in_window": 0, "burst": 2, "t_origin": 95.0,
           "chips": 2, "steps_per_segment": 2, "segments": [1.0, 1.0],
           "ticks": [{"t": 8.0, "dt": 2.0, "slots": 3, "live": 300,
                      "queue": 0},
                     {"t": 30.0, "dt": 2.0, "slots": 9, "live": 900,
                      "queue": 0}],
           "requests": [{"wait_ms": 1.0, "late_ms": 0.1},
                        {"wait_ms": 3.0, "late_ms": 0.3}],
           "decode_bytes": (2 * 12 * 768 * 2, 2 * 12 * 768 * 2),
           "peaks": {"hbm_bytes_s": 819e9}}
    assert readers.device_busy(obs) == {"busy_s": 4.0, "window_s": 10.0}
    assert readers.device_idle_pct(obs) == pytest.approx(60.0)
    assert readers.coll_exposed_pct(obs) == pytest.approx(7.5)
    assert readers.step_dev_ms(obs) == pytest.approx(2500.0)
    assert readers.breakdown(obs)["idle_gaps"][0] == ["no_span", 3.0]
    assert readers.decode_slots_mean(obs) == 6.0
    assert readers.request_percentile(obs, "wait_ms", 50.0) == 2.0
    # only the first tick lies in the traced slice: 2 steps of 3 slots
    # over 300 live tokens -> (2*300 + 3*3) tokens of K and V, 6 q/out rows
    least = ((2 * 300 + 3 * 3) * 36864 + 6 * 2 * 12 * 768 * 2) / 819e9
    assert readers.paged_decode_roofline_pct(obs) \
        == pytest.approx(100.0 * least / 1.5)
    assert readers.paged_decode_roofline_pct(dict(obs, trace=None)) is None
    assert readers.prefill_dev_ms_p50(obs) is None  # nothing to read
