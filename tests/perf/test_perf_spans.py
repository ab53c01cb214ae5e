"""The readers PR 24 added, each on observations whose answer is known: the
program's span tree by name and interval (`obs["spans"]` is [name, t0, t1]
only), the clock skew and the kernel share from a hand-made trace — and
nothing to read (the parent commit's program has no such span) gives None.
"""

import importlib

import pytest

from perf.lib import spans


def read(metric: str, obs: dict):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ------------------------------------------------------------------ serve
def tick(t0: float, admit=0.0, plan=0.0, dispatch=0.0, readback=0.0,
         deliver=0.0, prefills=0) -> list:
    """One tick's spans, phases back to back from t0 + 1 ms on."""
    out, at = [], t0 + 0.001
    for name, dur in (("expire", 0.0005), ("admit", admit),
                      ("burst_plan", plan)):
        out.append([name, at, at + dur])
        if name == "admit":  # its prefills lie inside it, on other lanes
            for i in range(prefills):
                out.append(["prefill", at + i * admit / prefills,
                            at + (i + 1) * admit / prefills])
        at += dur
    burst0 = at
    out.append(["burst_dispatch", at, at + dispatch])
    at += dispatch
    out.append(["burst_readback", at, at + readback])
    at += readback
    out.append(["decode_burst", burst0, at])
    out.append(["deliver", at, at + deliver])
    at += deliver
    out.append(["tick", t0, at + 0.001])
    return out


def serve_obs(extra=()) -> dict:
    """Three ticks inside the window and before the traced slice closes at
    101.0; a fourth runs across that edge (the profiler closing stalls it)
    and a fifth lies after it."""
    sp = []
    sp += tick(100.000, admit=0.004, plan=0.001, dispatch=0.002,
               readback=0.100, deliver=0.003, prefills=2)
    sp += tick(100.200, admit=0.008, plan=0.001, dispatch=0.002,
               readback=0.110, deliver=0.005, prefills=4)
    sp += tick(100.400, admit=0.000, plan=0.001, dispatch=0.005,
               readback=0.120, deliver=0.004)
    sp += tick(100.900, admit=0.050, plan=0.001, dispatch=0.002,
               readback=3.000, deliver=0.004)
    sp += tick(105.000, admit=0.900, plan=0.001, dispatch=0.002,
               readback=0.100, deliver=0.004)
    sp += tick(99.000, admit=0.700, readback=0.100)   # before the window
    return {"kind": "serve", "window": (99.5, 145.0), "traced": (100.0, 101.0),
            "trace": None, "spans": sorted(sp + list(extra),
                                           key=lambda s: s[1])}


def test_only_ticks_in_the_window_and_before_the_slice_closes_count():
    tk = spans.ticks(serve_obs())
    assert [round(a, 3) for a, _ in tk] == [100.0, 100.2, 100.4]
    # with no traced slice the window's end is the edge
    obs = dict(serve_obs(), traced=None)
    assert len(spans.ticks(obs)) == 5


@pytest.mark.parametrize("metric,want", [
    ("flood_tick_admit_ms", (4 + 8 + 0) / 3),
    ("flood_tick_dispatch_ms", (3 + 3 + 6) / 3),       # plan + dispatch
    ("flood_tick_readback_ms", (100 + 110 + 120) / 3),
    ("flood_tick_deliver_ms", (3 + 5 + 4) / 3),
    ("flood_tick_max_ms", 0.001e3 + 0.5 + 0 + 1 + 5 + 120 + 4 + 0.001e3),
    # 100.2 - end of tick 1, 100.4 - end of tick 2
    ("flood_outside_tick_ms", 1e3 * ((100.2 - 100.1125) + (100.4 - 100.3285))
     / 2),
])
def test_tick_readers_read_known_spans(metric, want):
    assert read(metric, serve_obs()) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("metric", [
    "flood_tick_admit_ms", "flood_tick_dispatch_ms",
    "flood_tick_readback_ms", "flood_tick_deliver_ms", "flood_tick_max_ms",
    "flood_outside_tick_ms", "flood_clock_skew_us",
    "flood_prefill_dev_ms_p50", "train_dispatch_ms_step",
    "train_block_ms_step", "train_host_other_ms_step",
    "train_clock_skew_us", "train_flash_dev_pct",
])
def test_a_program_without_the_spans_gives_nothing_and_does_not_raise(
        metric):
    """The parent commit's program: `prefill` and `decode_burst` spans (or
    `data`), annotations named with request ids, no tick, no train_epoch."""
    kind = "serve" if metric.startswith("flood") else "train"
    old = {"kind": kind, "window": (0.0, 50.0), "traced": (10.0, 11.0),
           "trace": None, "segments": [2.0] * 4, "steps_per_segment": 12,
           "chips": 1,
           "spans": [["prefill", 10.1, 10.2], ["decode_burst", 10.2, 10.4],
                     ["data", 10.0, 10.1]] if kind == "serve"
           else [["data", 10.0, 10.1]]}
    assert read(metric, old) is None
    old["trace"] = parent_trace()
    if metric in ("flood_prefill_dev_ms_p50",):
        assert read(metric, old) == pytest.approx(15.0)  # it has that program
    else:
        assert read(metric, old) is None
    assert read(metric, dict(old, spans=[])) is None or metric == \
        "flood_prefill_dev_ms_p50"


def test_a_span_outside_every_tick_is_not_counted():
    stray = [["burst_readback", 100.15, 100.19]]   # between two ticks
    assert read("flood_tick_readback_ms", serve_obs(stray)) \
        == read("flood_tick_readback_ms", serve_obs())


# ------------------------------------------------------------------ train
def train_obs() -> dict:
    """Two segments of 2 steps in the window, one warm-up epoch before."""
    sp = [["train_epoch", 1.0, 2.0], ["dispatch", 1.1, 1.9]]   # warm-up
    for e0 in (10.0, 11.0):
        sp += [["train_epoch", e0, e0 + 0.9],
               ["epoch_open", e0, e0 + 0.010],
               ["data", e0 + 0.010, e0 + 0.020],
               ["dispatch", e0 + 0.020, e0 + 0.050],
               ["after_group", e0 + 0.050, e0 + 0.060],
               ["data", e0 + 0.060, e0 + 0.070],
               ["dispatch", e0 + 0.070, e0 + 0.100],
               ["after_group", e0 + 0.100, e0 + 0.500],
               ["block", e0 + 0.110, e0 + 0.490],     # inside after_group
               ["block", e0 + 0.500, e0 + 0.880]]     # the closing fence
    return {"kind": "train", "window": (9.0, 12.0), "traced": None,
            "trace": None, "segments": [0.9, 0.9], "steps_per_segment": 2,
            "chips": 1, "spans": sp}


@pytest.mark.parametrize("metric,want", [
    ("train_dispatch_ms_step", 1e3 * 4 * 0.030 / 4),
    ("train_block_ms_step", 1e3 * 2 * (0.380 + 0.380) / 4),
    # an epoch: 0.9 - data 0.02 - dispatch 0.06 - block 0.76 = 0.06:
    # epoch_open 0.01, after_group less its block 0.01 + 0.02, unnamed 0.02
    ("train_host_other_ms_step", 1e3 * 2 * 0.060 / 4),
])
def test_train_readers_read_known_spans(metric, want):
    assert read(metric, train_obs()) == pytest.approx(want, rel=1e-6)


def test_self_time_is_by_name_and_interval_not_by_every_span_of_a_name():
    """A `block` outside every counted `train_epoch` (a checkpoint's, an
    evaluation's) is not taken off the epochs' time."""
    obs = train_obs()
    obs["spans"].append(["block", 11.95, 11.99])
    assert read("train_host_other_ms_step", obs) == pytest.approx(30.0)


# ----------------------------------------------------------------- clocks
def mirrored_trace(prefix: str, spans_: list, off: float, skews: dict,
                   ops=()) -> dict:
    """A trace whose marker sits at `traced[0] + off` and whose host line
    mirrors `spans_`, each late by skews.get(name, 0)."""
    host = [["perf:traced", 10.0 + off, 1.0]]
    for n, a, b in spans_:
        host.append([f"{prefix}:{n}", a + off + skews.get(n, 0.0), b - a])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": list(ops)},
            {"name": "XLA Modules", "events": []}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


def parent_trace() -> dict:
    """What the parent's program leaves: annotations named with request
    ids, a kernel island named `shard_map`."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%shard_map.2976 = custom-call(...)", 1010.1, 0.2],
                ["%fusion.1 = fusion(...)", 1010.4, 0.2]]},
            {"name": "XLA Modules", "events": [
                ["jit__prefill_admit(123)", 1010.1, 0.015]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["perf:traced", 1010.0, 1.0],
            ["serve:prefill:r128", 1010.1, 0.1],
            ["serve:decode[r1,r2]", 1010.2, 0.2],
            ["train", 1010.0, 0.1]]}]}]}


@pytest.mark.parametrize("prefix,metric", [
    ("serve", "flood_clock_skew_us"), ("train", "train_clock_skew_us")])
def test_clock_skew_is_the_worst_mirrored_span(prefix, metric):
    sp = [["tick", 10.10, 10.30], ["admit", 10.11, 10.12],
          ["tick", 10.40, 10.60], ["admit", 10.41, 10.42],
          ["tick", 10.95, 11.40],        # ends after the slice: left out
          ["tick", 9.0, 9.2]]            # before the profiler: no mirror
    obs = {"kind": prefix, "window": (0.0, 50.0), "traced": (10.0, 11.0),
           "spans": sp, "chips": 1}
    inside_slice = sp[:4]
    off = 1234.5
    obs["trace"] = mirrored_trace(prefix, inside_slice, off, {})
    assert read(metric, obs) == pytest.approx(0.0, abs=1e-3)
    obs["trace"] = mirrored_trace(prefix, inside_slice, off,
                                  {"tick": 40e-6, "admit": -15e-6})
    assert read(metric, obs) == pytest.approx(40.0, rel=1e-3)
    # the mirror of a span that runs past the slice's end is not read
    obs["trace"] = mirrored_trace(prefix, sp[:5], off, {"tick": 40e-6})
    late = obs["trace"]["planes"][1]["lines"][0]["events"][-1]
    late[1] += 0.01
    assert read(metric, obs) == pytest.approx(40.0, rel=1e-3)
    # no trace, no number
    assert read(metric, dict(obs, trace=None)) is None


def test_clock_skew_beyond_the_spacing_of_a_name_is_read_in_full():
    """Spans of one name 2 ms apart and a clock 5 ms off: paired by order
    from the marker, not with the nearest, the reading is the 5 ms."""
    sp = [["prefill_host", 10.1 + 0.002 * i, 10.1005 + 0.002 * i]
          for i in range(20)]
    sp += [["prefill_host", 9.5, 9.5005], ["prefill_host", 11.5, 11.5005]]
    obs = {"kind": "serve", "window": (0.0, 50.0), "traced": (10.0, 11.0),
           "spans": sp, "chips": 1,
           "trace": mirrored_trace("serve", sp[:20], 1234.5,
                                   {"prefill_host": 5e-3})}
    assert read("flood_clock_skew_us", obs) == pytest.approx(5000.0,
                                                             rel=1e-3)
    # an annotation the profiler lost: that name cannot be paired by order
    del obs["trace"]["planes"][1]["lines"][0]["events"][3]
    assert read("flood_clock_skew_us", obs) is None


# ---------------------------------------------------------------- kernels
def test_flash_share_is_the_named_kernels_over_the_chips_busy_time():
    ops = [["%while.3 = while(...)", 1244.6, 0.35],          # a parent
           ["%flash_fwd_packed.1 = custom-call(...)", 1244.6, 0.10],
           ["%flash_bwd_dkv_packed.1 = custom-call(...)", 1244.7, 0.15],
           ["%flash_bwd_dq_packed.1 = custom-call(...)", 1244.85, 0.05],
           ["%fusion.7 = fusion(...)", 1245.0, 0.30]]
    obs = {"kind": "train", "window": (0.0, 50.0), "traced": (10.0, 11.0),
           "spans": [], "chips": 4,
           "trace": mirrored_trace("train", [], 1234.5, {}, ops)}
    assert read("train_flash_dev_pct", obs) == pytest.approx(
        100.0 * 0.30 / 0.65)
    # the island's own name is not a flash kernel: nothing to read
    assert read("train_flash_dev_pct", dict(obs, trace=parent_trace(),
                                            traced=(0.0, 1.0))) is None


def test_flood_prefill_reader_is_the_parked_cells_twin():
    obs = {"kind": "serve", "window": (0.0, 50.0), "traced": (0.0, 1.0),
           "spans": [], "chips": 1, "trace": parent_trace()}
    assert read("flood_prefill_dev_ms_p50", obs) == read(
        "chat_prefill_dev_ms_p50", obs) == pytest.approx(15.0)
