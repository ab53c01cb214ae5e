"""`perf/lib/annots.py` and the five readers of PR 49, each on a small
hand-made list of annotation rows whose answer is known, and the lib once on
an xplane the CPU profiler really wrote. Rows are [name, start_s, dur_s,
attrs]; a program that hands its annotations no attributes (the parent
commit's) gives every reader nothing to read, and it returns None.
"""

import importlib

import pytest

from perf.lib import annots

READERS = ("flood_slots_decoding_pct", "flood_slots_prefilling_pct",
           "flood_prefill_pad_pct", "flood_prefill_dev_tok_s",
           "flood_prefix_hit_pct")


def read(metric: str, obs: dict):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


def tick(at, dur, slots=4, decoding=0, prefilling=0, **more):
    return ["serve:tick", at, dur, dict(slots=slots, decoding=decoding,
                                        prefilling=prefilling, **more)]


def prefill(at, bucket, prompt_len, hit=0, slot=0):
    return ["serve:prefill", at, 0.002,
            dict(bucket=bucket, prompt_len=prompt_len, prefix_hit=hit,
                 blocks=9, slot=slot)]


def chunk(at, bucket, pos0, take, hit=0, slot=1):
    return ["serve:prefill_chunk", at, 0.002,
            dict(bucket=bucket, pos0=pos0, take=take, chunk=pos0 // 2048,
                 prefix_hit=hit, slot=slot)]


def device_trace(runs, marker=(10.0, 16.0)) -> dict:
    """A loaded trace (`xtrace.load`'s form): the marker on the host plane,
    `runs` = (program name, start_s, dur_s) on chip 0's "XLA Modules" line."""
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["perf:traced", marker[0], marker[1] - marker[0]]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [list(r) for r in runs]},
            {"name": "XLA Ops", "events": [["fusion.1", 10.0, 0.001]]}]}]}


# -------------------------------------------------------------- the lib
def test_clip_keeps_what_lies_wholly_inside_the_marker():
    rows = (("serve:tick", 9.9, 0.2, {}),            # begins before it
            ("perf:traced", 10.0, 6.0, {}),
            ("serve:tick", 10.1, 0.3, {"slots": 4}),
            ("serve:slow_tick", 12.0, 0.0, {"admit_s": 1.5}),   # an instant
            ("serve:tick", 15.9, 0.2, {}),           # ends after it
            ("serve:tick", 17.0, 0.1, {}))
    got = annots.clip(rows)
    assert [e[:2] for e in got] == [["serve:tick", 10.1],
                                    ["serve:slow_tick", 12.0]]
    assert got[0][3] == {"slots": 4}
    # a trace without a marker (a test's) is taken whole
    assert len(annots.clip([r for r in rows if r[0] != "perf:traced"])) == 5


def test_named_asks_for_the_name_and_every_key():
    rows = [tick(0.0, 0.1, decoding=3), ["serve:tick", 0.2, 0.1, {}],
            ["serve:tick", 0.4, 0.1, {"slots": 4}],
            ["serve:decode_burst", 0.0, 0.1, {"slots": 4, "decoding": 1}]]
    assert annots.named(rows, "serve:tick", "slots", "decoding") == rows[:1]
    assert annots.named(rows, "serve:tick") == rows[:3]
    assert annots.named(None, "serve:tick") == []


def test_rows_are_read_once_and_kept_on_the_observations(monkeypatch):
    calls = []
    monkeypatch.setattr(annots, "events",
                        lambda path=None: calls.append(path) or [])
    obs = {"trace": device_trace([]), "xplane": "somewhere.xplane.pb"}
    assert annots.of(obs) == [] and annots.of(obs) == []
    assert calls == ["somewhere.xplane.pb"]
    # an untraced run has no xplane of its own to look for
    assert annots.of({"trace": None}) is None and len(calls) == 1


# ----------------------------------------------------------- slot-seconds
def test_slot_seconds_are_weighted_by_the_ticks_time():
    """A tick of 0.1 s with 3 of 4 slots beside one of 0.3 s with 1 of 4:
    (0.3 + 0.3) / (0.4 + 1.2) = 37.5%, where the ticks' mean reads 50."""
    obs = {"annots": [tick(10.0, 0.1, decoding=3, prefilling=1),
                      tick(10.1, 0.3, decoding=1, prefilling=3)]}
    assert read("flood_slots_decoding_pct", obs) == pytest.approx(37.5)
    assert read("flood_slots_prefilling_pct", obs) == pytest.approx(62.5)


def test_a_tick_without_a_burst_spends_its_slot_seconds_undecoded():
    obs = {"annots": [tick(10.0, 0.2, decoding=0, prefilling=2),
                      tick(10.2, 0.2, decoding=4),
                      # the parent's kind of tick among them: not counted
                      ["serve:tick", 10.4, 5.0, {}]]}
    assert read("flood_slots_decoding_pct", obs) == pytest.approx(50.0)
    assert read("flood_slots_prefilling_pct", obs) == pytest.approx(25.0)


# -------------------------------------------------------------- prefills
def test_padding_is_what_the_bucket_runs_beyond_the_real_positions():
    """A 2,048 chunk all real, a last chunk of 300 in a 512 bucket, a
    prompt of 100 in a 128 bucket, and a prompt of 6,272 of which the
    cache had 6,144 (its 128-token suffix fills its bucket)."""
    obs = {"annots": [chunk(10.0, 2048, 0, 2048), chunk(10.5, 512, 2048, 300),
                      prefill(11.0, 128, 100), prefill(11.5, 128, 6272, 6144)]}
    assert annots.prefill_calls(obs) == [(2048, 2048), (512, 300),
                                         (128, 100), (128, 128)]
    ran, real = 2048 + 512 + 128 + 128, 2048 + 300 + 100 + 128
    assert read("flood_prefill_pad_pct", obs) == pytest.approx(
        100.0 * (ran - real) / ran)


def test_prefill_rate_divides_real_positions_by_the_programs_device_time():
    runs = [("jit__prefix_prefill(123)", 10.2, 0.050),
            ("jit__prefill_admit(77)", 11.0, 0.010),
            ("jit__decode_burst(5)", 11.5, 0.200),       # not a prefill
            ("jit__prefix_prefill(123)", 15.99, 0.050),  # ends past the slice
            ("jit__prefix_prefill(123)", 9.9, 0.050)]    # before it
    obs = {"trace": device_trace(runs),
           "annots": [chunk(10.1, 2048, 0, 2048), prefill(10.9, 128, 100)]}
    assert read("flood_prefill_dev_tok_s", obs) == pytest.approx(
        (2048 + 100) / 0.060)
    # no prefill program inside the slice, or no trace: nothing to read
    assert read("flood_prefill_dev_tok_s",
                dict(obs, trace=device_trace(runs[2:]))) is None
    assert read("flood_prefill_dev_tok_s", dict(obs, trace=None)) is None


def test_a_hit_is_counted_once_an_admission():
    """One prompt of 3,000 of which the cache had 1,024, taken in by three
    chunks that each repeat the hit; one plain admission of 200 with 128."""
    obs = {"annots": [
        ["serve:chunk_admit", 10.0, 0.0,
         dict(prompt_len=3000, prefix_hit=1024, chunk=1024, slot=1)],
        chunk(10.1, 1024, 1024, 1024, hit=1024),
        chunk(10.4, 1024, 2048, 952, hit=1024),
        chunk(10.7, 128, 3000 - 24, 24, hit=1024),
        prefill(11.0, 128, 200, hit=128)]}
    assert read("flood_prefix_hit_pct", obs) == pytest.approx(
        100.0 * (1024 + 128) / (3000 + 200))
    # a cell without the cache admits with a hit of 0 everywhere
    assert read("flood_prefix_hit_pct",
                {"annots": [prefill(10.0, 128, 100)]}) == 0.0


# ------------------------------------------- nothing handed over, no number
@pytest.mark.parametrize("metric", READERS)
def test_annotations_without_stats_give_no_number(metric):
    """The parent commit's program mirrors its spans with no attributes."""
    bare = [[name, 10.0 + i, 0.5, {}] for i, name in enumerate(
        ("serve:tick", "serve:prefill", "serve:prefill_chunk",
         "serve:decode_burst", "serve:tick"))]
    runs = [("jit__prefix_prefill(1)", 10.2, 0.05)]
    assert read(metric, {"annots": bare, "trace": device_trace(runs)}) is None
    assert read(metric, {"annots": None, "trace": device_trace(runs)}) is None
    assert read(metric, {"trace": None}) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_reads_rows_and_the_loaded_trace_and_no_driver_object(
        metric):
    """What a reader may touch of `obs`: the rows, the loaded trace and
    where the xplane lies. No span list, no tick list, no offset."""
    class Watched(dict):
        def __getitem__(self, key):
            assert key in ("annots", "trace", "xplane"), key
            return dict.__getitem__(self, key)

        def get(self, key, default=None):
            assert key in ("annots", "trace", "xplane"), key
            return dict.get(self, key, default)

    obs = Watched(annots=[tick(10.0, 0.1, decoding=2, prefilling=1),
                          prefill(10.0, 128, 100, hit=32)],
                  trace=device_trace([("jit__prefill_admit(1)", 10.1, 0.01)]),
                  spans=[], ticks=[], traced=(0.0, 1.0), t_origin=0.0)
    assert read(metric, obs) is not None


# ----------------------------------------------- a real xplane, on the CPU
def test_events_come_back_from_an_xplane_the_profiler_wrote(tmp_path,
                                                            monkeypatch):
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from ddp_practice_tpu.utils.trace import TraceRecorder

    rec = TraceRecorder()
    rec.set_annotate(TraceAnnotation, "serve")
    with rec.span("tick", slots=4):          # no session yet: not in the file
        pass
    jax.profiler.start_trace(str(tmp_path / "xplane"))
    try:
        with TraceAnnotation("perf:traced"):
            with rec.span("tick", slots=4, label="x", skipped=[1, 2],
                          nothing=None) as span:
                jnp.ones((8, 8)).sum().block_until_ready()
                span.attrs["decoding"] = 3   # filled in after it began
                span.attrs["share"] = 0.25
            rec.instant("slow_tick", mirror=True, admit_s=1.5)
            rec.instant("shed", reason="full")          # not mirrored
        with rec.span("tick", slots=4, decoding=1):     # past the marker
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "xplane" / "plugins" / "profile"
                            / "*" / "*.xplane.pb"))
    parses, real = [], ProfileData.from_file
    monkeypatch.setattr(ProfileData, "from_file", staticmethod(
        lambda p: parses.append(p) or real(p)))
    annots.load.cache_clear()
    rows = annots.events(path)
    assert [(e[0], e[3]) for e in rows] == [
        ("serve:tick", {"slots": 4, "label": "x", "decoding": 3,
                        "share": 0.25}),
        ("serve:slow_tick", {"admit_s": 1.5})]
    tick_row, instant = rows
    assert tick_row[2] > 0 and instant[2] < 1e-3
    assert tick_row[1] + tick_row[2] <= instant[1]       # one clock, in order
    assert annots.events(path) == rows and parses == [path]   # one parse
    assert len([e for e in annots.load(path) if e[0] == "serve:tick"]) == 2
    obs = {"trace": {"planes": []}, "xplane": path}
    assert read("flood_slots_decoding_pct", obs) == pytest.approx(75.0)
    # a trace with no device plane has no prefill program to divide by
    assert read("flood_prefill_dev_tok_s", obs) is None
    # the report a builder reads after a traced run, off the same file
    from perf.tools import annots_report

    out = annots_report.report(str(tmp_path))
    assert out["names"]["serve:tick"] == {
        "events": 1, "attrs": ["decoding", "label", "share", "slots"]}
    assert out["slow_ticks"][0][1] == {"admit_s": 1.5}
    assert out["decode_burst"] == {
        "events": 0, "runs": 0, "active_differs_from_its_ticks_decoding": 0,
        "bursts_in_no_one_tick": 0}
    assert out["readers"]["flood_slots_decoding_pct"] == pytest.approx(75.0)
    assert out["readers"]["flood_prefill_pad_pct"] is None
    annots.load.cache_clear()
