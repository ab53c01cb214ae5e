"""The span tree (ISSUE 24): one `tick` per Scheduler.step() and one
`train_epoch` per Trainer.train_epoch(), children linked by `parent`,
mirrored into the profiler under fixed names — and nothing at all without
a tracer. Plus the names a device trace is read by: the jitted serving
programs and the flash kernels.
"""

import inspect
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools.check_traces import validate  # noqa: E402

from ddp_practice_tpu.utils.trace import (  # noqa: E402
    ENGINE_LANE,
    SLOT_LANE_BASE,
    TraceRecorder,
)

VOCAB = 32
# what a tick may hold directly, in the order it must appear
TICK_CHILDREN = ["expire", "admit", "burst_plan", "decode_burst", "deliver"]
SERVE_NAMES = {"tick", "expire", "admit", "prefill", "prefill_host",
               "prefill_dispatch", "burst_plan", "decode_burst",
               "burst_dispatch", "burst_readback", "deliver"}


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.models import create_model

    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=96, hidden_dim=32,
        depth=1, num_heads=2, mlp_dim=64, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def make_engine(lm, kind: str):
    """The engine under one of its two admission layouts: "paged" left-pads
    a prompt into a scratch cache (`_prefill_admit`), "prefix" appends it
    through the page table at canonical positions (`_prefix_prefill`)."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    model, params = lm
    return PagedEngine(model, params, EngineConfig(
        max_slots=2, prompt_buckets=(4, 8), eos_id=None, block_size=4,
        decode_burst=2, prefix_cache=kind == "prefix"))


def serve(lm, kind: str, traced: bool = True, *, requests: int = 4,
          max_new: int = 4, **recorder_kw):
    """Drive a toy scheduler to idle under a FakeClock, which is the
    recorder's clock too. Returns (scheduler, recorder or None, ticks)."""
    from ddp_practice_tpu.serve.scheduler import (
        FakeClock,
        Request,
        Scheduler,
    )

    clock = FakeClock(step_s=0.01)
    rec = TraceRecorder(clock=clock, **recorder_kw) if traced else None
    engine = make_engine(lm, kind)
    engine.set_tracer(rec, 0)
    sched = Scheduler(engine, clock=clock, tracer=rec, replica=0)
    for rid in range(requests):
        sched.submit(Request(rid=rid, prompt=[1, 2, 3],
                             max_new_tokens=max_new))
    n = 0
    while not sched.idle:
        sched.step()
        n += 1
    assert all(c.status == "length" for c in sched.completions)
    return sched, rec, n


def lane_spans(rec: TraceRecorder) -> list:
    """Lane spans in the order they BEGAN (a span's `link` is drawn
    then; its `seq` when it is recorded, i.e. as it ends)."""
    return sorted((r for r in rec._records if r.kind == 0),
                  key=lambda r: r.link)


# ------------------------------------------------------------ serve ticks
@pytest.mark.parametrize("kind", ["paged", "prefix"])
def test_every_tick_is_one_span_with_its_phases_as_children(lm, kind):
    sched, rec, n_ticks = serve(lm, kind)
    spans = lane_spans(rec)
    by_link = {r.link: r for r in spans}
    ticks = [r for r in spans if r.name == "tick"]
    assert len(ticks) == n_ticks and all(r.parent is None for r in ticks)
    assert all(r.tid == ENGINE_LANE for r in ticks)
    assert {r.name for r in spans} <= SERVE_NAMES
    # everything but a tick names the span that caused it
    assert all(r.parent in by_link for r in spans if r.name != "tick")
    admitted = delivered = 0
    for tick in ticks:
        kids = [r for r in spans if r.parent == tick.link]
        names = [r.name for r in kids]
        # its phases, in order, each at most once
        assert names == [n for n in TICK_CHILDREN if n in names], names
        assert names[:2] == ["expire", "admit"] and names[-1] == "deliver"
        # nested in the tick, one after the other, no more than it
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0
        assert all(tick.t0 <= r.t0 and r.t1 <= tick.t1 for r in kids)
        assert sum(r.t1 - r.t0 for r in kids) <= (tick.t1 - tick.t0) + 1e-9
        assert {"queue", "running", "admitted", "delivered"} <= set(
            tick.attrs)
        admitted += tick.attrs["admitted"]
        delivered += tick.attrs["delivered"]
    assert admitted == 4 and delivered == len(sched.completions) == 4
    # a prefill sits on its slot's lane and still names the `admit` on
    # the engine lane that caused it; the engine's own halves hang under
    # the dispatch they split
    prefills = [r for r in spans if r.name == "prefill"]
    assert len(prefills) == 4
    for r in prefills:
        assert r.tid >= SLOT_LANE_BASE
        assert by_link[r.parent].name == "admit"
        halves = [c.name for c in spans if c.parent == r.link]
        assert halves == ["prefill_host", "prefill_dispatch"]
    for r in spans:
        if r.name in ("burst_dispatch", "burst_readback"):
            assert by_link[r.parent].name == "decode_burst"
    assert any(r.name == "burst_plan" for r in spans)
    # the export carries the linkage and stays validator-clean
    trace = rec.to_chrome_trace()
    assert validate(trace) == []
    begins = {e["args"]["link"]: e for e in trace["traceEvents"]
              if e["ph"] == "B"}
    for ev in begins.values():
        if ev["name"] == "tick":
            assert "parent" not in ev["args"]
        else:
            assert ev["args"]["parent"] in begins


def test_decode_burst_says_how_far_the_walk_is_from_the_table(lm):
    """ISSUE 25: under the tracer a `decode_burst` span counts the pages
    the paged kernel walks (`attn // bs` to `(len + j) // bs` of every
    active slot, every step j of the burst) beside the pages the slots
    hold; a left-padded prompt's leading pages are held and not walked."""
    import numpy as np

    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.scheduler import (
        FakeClock,
        Request,
        Scheduler,
    )

    model, params = lm
    bs, k = 4, 2
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=2, prompt_buckets=(8,), eos_id=None, block_size=bs,
        decode_burst=k))
    clock = FakeClock(step_s=0.01)
    rec = TraceRecorder(clock=clock)
    engine.set_tracer(rec, 0)
    sched = Scheduler(engine, clock=clock, tracer=rec, replica=0)
    # 2 tokens in a bucket of 8: attn_start 6, so page 0 is never walked
    for rid, prompt in enumerate([[1, 2], [1, 2, 3, 4, 5, 6, 7]]):
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=6))
    step_burst, before = engine.step_burst, []

    def spy():
        before.append((engine._active.copy(), engine._len.copy(),
                       engine._attn.copy()))
        out = step_burst()
        before[-1] += (engine._nblk.copy(),)
        return out

    engine.step_burst = spy
    while not sched.idle:
        sched.step()
    bursts = [r for r in lane_spans(rec) if r.name == "decode_burst"]
    assert len(bursts) == len(before) >= 3
    for span, (active, length, attn, nblk) in zip(bursts, before):
        walked = sum((int(length[s]) + j) // bs - int(attn[s]) // bs + 1
                     for s in np.flatnonzero(active) for j in range(k))
        assert span.attrs["pages_walked"] == walked > 0
        assert span.attrs["pages_held"] == k * int(nblk[active].sum())
        assert type(span.attrs["pages_walked"]) is int
    assert bursts[0].attrs["pages_walked"] < bursts[0].attrs["pages_held"]


def test_self_time_comes_from_linkage(lm):
    """A tick's self time is its duration minus its children's: under the
    FakeClock every clock step is taken by `deliver` (one a token row), so
    the tick itself and every other phase own none."""
    _, rec, _ = serve(lm, "paged")
    spans = lane_spans(rec)
    for tick in (r for r in spans if r.name == "tick"):
        kids = [r for r in spans if r.parent == tick.link]
        own = (tick.t1 - tick.t0) - sum(r.t1 - r.t0 for r in kids)
        assert own == pytest.approx(0.0, abs=1e-9)
        deliver = kids[-1]
        assert deliver.t1 - deliver.t0 == pytest.approx(tick.t1 - tick.t0)


def test_the_ring_keeps_record_order_and_the_drain_loses_no_parent(lm):
    """A parent is recorded after its children, so its `seq` (drawn as it
    is recorded) is the higher one: the ring stays in seq order, which is
    what `drain_otlp`'s high-water mark counts on. A push that lands
    between `prefill_host` ending and `prefill` ending must still ship
    the request's `prefill` in the next batch."""
    _, rec, _ = serve(lm, "paged")
    seqs = [r.seq for r in rec._records]
    assert seqs == sorted(seqs)

    def drained(r):
        batch = r.drain_otlp()
        return batch and [s["name"] for s in
                          batch["resourceSpans"][0]["scopeSpans"][0]["spans"]]

    r = TraceRecorder()
    with r.span("prefill", trace_id="r1", tid=SLOT_LANE_BASE):
        with r.span("prefill_host", trace_id="r1", tid=SLOT_LANE_BASE):
            pass
        assert drained(r) == ["prefill_host"]       # the push, mid-prefill
    assert drained(r) == ["prefill"]
    assert drained(r) is None


def test_validator_flags_a_child_outside_its_parent():
    def ev(ph, name, ts, tid, **args):
        return {"ph": ph, "name": name, "ts": ts, "pid": 0, "tid": tid,
                **({"args": args} if args else {})}

    def trace(child_end):
        return {"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": "replica0"}},
            ev("B", "admit", 10.0, 0, link=1), ev("E", "admit", 20.0, 0),
            ev("B", "prefill", 12.0, 1, link=2, parent=1),
            ev("E", "prefill", child_end, 1),
            # its parent was sampled out of the file: no error
            ev("B", "prefill", 30.0, 1, link=4, parent=3),
            ev("E", "prefill", 31.0, 1)]}

    assert validate(trace(19.0)) == []
    errors = validate(trace(21.0))
    assert len(errors) == 1 and "outside its parent 'admit'" in errors[0]


class CountingAnnotation:
    """Stands where `jax.profiler.TraceAnnotation` stands."""

    names: list = []

    def __init__(self, name):
        CountingAnnotation.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax

    CountingAnnotation.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    return CountingAnnotation.names


@pytest.mark.parametrize("kind", ["paged", "prefix"])
def test_no_tracer_no_record_and_no_annotation(lm, kind, annotations,
                                               monkeypatch):
    from ddp_practice_tpu.serve import engine, scheduler

    def built(*args, **kw):
        raise AssertionError("a span or its attrs built without a tracer")

    plain = inspect.getsource(scheduler.Scheduler.step)
    traced_tick = inspect.getsource(scheduler.Scheduler._traced_tick)
    # without a tracer the tick takes the plain path and the engine
    # builds no span, so no attr is computed for one either
    monkeypatch.setattr(scheduler.Scheduler, "_traced_tick", built)
    for maker in ("_span", "_prefill_spans", "_burst_spans"):
        monkeypatch.setattr(engine.PagedEngine, maker, built)
    sched, _, _ = serve(lm, kind, traced=False)
    assert annotations == []
    assert sched.engine.tracer is None and not sched.engine._slot_trace
    assert not sched._tick_history
    # how a tick's slots were spent (ISSUE 49) is counted for the `tick`
    # span alone: the plain path's source names none of the three
    for attr in ("slots=", "decoding=", "prefilling="):
        assert attr not in plain and traced_tick.count(attr) == 1


def test_a_disabled_tracer_costs_what_none_costs(lm, annotations):
    _, rec, _ = serve(lm, "paged", enabled=False)
    assert annotations == [] and len(rec) == 0


@pytest.mark.parametrize("kind", ["paged", "prefix"])
def test_spans_are_mirrored_under_a_closed_set_of_names(lm, kind,
                                                        annotations):
    _, rec, _ = serve(lm, kind)
    spans = lane_spans(rec)
    # one annotation a lane span, named by the span and by nothing else
    assert sorted(annotations) == sorted(
        "serve:" + r.name for r in spans)
    assert set(annotations) <= {"serve:" + n for n in SERVE_NAMES}
    assert not any(re.search(r"r\d|\[|,", n) for n in annotations)
    # the request ids are where they belong
    assert {r.trace_id for r in spans if r.name == "prefill"} == {
        f"r{i}" for i in range(4)}


def test_engine_source_builds_no_annotation_itself():
    import ddp_practice_tpu.serve.engine as engine

    src = open(engine.__file__).read()
    assert "TraceAnnotation(" not in src
    assert "_dispatch_ids" not in src


# -------------------------------------------------------------- slow_tick
def slow_ticks(rec: TraceRecorder) -> list:
    return [r for r in rec._records if r.kind == 2 and r.name == "slow_tick"]


def test_slow_tick_fires_on_a_tick_ten_times_the_median(lm, monkeypatch):
    from ddp_practice_tpu.serve.scheduler import Scheduler

    real_expire, stalled = Scheduler._expire_queue, []

    def expire(self):
        # once, twelve decoding ticks in: hold the `expire` phase for
        # ten normal ticks (a tick is 2 token rows = 0.02 s)
        if len(self._tick_history) == 12 and not stalled:
            stalled.append(True)
            self.clock.advance(0.2)
        real_expire(self)

    monkeypatch.setattr(Scheduler, "_expire_queue", expire)
    _, rec, _ = serve(lm, "paged", requests=2, max_new=40)
    hits = slow_ticks(rec)
    assert len(hits) == 1
    attrs = hits[0].attrs
    assert attrs["median_s"] == pytest.approx(0.02)
    assert attrs["tick_s"] == pytest.approx(0.22)
    # the phase that held it is named, and the others are beside it
    assert attrs["expire_s"] == pytest.approx(0.2)
    assert attrs["deliver_s"] == pytest.approx(0.02)
    assert {"admit_s", "decode_burst_s", "burst_dispatch_s",
            "burst_readback_s", "burst_plan_s"} <= set(attrs)


def test_slow_tick_stays_quiet_on_a_flat_history(lm):
    sched, rec, n = serve(lm, "paged", requests=2, max_new=40)
    assert n >= 20 and len(sched._tick_history) >= 20
    assert slow_ticks(rec) == []


# ------------------------------------------- attributes ride the mirror
class RecordingAnnotation:
    """Stands where `jax.profiler.TraceAnnotation` stands and says, like
    it, whether a profiler session is open."""

    live = True
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False

    def set_metadata(self, **kw):
        self.log.append(("meta", self.name, kw))

    @staticmethod
    def is_enabled():
        return RecordingAnnotation.live


@pytest.fixture
def recording(monkeypatch):
    import jax

    monkeypatch.setattr(RecordingAnnotation, "live", True)
    monkeypatch.setattr(RecordingAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", RecordingAnnotation)
    return RecordingAnnotation


def test_a_spans_scalar_attributes_reach_its_annotation_once_at_its_end(
        recording):
    rec = TraceRecorder()
    rec.set_annotate(recording, "serve")
    with rec.span("decode_burst", active=3, burst=2, rows=[1, 2],
                  nothing=None, impl="flash", share=0.5, ok=True) as span:
        with rec.span("burst_dispatch"):       # no attributes: no call
            pass
        span.attrs["expert_rows"] = 17         # arrives with the readback
        span.attrs["late_list"] = (1, 2)
    assert recording.log == [
        ("enter", "serve:decode_burst"), ("enter", "serve:burst_dispatch"),
        ("exit", "serve:burst_dispatch"),
        ("meta", "serve:decode_burst",
         {"active": 3, "burst": 2, "impl": "flash", "share": 0.5,
          "ok": True, "expert_rows": 17}),
        ("exit", "serve:decode_burst")]
    # the record keeps everything, the trace id stays where it was
    (burst,) = [r for r in rec._records if r.name == "decode_burst"]
    assert burst.attrs["rows"] == [1, 2] and burst.attrs["nothing"] is None
    with rec.span("prefill", trace_id="r7", slot=1):
        pass
    assert recording.log[-2] == ("meta", "serve:prefill", {"slot": 1})


def test_no_session_no_metadata_and_a_double_that_cannot_say_is_not_asked(
        recording, annotations):
    rec = TraceRecorder()
    rec.set_annotate(recording, "serve")
    recording.live = False
    with rec.span("tick", slots=4) as span:
        span.attrs["decoding"] = 2
    rec.instant("slow_tick", mirror=True, tick_s=1.0)
    assert recording.log == [("enter", "serve:tick"), ("exit", "serve:tick")]
    # `CountingAnnotation` has neither `is_enabled` nor `set_metadata`
    rec.set_annotate(CountingAnnotation, "serve")
    with rec.span("tick", slots=4):
        pass
    rec.instant("slow_tick", mirror=True, tick_s=1.0)
    assert annotations == ["serve:tick"]


def test_the_scheduler_says_how_each_ticks_slots_were_spent(lm, recording):
    sched, rec, n_ticks = serve(lm, "paged", requests=3)
    metas = [e for e in recording.log if e[0] == "meta"]
    ticks = [kw for _, name, kw in metas if name == "serve:tick"]
    bursts = [kw for _, name, kw in metas if name == "serve:decode_burst"]
    assert len(ticks) == n_ticks
    for kw in ticks:
        assert {"queue", "running", "slots", "admitted", "delivered",
                "decoding", "prefilling"} <= set(kw)
        assert kw["slots"] == 2 and 0 <= kw["decoding"] <= 2
        assert kw["prefilling"] == 0       # no chunked admission here
        assert all(type(v) is int for v in kw.values())
    # a burst's `active` is its tick's `decoding`; 0 where none ran
    assert [kw["decoding"] for kw in ticks if kw["decoding"]] == [
        kw["active"] for kw in bursts]
    assert sum(kw["decoding"] for kw in ticks) == sum(
        r.attrs["active"] for r in lane_spans(rec)
        if r.name == "decode_burst")
    assert all({"active", "burst", "pages_walked", "pages_held"} <= set(kw)
               for kw in bursts)
    prefills = [kw for _, name, kw in metas if name == "serve:prefill"]
    assert len(prefills) == 3 and all(
        (kw["bucket"], kw["prompt_len"], kw["prefix_hit"]) == (4, 3, 0)
        for kw in prefills)


def test_slow_tick_is_mirrored_with_the_phase_that_held_it(lm, monkeypatch,
                                                           recording):
    from ddp_practice_tpu.serve.scheduler import Scheduler

    real_expire, stalled = Scheduler._expire_queue, []

    def expire(self):
        if len(self._tick_history) == 12 and not stalled:
            stalled.append(True)
            self.clock.advance(0.2)
        real_expire(self)

    monkeypatch.setattr(Scheduler, "_expire_queue", expire)
    _, rec, _ = serve(lm, "paged", requests=2, max_new=40)
    assert len(slow_ticks(rec)) == 1
    at = [i for i, e in enumerate(recording.log)
          if e[1] == "serve:slow_tick"]
    assert [recording.log[i][0] for i in at] == ["enter", "meta", "exit"]
    assert at[2] - at[0] == 2                  # zero-length: nothing inside
    phases = recording.log[at[1]][2]
    assert phases["expire_s"] == pytest.approx(0.2)
    assert phases["tick_s"] == pytest.approx(0.22) and "admit_s" in phases


def test_the_programs_counts_come_back_from_a_cpu_xplane(lm, tmp_path):
    """The REAL round trip: the profiler open on the CPU around a toy
    `PagedEngine` + `Scheduler`, then `perf/lib/annots.py` alone on the
    `.xplane.pb` it wrote: every span's attributes, every child inside its
    tick, on the trace's own clock."""
    import glob

    import jax

    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler
    from perf.lib import annots

    model, params = lm
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=2, prompt_buckets=(4, 8), eos_id=None, block_size=4,
        decode_burst=2, prefix_cache=True, prefill_chunk=4))
    rec = TraceRecorder()
    engine.set_tracer(rec, 0)
    sched = Scheduler(engine, tracer=rec, replica=0)
    # two prompts of one chunk, one of three chunks that shares a block
    for rid, prompt in enumerate(([1, 2, 3], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10],
                                  [1, 2, 3, 4, 9, 9, 9, 9, 9, 9, 9])):
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("perf:traced"):
            n_ticks = 0
            while not sched.idle:
                sched.step()
                n_ticks += 1
    finally:
        jax.profiler.stop_trace()
    assert all(c.status == "length" for c in sched.completions)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    annots.load.cache_clear()
    rows = annots.events(path)
    ticks = annots.named(rows, "serve:tick", "slots", "decoding",
                         "prefilling", "admitted", "delivered")
    assert len(ticks) == n_ticks == len(annots.named(rows, "serve:tick"))
    assert all(t[3]["slots"] == 2 for t in ticks)
    assert any(t[3]["prefilling"] for t in ticks)     # the chunked prompts
    bursts = annots.named(rows, "serve:decode_burst", "active", "burst",
                          "pages_walked", "pages_held")
    recorded = [r for r in lane_spans(rec) if r.name == "decode_burst"]
    assert len(bursts) == len(recorded) > 0
    assert [b[3]["pages_walked"] for b in bursts] == [
        r.attrs["pages_walked"] for r in recorded]

    def tick_of(row):
        (owner,) = [t for t in ticks
                    if t[1] <= row[1] and row[1] + row[2] <= t[1] + t[2]]
        return owner

    for b in bursts:                 # inside its tick, and counted as its
        assert tick_of(b)[3]["decoding"] == b[3]["active"]
    prefills = annots.named(rows, "serve:prefill", "bucket", "prompt_len",
                            "prefix_hit")
    chunks = annots.named(rows, "serve:prefill_chunk", "bucket", "take",
                          "pos0", "prefix_hit")
    admits = annots.named(rows, "serve:chunk_admit", "prompt_len",
                          "prefix_hit")
    assert len(prefills) == 1 and len(admits) == 2
    assert len(chunks) == len(
        [r for r in lane_spans(rec) if r.name == "prefill_chunk"]) >= 4
    for row in prefills + chunks + admits:
        tick_of(row)
    for name in ("serve:admit", "serve:deliver", "serve:burst_readback"):
        assert annots.named(rows, name) and all(
            tick_of(r) for r in annots.named(rows, name))
    # the five readers, from the file alone
    obs = {"trace": {"planes": []}, "xplane": path}
    spent = [annots.slot_seconds_pct(obs, k)
             for k in ("decoding", "prefilling")]
    assert 0 < spent[0] <= 100 and 0 < spent[1] <= 100
    real = sum(c[3]["take"] for c in chunks) + 3
    ran = sum(r[3]["bucket"] for r in prefills + chunks)
    assert annots.prefill_pad_pct(obs) == pytest.approx(
        100.0 * (ran - real) / ran)
    hit = sum(r[3]["prefix_hit"] for r in prefills + admits)
    assert annots.prefix_hit_pct(obs) == pytest.approx(
        100.0 * hit / (3 + 10 + 11))
    annots.load.cache_clear()


# ---------------------------------------------------------------- trainer
def train_config(**kw):
    from ddp_practice_tpu.config import MeshConfig, TrainConfig

    cfg = dict(dataset="synthetic", epochs=1, batch_size=4,
               optimizer="adam", learning_rate=1e-3, log_every_steps=2,
               max_steps_per_epoch=4, mesh=MeshConfig(data=-1))
    cfg.update(kw)
    return TrainConfig(**cfg)


@pytest.mark.parametrize("placement", ["device", "host"])
def test_train_epoch_alone_yields_the_tree(devices, placement, annotations):
    from ddp_practice_tpu.train.loop import Trainer

    rec = TraceRecorder()
    trainer = Trainer(train_config(data_placement=placement), tracer=rec)
    assert trainer.tracer is trainer._tracer is rec
    assert (trainer.resident_train_step is not None) == (
        placement == "device")
    trainer.train_epoch(0)          # fit() is never called
    spans = lane_spans(rec)
    by_link = {r.link: r for r in spans}
    roots = [r for r in spans if r.parent is None]
    assert [r.name for r in roots] == ["train_epoch"]
    root = roots[0]
    assert root.attrs == {"epoch": 0}
    kids = [r for r in spans if r.parent == root.link]
    names = [r.name for r in kids]
    assert names[0] == "epoch_open" and names[-1] == "block"
    assert names.count("dispatch") == names.count("after_group") >= 2
    assert set(names) == {"epoch_open", "data", "dispatch", "after_group",
                          "block"}
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0
    assert all(root.t0 <= r.t0 and r.t1 <= root.t1 for r in kids)
    # the log readback stays a `block`, now under the after_group it
    # belongs to
    inner = [r for r in spans if r.name == "block"
             and by_link[r.parent].name == "after_group"]
    assert len(inner) == 2          # steps 2 and 4 of 4, log every 2
    assert validate(rec.to_chrome_trace()) == []
    assert set(annotations) == {"train:" + n for n in set(names)
                                | {"train_epoch"}}


@pytest.mark.parametrize("asked,on_tpu,want", [
    ("auto", False, "xla"),          # off the TPU "auto" interprets nothing
    ("auto", True, "flash_short"),   # what the chip's programs would run
    # a named kernel is taken at its word; whole heads on the one device,
    # so the block around the streaming kernels stays flat (PR 33)
    ("flash", False, "flash_flat"),
])
def test_train_epoch_span_says_which_attention_runs(devices, monkeypatch,
                                                    asked, on_tpu, want):
    """The `attn_impl` attribute is what SelfAttention RESOLVED for the
    Trainer's shapes and mesh (traced once, in the abstract init), not the
    config's string; a model without attention has none (the conv model
    of test_train_epoch_alone_yields_the_tree: attrs == {"epoch": 0})."""
    from ddp_practice_tpu import models
    from ddp_practice_tpu.config import MeshConfig
    from ddp_practice_tpu.train.loop import Trainer
    from ddp_practice_tpu.utils import backend

    name = "vit_span_test"
    try:
        models.create_model(name)
    except ValueError:
        # one block, two heads of 64, 7 x 7 patches of a 28 x 28 image
        models.register(name)(lambda **kw: models.create_model(
            "vit_tiny", **{**kw, "hidden_dim": 128, "depth": 1,
                           "num_heads": 2, "mlp_dim": 128}))
    monkeypatch.setattr(backend, "on_tpu", lambda: on_tpu)
    if on_tpu:
        # the range's lower end belongs to the chip's measurements; the
        # rule here is only that the resolved value reaches the span
        import ddp_practice_tpu.ops.flash_attention as fa

        monkeypatch.setattr(fa, "SHORT_SEQ_MIN", 16)
    rec = TraceRecorder()
    trainer = Trainer(train_config(model=name, attn_impl=asked,
                                   mesh=MeshConfig(data=1),
                                   fused_encoder="off"), tracer=rec)
    assert trainer.attn_impl == want
    if on_tpu:
        return  # the step itself would interpret kernels as compiled ones
    trainer.train_epoch(0)
    root, = [r for r in lane_spans(rec) if r.parent is None]
    assert root.name == "train_epoch"
    assert root.attrs == {"epoch": 0, "attn_impl": want}


def test_save_trace_is_public_and_needs_no_fit(devices, tmp_path):
    from ddp_practice_tpu.train.loop import Trainer

    out = tmp_path / "host_spans.json"
    trainer = Trainer(train_config(trace_out=str(out)))
    assert trainer.tracer is not None       # trace_out still makes one
    trainer.train_epoch(0)
    trainer.save_trace()
    names = {e["name"] for e in json.loads(out.read_text())["traceEvents"]
             if e["ph"] == "B"}
    assert {"train_epoch", "epoch_open", "after_group", "dispatch"} <= names


def test_trainer_without_a_tracer_has_none(devices, annotations):
    from ddp_practice_tpu.train.loop import Trainer

    trainer = Trainer(train_config())
    assert trainer.tracer is None
    trainer.train_epoch(0)
    assert annotations == []


# ------------------------------------------------- names a trace is read by
@pytest.mark.parametrize("attr,needle", [
    ("_prefill_jit", "prefill_admit"),
    ("_prefix_jit", "prefix_prefill"),
    ("_decode_jit", "decode_burst"),
    ("_verify_jit", "verify"),
])
def test_paged_programs_keep_the_names_the_readers_match(lm, attr, needle):
    """A device trace shows a program as `jit_<function name>`;
    `perf/lib/readers.py` finds the prefill and decode programs by these
    substrings (PERF.md §3)."""
    fn = getattr(make_engine(lm, "paged"), attr)
    assert needle in fn.__name__


@pytest.mark.parametrize("kind,ran,idle", [
    ("paged", "prefill_compiles", "prefix_prefill_compiles"),
    ("prefix", "prefix_prefill_compiles", "prefill_compiles"),
])
def test_an_admission_layout_runs_the_program_its_readers_match(
        lm, kind, ran, idle):
    """A cell's prefill time is read off ONE of the two program names: a
    left-padded admission must dispatch `_prefill_admit` alone and a
    prefix-cache admission `_prefix_prefill` alone."""
    sched, _, _ = serve(lm, kind, traced=False)
    stats = sched.engine.compile_stats()
    assert stats[ran] == 1 and stats[idle] == 0
    assert stats["decode_compiles"] == 1


def pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                out.extend(pallas_names(inner))
    return out


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_the_paged_decode_kernel_is_one_op_named_paged_decode(pool):
    """`perf/lib/readers.py paged_decode_roofline_pct` sums every traced
    op whose name holds "paged_decode": the kernel is ONE pallas_call a
    call (no gather beside it) and carries the name itself, whatever
    module scope it is traced under (tests/test_tpu_compile.py reads the
    compiled custom call's name)."""
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    slots, mb, bs, heads, d = 2, 3, 16, 2, 64
    dtype = jnp.bfloat16 if pool == "bf16" else jnp.int8
    pages = jnp.zeros((1 + slots * mb, bs, heads * d), dtype)
    scale = (None if pool == "bf16"
             else jnp.ones((pages.shape[0], heads, bs), jnp.float32))

    def step(q, k, v, table, lengths, start):
        return paged_decode_attention(
            q, k, v, table, lengths, start, n_heads=heads, k_scale=scale,
            v_scale=scale, impl="kernel")

    names = pallas_names(jax.make_jaxpr(step)(
        jnp.zeros((slots, 1, heads * d), jnp.bfloat16), pages, pages,
        jnp.zeros((slots, mb), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32)).jaxpr)
    assert len(names) == 1 and "paged_decode" in names[0], names


@pytest.mark.parametrize("heads,names", [
    (2, ("flash_fwd_packed", "flash_bwd_packed")),
    (3, ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"))])
def test_every_flash_kernel_carries_a_flash_name(heads, names):
    """`name=` is what the compiled custom call is named by (and so the
    op on a device trace's "XLA Ops" line, under shard_map too:
    tests/test_tpu_compile.py compiles it): the forward and the backward,
    packed (heads pair up in 128 lanes: ONE backward kernel since PR 31)
    and folded (two)."""
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.ops.flash_attention import flash_attention

    x = jnp.zeros((1, 256, heads, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    got = pallas_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr)
    assert sorted(got) == sorted(names)


def test_no_pallas_call_in_flash_attention_goes_unnamed():
    import ddp_practice_tpu.ops.flash_attention as fa

    src = open(fa.__file__).read()
    calls = src.count("pl.pallas_call(")
    assert calls == 9 == len(
        re.findall(r'name="flash_(short_)?(fwd|bwd)', src))
