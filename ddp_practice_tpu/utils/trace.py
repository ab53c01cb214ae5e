"""Request-lifecycle tracing: Dapper-style spans over an injectable clock.

The serving stack (Scheduler -> PagedEngine -> Router) and the
training loop both answer "where did the time go?" with aggregate gauges
only (utils/metrics.py) — a bad TTFT or a failover hop leaves no record
of queue wait vs bucketed prefill vs decode-burst stalls vs retry hops.
This module is the missing recorder:

- **Host-pure and thread-safe.** Nothing here touches jax; appends are
  deque ops under the GIL, snapshots take the lock. A serve loop is
  single-threaded, but submission may come from another thread.
- **Injectable clock.** The recorder reads time through the same clock
  the schedulers use (`MonotonicClock` in production, `FakeClock` in
  tests), so a chaos replay's trace is bit-for-bit deterministic.
- **Bounded.** Records live in a ring buffer (`max_events`); a
  long-lived server's tracing memory is O(1), and the exported timeline
  is the most recent window — a flight recorder, not an archive.
- **Zero-overhead when off.** A disabled recorder's `span()` returns a
  shared no-op context manager and `instant()` returns immediately; the
  instrumented hot paths additionally gate on `tracer is not None`, so
  the production default (no tracer) pays a single attribute test.
- **Streamable.** An optional `sink` (utils/telemetry.py
  TelemetryExporter) receives every record as a plain dict THE MOMENT it
  is recorded — line-delimited JSONL export that survives a SIGKILL,
  where `save()` (the exit-time Chrome dump) would leave nothing.
  tools/check_traces.py validates both forms.

Three record kinds, three Chrome trace-event encodings
(`to_chrome_trace()` emits the JSON Perfetto / chrome://tracing /
vLLM's tooling consume):

- **Lane spans** (`span()` / `record_span()`): synchronous work on one
  (pid, tid) lane — a prefill dispatch on a slot lane, a decode burst
  on the engine lane, a train step phase. Exported as matched B/E
  pairs, properly nested per lane (tools/check_traces.py validates).
- **Request spans** (`record_async()`): per-request lifecycle intervals
  ("request", "queued") that overlap freely across requests. Exported
  as Chrome ASYNC events (ph "b"/"e") keyed by `id=trace_id`, so one
  request renders as one timeline row however many replicas it crossed.
- **Instants** (`instant()`): point events (shed, retry, failover,
  brownout flip) — ph "i".

Lane conventions for serving (shared by both engines and the router):
pid = replica id (`ROUTER_PID` for the router's own lane), tid 0 =
`ENGINE_LANE` (decode dispatches + scheduler instants), tid 1+slot =
the slot's prefill lane. `label_replica()` / `label_router()` stamp the
matching process/thread-name metadata so traces open pre-labelled.

Trace-id propagation is the router's failover contract: a re-admitted
request's sub-Request carries the ORIGINAL trace_id, so a crash-migrated
request's spans on the survivor join the same async track as its spans
on the dead replica — one request, one timeline (pinned in
tests/test_trace.py).

Every lane span also records the span that CAUSED it (`parent`: the
`link` id of the innermost span open on the recording thread when it
began, on whatever lane either sits; a span draws its `link` when it
BEGINS, its `seq` when it is RECORDED, so `seq` stays the ring's order
and the OTLP drain's high-water mark), so a span's self time is its
duration minus its children's — from linkage, not guessed from overlap.
And with
`set_annotate(jax.profiler.TraceAnnotation, "serve")` (the engines and
the Trainer install it; this module never imports jax) every lane span
is mirrored, for its lifetime, as a profiler annotation named
`<prefix>:<span name>` — a closed set of names (`serve:tick`,
`serve:decode_burst`, `train:dispatch`, ...; request ids live in span
attrs, never in names). While a profiler session is open each program
span is therefore also an event on the profiler's host line, on the
device trace's own clock, and carries the span's scalar attributes as
the event's own stats (handed over as the span ENDS, so what a readback
filled in is there): the program's counts beside the device's lines, in
the profiler's viewer and for `perf/lib/annots.py`. An instant recorded
with `mirror=True` (`slow_tick`, `chunk_admit`) is a zero-length event of
the same kind.
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import json
import threading
import time
import zlib
from collections import defaultdict, deque
from typing import Dict, Optional

# record kinds (internal)
_DUR, _ASYNC, _INSTANT = 0, 1, 2

# serving lane conventions (see module doc)
ENGINE_LANE = 0          # tid for decode dispatches + scheduler instants
SLOT_LANE_BASE = 1       # tid = SLOT_LANE_BASE + slot for prefill spans
ROUTER_PID = -1          # the router's own pid (replicas are 0..N-1)

# the shared no-op span: what a disabled recorder hands out, and what
# instrumented hot paths use when no tracer is attached at all
NULL_SPAN = contextlib.nullcontext()
_NULL_SPAN = NULL_SPAN


def _resolve_clock(clock):
    """Accept a scheduler-style clock object (has .now()), a plain
    callable, or None (wall monotonic)."""
    if clock is None:
        return time.monotonic
    now = getattr(clock, "now", None)
    if callable(now):
        return now
    if callable(clock):
        return clock
    raise TypeError(f"clock must have .now() or be callable: {clock!r}")


# ------------------------------------------------------------- sampling
# Tail-keep markers: an instant/span with one of these names arriving for
# a staged (head-unsampled) request promotes the whole staged timeline on
# the spot — anomalies keep their traces even if the process dies before
# the request completes. The names match what the router/scheduler/engine
# already record ("retry"/"failover" instants, "preempted"/"preempt",
# "stale_retry", "replica_dead") plus the terminal status instants the
# scheduler stamps for non-eos/length outcomes.
KEEP_MARKERS = frozenset({
    "preempt", "preempted", "retry", "failover", "resumed",
    "stale_retry", "replica_dead", "shed", "timeout", "error",
    "rejected",
})

# statuses that terminate cleanly — anything else is a keep-worthy outcome
_CLEAN_STATUSES = ("eos", "length")


def head_keep(trace_id: str, rate: float) -> bool:
    """The deterministic head-sampling decision: keep `trace_id` at
    `rate`. Dapper's coherence rule is that this decision is made ONCE
    per request and honored by every process the request touches — so it
    must be a pure function of the trace_id, stable across OS processes.
    Python's builtin hash() is salted per interpreter (PYTHONHASHSEED)
    and would give the router and a worker process DIFFERENT answers;
    crc32 is stable everywhere."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    h = zlib.crc32(trace_id.encode("utf-8")) & 0xFFFFFFFF
    return (h / 4294967296.0) < rate


class TraceSampler:
    """Sampling policy: head rate + tail keep-rules.

    `rate` is the head-sampling probability (decided per trace_id by
    `head_keep`, or by an injected `decide` callable in tests);
    `keep_slow_s` is the latency threshold above which a completed
    request is tail-kept even when head-unsampled (SLO-derived: a
    straggler IS the interesting trace); `stage_limit` bounds the
    per-request staging area a head-unsampled request's spans wait in
    until its tail verdict; `tenant_rates` maps tenant id -> head rate
    override (a debugged tenant runs at 1.0 while the fleet default
    stays at 1%), consulted per request via `rate_for`. Tail keep-rules
    are deliberately tenant-blind: a fault-affected request keeps its
    trace whatever its tenant's head rate."""

    def __init__(self, rate: float = 1.0, *,
                 keep_slow_s: Optional[float] = None,
                 stage_limit: int = 256, decide=None,
                 tenant_rates: Optional[Dict[str, float]] = None) -> None:
        if stage_limit < 1:
            raise ValueError("stage_limit must be positive")
        self.rate = float(rate)
        self.keep_slow_s = keep_slow_s
        self.stage_limit = stage_limit
        self._decide = decide
        self.tenant_rates: Optional[Dict[str, float]] = (
            {str(k): float(v) for k, v in tenant_rates.items()}
            if tenant_rates else None)

    def rate_for(self, tenant: Optional[str] = None) -> float:
        """The head rate this request samples at: the tenant's override
        when one is configured, the fleet default otherwise."""
        if tenant is not None and self.tenant_rates:
            r = self.tenant_rates.get(tenant)
            if r is not None:
                return r
        return self.rate

    def sampled(self, trace_id: str,
                tenant: Optional[str] = None) -> bool:
        if self._decide is not None:
            return bool(self._decide(trace_id))
        return head_keep(trace_id, self.rate_for(tenant))

    def keep_reason(self, *, status: Optional[str] = None,
                    latency_s: Optional[float] = None,
                    retries: int = 0, failovers: int = 0
                    ) -> Optional[str]:
        """Tail verdict at completion: the keep reason, or None to
        suppress. Any non-clean terminal status, any retry/failover hop,
        or a latency past the slow threshold keeps the trace."""
        if status is not None and status not in _CLEAN_STATUSES:
            return str(status)
        if failovers:
            return "failover"
        if retries:
            return "retry"
        if (self.keep_slow_s is not None and latency_s is not None
                and latency_s > self.keep_slow_s):
            return "slow"
        return None


class _TailStage:
    """One head-unsampled request's bounded span staging area."""

    __slots__ = ("records", "dropped")

    def __init__(self, limit: int) -> None:
        self.records: deque = deque(maxlen=limit)
        self.dropped = 0


class _Rec:
    __slots__ = ("kind", "name", "t0", "t1", "pid", "tid", "trace_id",
                 "attrs", "seq", "link", "parent")

    def __init__(self, kind, name, t0, t1, pid, tid, trace_id, attrs, seq,
                 link=None, parent=None):
        self.kind = kind
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.pid = pid
        self.tid = tid
        self.trace_id = trace_id
        self.attrs = attrs
        # drawn when the record is made: the ring's own order
        self.seq = seq
        # lane spans only: this span's link id (drawn when it BEGAN) and
        # the link id of the span that caused it
        self.link = link
        self.parent = parent


def _hand_attrs(ann, attrs: dict) -> None:
    """The scalar attributes (int, float, bool, str; a list or None is
    left out) of a span that is ending, onto its mirror annotation."""
    scalars = {k: v for k, v in attrs.items()
               if isinstance(v, (int, float, str))}
    if scalars:
        ann.set_metadata(**scalars)


class _Span:
    """Context manager for one lane span; created only when enabled.

    Its `link` id is drawn when it BEGINS, so the spans it causes can
    name it as their parent before it is recorded (a parent is recorded
    after its children: records are appended as spans end, and draw
    their `seq` then). `attrs` may be filled in until it ends; `t0`/`t1`
    stay readable afterwards, and `caused` holds the seconds of every
    span it caused, by name (None if it caused none)."""

    __slots__ = ("rec", "name", "trace_id", "pid", "tid", "attrs", "t0",
                 "t1", "link", "parent", "caused", "sampled_only", "_ann")

    def __init__(self, rec, name, trace_id, pid, tid, attrs,
                 sampled_only=False):
        self.rec = rec
        self.name = name
        self.trace_id = trace_id
        self.pid = pid
        self.tid = tid
        self.attrs = attrs
        self.sampled_only = sampled_only
        self.caused = None

    def __enter__(self):
        rec = self.rec
        stack = rec._open_spans()
        self.parent = stack[-1] if stack else None
        self.link = next(rec._link)
        stack.append(self)
        # the mirror annotation begins when it is built: build it right
        # beside the clock read, in the order the benchmark's own marker
        # uses (annotation, then clock), so the two clocks are compared
        # at one instant
        self._ann = rec._annotation(self.name)
        self.t0 = rec._now()
        return self

    def __exit__(self, exc_type, exc, tb):
        rec = self.rec
        self.t1 = rec._now()
        ann = self._ann
        if ann is not None:
            if self.attrs and rec._session_open():
                _hand_attrs(ann, self.attrs)
            ann.__exit__(exc_type, exc, tb)
        rec._open_spans().pop()
        parent = self.parent
        if parent is not None:
            # hand the parent this span's seconds and what it caused
            total = parent.caused
            if total is None:
                total = parent.caused = {}
            total[self.name] = (total.get(self.name, 0.0)
                                + self.t1 - self.t0)
            if self.caused:
                for name, secs in self.caused.items():
                    total[name] = total.get(name, 0.0) + secs
        rec._record_span(
            self.name, self.t0, self.t1, self.trace_id, self.pid,
            self.tid, self.attrs or None, self.sampled_only, self.link,
            None if parent is None else parent.link,
        )
        return False


class TraceRecorder:
    """Bounded, clock-injected span/event recorder (see module doc)."""

    def __init__(self, *, clock=None, max_events: int = 65536,
                 enabled: bool = True, sink=None,
                 drop_counter=None) -> None:
        if max_events < 1:
            raise ValueError("max_events must be positive")
        self._now = _resolve_clock(clock)
        self.enabled = enabled
        self._records: deque = deque(maxlen=max_events)
        self._seq = itertools.count()
        self._lock = threading.Lock()
        # span-loss accounting: the ring buffer SILENTLY evicts the
        # oldest record when full — count every eviction (plus drops
        # reported by external producers, e.g. a worker's bounded trace
        # buffer via TraceCollector) so a truncated timeline is
        # observable instead of quietly validating. `drop_counter` is an
        # optional utils/metrics.py Counter (trace_events_dropped_total);
        # the count is also stamped into the export metadata so
        # tools/check_traces.py can warn.
        self.dropped = 0
        self._drop_counter = drop_counter
        self._process_names: Dict[int, str] = {}
        self._thread_names: Dict[tuple, str] = {}
        # streaming sink (utils/telemetry.py TelemetryExporter): called
        # with one plain dict per record AS IT IS RECORDED, so a killed
        # run's events survive outside this ring buffer. None = the
        # exit-time export (save()) is the only output.
        self._sink = None
        # head sampling + tail keep (None = record everything, the
        # pre-sampling behavior; see set_sampler / begin_trace)
        self.sampler: Optional[TraceSampler] = None
        self._head: Dict[str, int] = {}        # 0 staged / 1 head / 2 kept
        self._staged: Dict[str, _TailStage] = {}
        self._outcomes: Dict[str, bool] = {}   # finished trace -> recorded
        self._active_flowing = 0               # in-flight head/kept traces
        self.spans_sampled = 0                 # records kept by the head decision
        self.spans_kept = 0                    # records kept by a tail rule
        self.spans_suppressed = 0              # staged records discarded
        self.traces_sampled = 0
        self.traces_kept = 0
        self.traces_suppressed = 0
        self.kept_reasons: Dict[str, int] = {}
        self._c_sampled = self._c_kept = self._c_suppressed = None
        self._keep_registry = None
        # incremental OTLP drain state (drain_otlp / OtlpPusher): the
        # high-water seq already exported plus each trace's remembered
        # root spanId, so successive batches never re-emit a record
        # (spanIds must stay unique across a merged push capture) and a
        # span drained after its root shipped still parents onto it.
        self._otlp_drained = -1
        self._otlp_roots: Dict[str, str] = {}
        # per-thread stack of the lane spans open there (what a new
        # span's `parent` is read from) and the link ids they draw
        self._tls = threading.local()
        self._link = itertools.count()
        # profiler mirror (set_annotate): None = spans stay host-only
        self._annotate = None
        self._ann_live = None
        self._ann_prefix = ""
        self._ann_names: Dict[str, str] = {}
        if sink is not None:
            self.set_sink(sink)

    def set_annotate(self, fn, prefix: str) -> None:
        """Mirror every lane span into a profiler: `fn(name)` must
        return a context manager (the program passes
        `jax.profiler.TraceAnnotation`) and is entered for the span's
        lifetime under the name `<prefix>:<span name>`. With a profiler
        session open, each program span is then also an event on the
        profiler's host line, on the device trace's clock; with none,
        the annotation is a no-op of the profiler's. Where `fn` has an
        `is_enabled()` and it says a session is open, what `fn(name)`
        returned is handed the span's scalar attributes (int, float,
        bool, str) by `set_metadata(**kw)` just before it is left; a
        `fn` without `is_enabled` is never asked. `fn=None` turns the
        mirror off."""
        self._annotate = fn
        self._ann_live = getattr(fn, "is_enabled", None)
        self._ann_prefix = prefix
        self._ann_names = {}

    def _annotation(self, name: str):
        fn = self._annotate
        if fn is None:
            return None
        full = self._ann_names.get(name)
        if full is None:
            full = self._ann_names[name] = f"{self._ann_prefix}:{name}"
        ann = fn(full)
        ann.__enter__()
        return ann

    def _session_open(self) -> bool:
        """Whether the mirror's profiler says a session is open: asked
        once a span that has attributes, as it ends, so a recorder
        without a profiler pays this test and builds nothing."""
        live = self._ann_live
        return live is not None and live()

    def _open_spans(self) -> list:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def set_sink(self, sink) -> None:
        """Attach a streaming consumer: `sink(record_dict)` per span/
        async/instant record (kind-tagged; see _stream) plus one "meta"
        record per lane label. Already-recorded lane labels are replayed
        into the sink at attach time, so a sink attached after
        label_replica() still knows every pid."""
        self._sink = sink
        for pid, name in self._process_names.items():
            sink({"kind": "meta", "meta": "process_name",
                  "pid": pid, "name": name})
        for (pid, tid), name in self._thread_names.items():
            sink({"kind": "meta", "meta": "thread_name",
                  "pid": pid, "tid": tid, "name": name})

    def _stream(self, rec: dict) -> None:
        if self._sink is not None:
            self._sink(rec)

    def _stream_record(self, kind: str, name, pid, tid, trace_id,
                       attrs, **times) -> None:
        """Build + emit one sink record (callers gate on `_sink is not
        None` first, so the no-sink hot path never builds the dict).
        The stream schema has ONE producer: change it here, and every
        record kind follows."""
        rec = {"kind": kind, "name": name, **times, "pid": pid}
        if tid is not None:
            rec["tid"] = tid
        if trace_id is not None:
            rec["trace_id"] = trace_id
        if attrs:
            rec["attrs"] = attrs
        self._sink(rec)

    # ------------------------------------------------------------ recording
    def now(self) -> float:
        return self._now()

    def _append(self, rec: "_Rec") -> None:
        if len(self._records) == self._records.maxlen:
            self._note_drops(1)
        self._records.append(rec)

    def _note_drops(self, n: int) -> None:
        if n <= 0:
            return
        self.dropped += n
        if self._drop_counter is not None:
            self._drop_counter.inc(n)

    def count_external_drops(self, n: int) -> None:
        """Fold drops that happened OUTSIDE this ring buffer (a worker's
        bounded trace buffer, a full push queue) into this recorder's
        loss accounting — one number answers "is this timeline whole"."""
        self._note_drops(n)

    # ------------------------------------------------------------- sampling
    def set_sampler(self, sampler: Optional[TraceSampler], *,
                    registry=None) -> None:
        """Attach the sampling policy. With `registry` (utils/metrics.py
        MetricsRegistry), mints the accounting counters —
        trace_spans_sampled/kept/suppressed_total plus a per-reason
        trace_traces_kept_total{reason=...} family."""
        self.sampler = sampler
        if registry is not None:
            self._c_sampled = registry.counter("trace_spans_sampled_total")
            self._c_kept = registry.counter("trace_spans_kept_total")
            self._c_suppressed = registry.counter(
                "trace_spans_suppressed_total")
            self._keep_registry = registry

    def begin_trace(self, trace_id: Optional[str],
                    sampled: Optional[bool] = None, *,
                    tenant: Optional[str] = None) -> bool:
        """Stamp the head decision for one request at admission.
        Idempotent per trace_id (the router and a scheduler sharing one
        recorder both call it); `sampled` carries an upstream decision
        across the RPC seam (Dapper coherence: decided once, honored
        everywhere); `tenant` selects a per-tenant head-rate override
        when the sampler has one. Returns whether the request's spans
        flow."""
        if self.sampler is None or trace_id is None or not self.enabled:
            return True if sampled is None else bool(sampled)
        v = self._head.get(trace_id)
        if v is not None:
            return v != 0
        if sampled is None:
            sampled = self.sampler.sampled(trace_id, tenant)
        if len(self._head) >= 16384:
            # runaway begin/finish imbalance must not leak: evict the
            # oldest in-flight trace, suppressing anything it staged
            old, ov = next(iter(self._head.items()))
            del self._head[old]
            stg = self._staged.pop(old, None)
            if stg is not None:
                self._suppress(len(stg.records) + stg.dropped)
            elif ov != 0:
                self._active_flowing -= 1
        if sampled:
            self._head[trace_id] = 1
            self._active_flowing += 1
            self.traces_sampled += 1
        else:
            self._head[trace_id] = 0
            self._staged[trace_id] = _TailStage(self.sampler.stage_limit)
        return bool(sampled)

    def note_keep(self, trace_id: Optional[str],
                  reason: str = "marked") -> None:
        """Promote a staged request to kept RIGHT NOW (flush its staged
        spans; everything it records from here on flows). No-op for
        head-sampled / unknown / already-resolved traces."""
        if self.sampler is None or trace_id is None:
            return
        if self._head.get(trace_id) == 0:
            self._promote(trace_id, reason)

    def trace_recorded(self, trace_id: Optional[str]) -> bool:
        """Is this trace_id in the timeline (head-sampled, tail-kept, or
        sampling off)? The exemplar gate: a histogram exemplar citing a
        suppressed trace is a dead link."""
        if self.sampler is None or trace_id is None:
            return True
        v = self._head.get(trace_id)
        if v is not None:
            return v != 0
        return self._outcomes.get(trace_id, True)

    def finish_trace(self, trace_id: Optional[str], *,
                     status: Optional[str] = None,
                     latency_s: Optional[float] = None,
                     retries: int = 0, failovers: int = 0) -> bool:
        """The tail verdict at request completion: promote the staged
        spans when any keep-rule fires, otherwise discard them as
        suppressed. Returns whether the trace is in the timeline (the
        exemplar gate). Idempotent: a second finish (router after
        scheduler on a shared recorder) returns the first outcome."""
        if self.sampler is None or trace_id is None or not self.enabled:
            return True
        v = self._head.pop(trace_id, None)
        if v is None:
            return self._outcomes.get(trace_id, True)
        if v != 0:
            self._active_flowing -= 1
            self._remember(trace_id, True)
            return True
        stg = self._staged.pop(trace_id, None)
        reason = self.sampler.keep_reason(
            status=status, latency_s=latency_s, retries=retries,
            failovers=failovers)
        if reason is not None:
            self.traces_kept += 1
            self._count_reason(reason)
            if stg is not None:
                for r in stg.records:
                    self._flush_rec(r)
                self.spans_kept += len(stg.records)
                if self._c_kept is not None:
                    self._c_kept.inc(len(stg.records))
                if stg.dropped:
                    self._note_drops(stg.dropped)
            self._remember(trace_id, True)
            return True
        self.traces_suppressed += 1
        if stg is not None:
            self._suppress(len(stg.records) + stg.dropped)
        self._remember(trace_id, False)
        return False

    def _remember(self, trace_id: str, recorded: bool) -> None:
        self._outcomes[trace_id] = recorded
        if len(self._outcomes) > 8192:
            self._outcomes.pop(next(iter(self._outcomes)))

    def _suppress(self, n: int) -> None:
        if n <= 0:
            return
        self.spans_suppressed += n
        if self._c_suppressed is not None:
            self._c_suppressed.inc(n)

    def _count_reason(self, reason: str) -> None:
        self.kept_reasons[reason] = self.kept_reasons.get(reason, 0) + 1
        if self._keep_registry is not None:
            from .metrics import labelled
            self._keep_registry.counter(
                labelled("trace_traces_kept_total", reason=reason)).inc()

    def _promote(self, trace_id: str, reason: str) -> None:
        """Staged -> kept: flush the staging area into the ring + sink,
        record the reason, let subsequent records flow."""
        self._head[trace_id] = 2
        self._active_flowing += 1
        self.traces_kept += 1
        self._count_reason(reason)
        stg = self._staged.pop(trace_id, None)
        if stg is None:
            return
        for r in stg.records:
            self._flush_rec(r)
        self.spans_kept += len(stg.records)
        if self._c_kept is not None:
            self._c_kept.inc(len(stg.records))
        if stg.dropped:
            # staged overflow became real loss the moment we kept the
            # trace — fold it into the recorder's drop accounting
            self._note_drops(stg.dropped)

    def _flush_rec(self, r: "_Rec") -> None:
        self._append(r)
        if self._sink is None:
            return
        if r.kind == _DUR:
            self._stream_record("span", r.name, r.pid, r.tid,
                                r.trace_id, r.attrs, t0=r.t0, t1=r.t1)
        elif r.kind == _ASYNC:
            self._stream_record("async", r.name, r.pid, None,
                                r.trace_id, r.attrs, t0=r.t0, t1=r.t1)
        else:
            self._stream_record("instant", r.name, r.pid, r.tid,
                                r.trace_id, r.attrs, t=r.t0)

    def _admit(self, rec: "_Rec", sampled_only: bool = False) -> bool:
        """The sampling gate on every record: True = record now, False =
        staged or suppressed. Marker-named records promote their staged
        trace on the spot (anomalies survive even a later SIGKILL)."""
        tid_ = rec.trace_id
        if tid_ is None:
            # shared lane work (decode bursts): recorded only while some
            # sampled/kept request is in flight when the producer asked
            # for the gate — the residual cost at a 1% head rate
            if sampled_only and self._active_flowing == 0:
                self._suppress(1)
                return False
            return True
        v = self._head.get(tid_)
        if v is None:
            return True
        if v != 0:
            if v == 1:
                self.spans_sampled += 1
                if self._c_sampled is not None:
                    self._c_sampled.inc()
            else:
                self.spans_kept += 1
                if self._c_kept is not None:
                    self._c_kept.inc()
            return True
        if rec.name in KEEP_MARKERS:
            self._promote(tid_, rec.name)
            self.spans_kept += 1
            if self._c_kept is not None:
                self._c_kept.inc()
            return True
        stg = self._staged.get(tid_)
        if stg is None:    # defensive: decision says staged, stage gone
            return True
        if len(stg.records) == stg.records.maxlen:
            stg.dropped += 1
        stg.records.append(rec)
        return False

    def span(self, name: str, *, trace_id: Optional[str] = None,
             pid: int = 0, tid: int = 0, sampled_only: bool = False,
             **attrs):
        """Lane span context manager; a shared no-op when disabled.
        `sampled_only` marks shared-lane work (no trace_id of its own)
        that should be suppressed while nothing sampled is in flight."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, trace_id, pid, tid, attrs, sampled_only)

    def record_span(self, name: str, t0: float, t1: float, *,
                    trace_id: Optional[str] = None, pid: int = 0,
                    tid: int = 0, attrs: Optional[dict] = None,
                    sampled_only: bool = False) -> None:
        """Explicit-timestamp lane span (for intervals the caller timed).
        It hangs under whatever span is open on this thread now."""
        if not self.enabled:
            return
        stack = self._open_spans()
        self._record_span(name, t0, t1, trace_id, pid, tid, attrs,
                          sampled_only, next(self._link),
                          stack[-1].link if stack else None)

    def _record_span(self, name, t0, t1, trace_id, pid, tid, attrs,
                     sampled_only, link, parent) -> None:
        rec = _Rec(_DUR, name, t0, t1, pid, tid, trace_id, attrs,
                   next(self._seq), link, parent)
        if self.sampler is not None and not self._admit(rec, sampled_only):
            return
        self._append(rec)
        if self._sink is not None:
            self._stream_record("span", name, pid, tid, trace_id, attrs,
                                t0=t0, t1=t1)

    def record_async(self, name: str, t0: float, t1: float, *,
                     trace_id: str, pid: int = 0,
                     attrs: Optional[dict] = None) -> None:
        """Per-request interval: exported as async b/e keyed by trace_id,
        so overlapping requests never fight over one lane's B/E stack."""
        if not self.enabled:
            return
        rec = _Rec(
            _ASYNC, name, t0, t1, pid, 0, trace_id, attrs, next(self._seq)
        )
        if self.sampler is not None and not self._admit(rec):
            return
        self._append(rec)
        if self._sink is not None:
            self._stream_record("async", name, pid, None, trace_id,
                                attrs, t0=t0, t1=t1)

    def instant(self, name: str, *, trace_id: Optional[str] = None,
                pid: int = 0, tid: int = 0, mirror: bool = False,
                **attrs) -> None:
        """A point event, now. `mirror=True` also leaves it in an open
        profiler session as a zero-length annotation `<prefix>:<name>`
        with its scalar attributes (set_annotate)."""
        if not self.enabled:
            return
        if mirror and self._session_open():
            ann = self._annotation(name)
            _hand_attrs(ann, attrs)
            ann.__exit__(None, None, None)
        self.record_instant(name, self._now(), trace_id=trace_id,
                            pid=pid, tid=tid, attrs=attrs or None)

    def record_instant(self, name: str, t: float, *,
                       trace_id: Optional[str] = None, pid: int = 0,
                       tid: int = 0, attrs: Optional[dict] = None) -> None:
        """Explicit-timestamp instant — for events timed in another
        process's clock domain (TraceCollector merges worker instants
        with the measured offset already applied)."""
        if not self.enabled:
            return
        rec = _Rec(
            _INSTANT, name, t, t, pid, tid, trace_id, attrs or None,
            next(self._seq)
        )
        if self.sampler is not None and not self._admit(rec):
            return
        self._append(rec)
        if self._sink is not None:
            self._stream_record("instant", name, pid, tid, trace_id,
                                attrs or None, t=t)

    # ------------------------------------------------------------- metadata
    def set_process_name(self, pid: int, name: str) -> None:
        self._process_names[pid] = name
        self._stream({"kind": "meta", "meta": "process_name",
                      "pid": pid, "name": name})

    def set_thread_name(self, pid: int, tid: int, name: str) -> None:
        self._thread_names[(pid, tid)] = name
        self._stream({"kind": "meta", "meta": "thread_name",
                      "pid": pid, "tid": tid, "name": name})

    # ------------------------------------------------------------- plumbing
    def __len__(self) -> int:
        return len(self._records)

    def clear(self) -> None:
        """Drop recorded events (lane labels survive) — e.g. after a
        warmup phase whose compile-time spans would dwarf the workload.
        In-flight sampling decisions survive (a cleared recorder must
        still resolve its open requests coherently); their already-staged
        records are dropped with the ring, uncounted, like everything
        else clear() discards."""
        with self._lock:
            self._records.clear()
            for stg in self._staged.values():
                stg.records.clear()
                stg.dropped = 0

    def disable(self) -> None:
        self.enabled = False

    def enable(self) -> None:
        self.enabled = True

    # --------------------------------------------------------------- export
    def to_chrome_trace(self) -> dict:
        """Render the ring buffer as Chrome trace-event JSON.

        Lane spans become matched B/E pairs, emitted per (pid, tid) in
        stack order (outer-first at shared starts), so zero-duration
        spans on a FakeClock still nest cleanly; request spans become
        async b/e pairs keyed by id=trace_id; instants become ph "i".
        ts is microseconds of the recorder's clock domain.
        """
        with self._lock:
            records = list(self._records)
        events = []
        pids = ({r.pid for r in records} | set(self._process_names))
        for pid in sorted(pids):
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": self._process_names.get(pid, f"pid{pid}")},
            })
        lane_tids = {(r.pid, r.tid) for r in records if r.kind == _DUR}
        for (pid, tid) in sorted(set(self._thread_names) | lane_tids):
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": self._thread_names.get(
                    (pid, tid), f"tid{tid}")},
            })

        def us(t: float) -> float:
            return round(t * 1e6, 3)

        def begin(r: _Rec, ph: str) -> dict:
            ev = {"name": r.name, "ph": ph, "ts": us(r.t0),
                  "pid": r.pid, "tid": r.tid}
            args = dict(r.attrs) if r.attrs else {}
            if r.trace_id is not None:
                args["trace_id"] = r.trace_id
            if ph == "B":
                # linkage for self time: a child names its parent's link
                args["link"] = r.link
                if r.parent is not None:
                    args["parent"] = r.parent
            if args:
                ev["args"] = args
            if ph == "b":
                ev["cat"] = "request"
                ev["id"] = r.trace_id
            return ev

        def end(r: _Rec, ph: str) -> dict:
            ev = {"name": r.name, "ph": ph, "ts": us(r.t1),
                  "pid": r.pid, "tid": r.tid}
            if ph == "e":
                ev["cat"] = "request"
                ev["id"] = r.trace_id
            return ev

        def sweep(recs, b_ph, e_ph):
            """Emit properly nested begin/end pairs for one lane: sort by
            (start, -end, order begun), close every span that ends
            at-or-before the next span's start, drain at the end.
            Genuinely crossing intervals come out ts-disordered — the
            validator flags them rather than this export papering over
            them."""
            recs.sort(key=lambda r: (
                r.t0, -r.t1, r.seq if r.link is None else r.link))
            stack = []
            for r in recs:
                while stack and stack[-1].t1 <= r.t0:
                    events.append(end(stack.pop(), e_ph))
                events.append(begin(r, b_ph))
                stack.append(r)
            while stack:
                events.append(end(stack.pop(), e_ph))

        lanes = defaultdict(list)
        asyncs = defaultdict(list)
        instants = []
        for r in records:
            if r.kind == _DUR:
                lanes[(r.pid, r.tid)].append(r)
            elif r.kind == _ASYNC:
                asyncs[(r.pid, r.trace_id)].append(r)
            else:
                instants.append(r)
        for key in sorted(lanes):
            sweep(lanes[key], "B", "E")
        for key in sorted(asyncs, key=lambda k: (k[0], str(k[1]))):
            sweep(asyncs[key], "b", "e")
        for r in instants:
            ev = begin(r, "i")
            ev["s"] = "t"  # thread-scoped instant
            events.append(ev)
        out = {"traceEvents": events, "displayTimeUnit": "ms"}
        meta = {}
        if self.dropped:
            # a flight recorder that lost events must SAY so: the
            # validator (tools/check_traces.py) warns on this instead of
            # blessing a quietly truncated timeline
            meta["trace_events_dropped"] = self.dropped
        sm = self.sampling_meta()
        if sm is not None:
            # ...and a SAMPLED timeline must say it is partial BY POLICY
            # (suppressed != dropped): check_traces reads this back so a
            # missing lane for an unsampled request is not a loss warning
            meta["sampling"] = sm
        if meta:
            out["metadata"] = meta
        return out

    def sampling_meta(self) -> Optional[dict]:
        """The export-header sampling block; None when sampling is off."""
        if self.sampler is None:
            return None
        out = {
            "head_rate": self.sampler.rate,
            "keep_slow_s": self.sampler.keep_slow_s,
            "traces_sampled": self.traces_sampled,
            "traces_kept": self.traces_kept,
            "traces_suppressed": self.traces_suppressed,
            "spans_sampled": self.spans_sampled,
            "spans_kept": self.spans_kept,
            "spans_suppressed": self.spans_suppressed,
            "kept_reasons": dict(self.kept_reasons),
        }
        if self.sampler.tenant_rates:
            out["tenant_rates"] = dict(self.sampler.tenant_rates)
        return out

    def save(self, path: str) -> None:
        """Write the Chrome trace JSON (open in Perfetto / chrome://tracing)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    # --------------------------------------------------------- OTLP export
    def to_otlp(self, service_name: str = "ddp-serve") -> dict:
        """Render the per-request records as an OTLP-JSON
        ``ExportTraceServiceRequest`` (the shape an OTLP/HTTP collector
        accepts at /v1/traces), alongside the Chrome export.

        Mapping: every record carrying a trace_id becomes one span;
        traceId (16 bytes) / spanId (8 bytes) are derived by stable hash
        from the request's trace_id and the record identity, the
        "request" async span is the trace root and every other record
        parents onto it (lane spans and instants are children — instants
        become zero-duration spans). Records WITHOUT a trace_id (shared
        decode-burst lanes, clock_offset instants) are infrastructure,
        not request traces, and stay in the Chrome export only.
        Timestamps are the recorder's clock domain as unix-nanos strings
        (proto3 JSON int64); the original trace_id and lane rides along
        as ``ddp.*`` attributes, so tools/check_otlp.py can round-trip
        against the Chrome export. Resource attributes carry the
        sampling header."""
        with self._lock:
            records = [r for r in self._records if r.trace_id is not None]
        by_trace: Dict[str, list] = defaultdict(list)
        for r in records:
            by_trace[str(r.trace_id)].append(r)
        spans = []
        for tid_, recs in sorted(by_trace.items()):
            recs.sort(key=lambda r: (r.t0, r.seq))
            root_sid = None
            for r in recs:
                if r.kind == _ASYNC and r.name == "request":
                    root_sid = _otlp_span_id(tid_, r.seq)
                    break
            for r in recs:
                spans.append(_otlp_record_span(r, tid_, root_sid))
        return self._otlp_request(service_name, spans)

    def drain_otlp(self, service_name: str = "ddp-serve"
                   ) -> Optional[dict]:
        """Incremental OTLP export: the per-request records that entered
        the ring since the previous drain, as one
        ``ExportTraceServiceRequest`` (None when nothing is new). This is
        the push-plane producer (utils/telemetry.py OtlpPusher): each
        record is emitted in EXACTLY one batch — a seq high-water mark —
        so a collector that dedups whole batches by batch id never sees
        a duplicate spanId across the merged capture. The first
        "request" async span seen for a trace becomes (and stays) its
        root: spans in later batches parent onto it even though it
        shipped batches ago, and spans drained BEFORE their root exists
        go parentless — legal OTLP roots until the real root arrives."""
        with self._lock:
            records = [r for r in self._records
                       if r.trace_id is not None
                       and r.seq > self._otlp_drained]
            if not records:
                return None
            self._otlp_drained = max(r.seq for r in records)
        records.sort(key=lambda r: (str(r.trace_id), r.t0, r.seq))
        spans = []
        for r in records:
            tid_ = str(r.trace_id)
            root_sid = self._otlp_roots.get(tid_)
            if (root_sid is None and r.kind == _ASYNC
                    and r.name == "request"):
                root_sid = _otlp_span_id(tid_, r.seq)
                if len(self._otlp_roots) >= 16384:
                    self._otlp_roots.pop(next(iter(self._otlp_roots)))
                self._otlp_roots[tid_] = root_sid
            spans.append(_otlp_record_span(r, tid_, root_sid))
        return self._otlp_request(service_name, spans)

    def _otlp_request(self, service_name: str, spans: list) -> dict:
        """Wrap built spans in the export envelope (resource header =
        service name + sampling accounting + drop count)."""
        resource_attrs = {"service.name": service_name}
        sm = self.sampling_meta()
        if sm is not None:
            resource_attrs["ddp.sampling.head_rate"] = sm["head_rate"]
            resource_attrs["ddp.sampling.traces_kept"] = sm["traces_kept"]
            resource_attrs["ddp.sampling.traces_suppressed"] = (
                sm["traces_suppressed"])
            resource_attrs["ddp.sampling.spans_suppressed"] = (
                sm["spans_suppressed"])
        if self.dropped:
            resource_attrs["ddp.trace.dropped_events"] = self.dropped
        return {"resourceSpans": [{
            "resource": {"attributes": _otlp_attrs(resource_attrs)},
            "scopeSpans": [{
                "scope": {"name": "ddp_practice_tpu.trace"},
                "spans": spans,
            }],
        }]}

    def save_otlp(self, path: str,
                  service_name: str = "ddp-serve") -> None:
        """Write the OTLP-JSON export (tools/check_otlp.py validates)."""
        with open(path, "w") as f:
            json.dump(self.to_otlp(service_name=service_name), f)


def _otlp_trace_id(trace_id: str) -> str:
    """16-byte OTLP traceId as 32 hex chars, stable-hashed from the
    request trace_id (md5 as a hash, not a credential)."""
    return hashlib.md5(("ddp:" + trace_id).encode("utf-8")).hexdigest()


def _otlp_span_id(trace_id: str, seq: int) -> str:
    """8-byte OTLP spanId as 16 hex chars, unique per record."""
    return hashlib.md5(
        f"{trace_id}#{seq}".encode("utf-8")).hexdigest()[:16]


def _otlp_record_span(r: "_Rec", tid_: str,
                      root_sid: Optional[str]) -> dict:
    """One record -> one OTLP span (shared by the exit-time to_otlp and
    the incremental drain_otlp, so both exports speak the same shape).
    `root_sid` is the trace root's spanId or None; the root itself
    (sid == root_sid) carries the status instead of a parent link."""
    sid = _otlp_span_id(tid_, r.seq)
    attrs = {"ddp.trace_id": tid_, "ddp.pid": r.pid,
             "ddp.kind": ("span", "async", "instant")[r.kind]}
    if r.kind == _DUR:
        attrs["ddp.tid"] = r.tid
    if r.attrs:
        attrs.update(r.attrs)
    span = {
        "traceId": _otlp_trace_id(tid_),
        "spanId": sid,
        "name": str(r.name),
        "kind": 1,  # SPAN_KIND_INTERNAL
        "startTimeUnixNano": str(int(round(r.t0 * 1e9))),
        "endTimeUnixNano": str(int(round(r.t1 * 1e9))),
        "attributes": _otlp_attrs(attrs),
    }
    if root_sid is not None and sid != root_sid:
        span["parentSpanId"] = root_sid
    elif sid == root_sid:
        status = (r.attrs or {}).get("status")
        if status is not None:
            span["status"] = (
                {"code": 1} if status in _CLEAN_STATUSES
                else {"code": 2, "message": str(status)})
    return span


def _otlp_attrs(attrs: dict) -> list:
    """dict -> OTLP KeyValue list (string/bool/int/double values)."""
    out = []
    for k, v in attrs.items():
        if isinstance(v, bool):
            val = {"boolValue": v}
        elif isinstance(v, int):
            val = {"intValue": str(v)}
        elif isinstance(v, float):
            val = {"doubleValue": v}
        else:
            val = {"stringValue": str(v)}
        out.append({"key": str(k), "value": val})
    return out


# ------------------------------------------------------- lane label helpers
def label_replica(recorder: TraceRecorder, replica: int,
                  max_slots: int) -> None:
    """Stamp the serving lane names for one replica: pid=replica,
    tid 0 = engine (decode dispatches), tid 1+slot = prefill lanes."""
    recorder.set_process_name(replica, f"replica{replica}")
    recorder.set_thread_name(replica, ENGINE_LANE, "engine")
    for s in range(max_slots):
        recorder.set_thread_name(replica, SLOT_LANE_BASE + s, f"slot{s}")


def label_router(recorder: TraceRecorder) -> None:
    recorder.set_process_name(ROUTER_PID, "router")
    recorder.set_thread_name(ROUTER_PID, 0, "dispatch")


# -------------------------------------------------- adaptive head rate
class AdaptiveHeadRateController:
    """Feedback loop steering the head sample rate toward a kept-spans/
    sec budget — Dapper's production lesson, applied: the right rate is
    a function of observed traffic, not a hand-tuned constant baked
    into the fleet spec.

    Each `step(now)` past `interval_s` measures the kept-span flow from
    the recorder's own accounting counters (spans_sampled + spans_kept,
    the same totals trace_spans_*_total export) and applies one
    multiplicative correction `rate *= budget / observed`, clamped to
    [min_rate, max_rate] — kept flow is ~linear in the head rate, so a
    single step lands near the budget and the loop converges without a
    gain schedule. Two guards keep it from thrashing:

    - **deadband**: observed flow within ±`deadband` (fraction) of the
      budget is "on budget" — no correction, no churn.
    - **hold window**: after a change the rate holds for `hold_s`
      regardless of the error signal, so a correction's effect is
      actually OBSERVED before the next one (and, trivially, the rate
      never reverses inside its own hold window — the no-oscillation
      contract the tests pin).

    Every change is applied to the local sampler, pushed to the fleet
    via `apply_fn(new_rate)` (each worker handle's live rpc ``trace``
    op), and stamped into the timeline as a ``trace_rate`` instant —
    a span captured at 2% says so, right in the trace. Per-tenant
    overrides are left alone: the controller steers the fleet DEFAULT
    rate only.
    """

    def __init__(self, recorder: TraceRecorder, budget_sps: float, *,
                 clock=None, interval_s: float = 1.0,
                 min_rate: float = 0.001, max_rate: float = 1.0,
                 deadband: float = 0.1, hold_s: float = 5.0,
                 apply_fn=None) -> None:
        if budget_sps <= 0:
            raise ValueError("budget_sps must be positive")
        self.recorder = recorder
        self.budget_sps = float(budget_sps)
        self.interval_s = float(interval_s)
        self.min_rate = float(min_rate)
        self.max_rate = float(max_rate)
        self.deadband = float(deadband)
        self.hold_s = float(hold_s)
        self.apply_fn = apply_fn
        self._now = _resolve_clock(clock)
        sampler = recorder.sampler
        self.rate = sampler.rate if sampler is not None else 1.0
        self.changes = 0
        self.rate_log: list = []
        self._last_eval: Optional[float] = None
        self._last_count: Optional[int] = None
        self._last_change_t: Optional[float] = None
        self.last_observed_sps: Optional[float] = None

    def _kept_count(self) -> int:
        r = self.recorder
        return r.spans_sampled + r.spans_kept

    def step(self, now: Optional[float] = None) -> Optional[float]:
        """Evaluate once; returns the new rate when a change was applied,
        None otherwise. Call from the serve loop — cheap when the
        interval has not elapsed."""
        if now is None:
            now = self._now()
        if self._last_eval is None:
            # first call establishes the measurement baseline
            self._last_eval = now
            self._last_count = self._kept_count()
            return None
        dt = now - self._last_eval
        if dt < self.interval_s:
            return None
        count = self._kept_count()
        observed = (count - self._last_count) / dt
        self._last_eval = now
        self._last_count = count
        self.last_observed_sps = observed
        if abs(observed - self.budget_sps) <= (
                self.deadband * self.budget_sps):
            return None
        if (self._last_change_t is not None
                and now - self._last_change_t < self.hold_s):
            return None
        cur = self.rate
        if observed <= 0.0:
            # nothing kept at all: probe upward instead of dividing by 0
            new = cur * 2.0
        else:
            new = cur * (self.budget_sps / observed)
        new = min(self.max_rate, max(self.min_rate, new))
        if new == cur:
            return None
        self.rate = new
        self.changes += 1
        self._last_change_t = now
        self.rate_log.append({"t": now, "prev": cur, "rate": new,
                              "observed_sps": observed})
        if self.recorder.sampler is not None:
            self.recorder.sampler.rate = new
        self.recorder.record_instant(
            "trace_rate", now, pid=ROUTER_PID,
            attrs={"rate": new, "prev": cur, "observed_sps": observed,
                   "budget_sps": self.budget_sps})
        if self.apply_fn is not None:
            # fleet push (worker handles' live trace op) must never take
            # the control loop down with it
            try:
                self.apply_fn(new)
            except Exception:
                pass
        return new


# ------------------------------------------------------- fleet trace plane
class ClockOffsetEstimator:
    """NTP-style clock-offset estimate from RPC round trips.

    Worker processes stamp trace events with their OWN clocks; merging
    them onto the router's timeline needs the per-worker offset. Each
    ping/poll round trip yields one sample: the client reads its clock
    before (t0) and after (t3) the call, the worker stamps its clock
    (tw) while handling it; then

        offset = tw - (t0 + t3) / 2        (remote minus local)

    with worst-case error rtt/2 — the classic symmetric-delay bound
    (the true receive instant lies somewhere inside [t0, t3]; assuming
    the midpoint is wrong by at most half the round trip, however
    asymmetric the two legs actually were). So the BEST sample is the
    minimum-RTT one: we keep the lowest-RTT samples seen and answer
    with the lowest's offset, `bound` = its rtt/2. `reset()` on
    reconnect/restart — a new worker incarnation is a new clock domain.
    """

    def __init__(self, max_samples: int = 32) -> None:
        self.max_samples = max_samples
        self._samples: list = []   # (rtt, offset), sorted ascending rtt
        self.total_samples = 0

    def add(self, t0: float, t_remote: float, t3: float) -> bool:
        """Fold one round trip in; True when the best (min-RTT) sample
        — and therefore the answer — changed."""
        if t3 < t0:
            return False  # a torn reading is not a sample
        rtt = t3 - t0
        offset = t_remote - 0.5 * (t0 + t3)
        self.total_samples += 1
        best_before = self._samples[0] if self._samples else None
        self._samples.append((rtt, offset))
        self._samples.sort(key=lambda s: s[0])
        del self._samples[self.max_samples:]
        return self._samples[0] != best_before

    @property
    def n_samples(self) -> int:
        return len(self._samples)

    @property
    def offset(self) -> float:
        """Best current estimate of (remote clock - local clock); 0.0
        until a sample exists (merge unshifted rather than invent)."""
        return self._samples[0][1] if self._samples else 0.0

    @property
    def min_rtt(self) -> Optional[float]:
        return self._samples[0][0] if self._samples else None

    @property
    def bound(self) -> Optional[float]:
        """Worst-case error of `offset` (min observed rtt / 2)."""
        return self._samples[0][0] / 2.0 if self._samples else None

    def reset(self) -> None:
        self._samples.clear()


class TraceCollector:
    """Router-side merge of worker-streamed trace events into ONE fleet
    recorder.

    Workers record their own prefill/decode_burst/queued/request spans
    locally (serve/worker.py) and push them back over the RPC push
    stream as batched ``trace`` frames; this collector folds each frame
    into the fleet TraceRecorder so `--trace-out` exports one merged
    timeline — the Dapper collection step. Contracts:

    - **pid = worker lane.** Events arrive already stamped with the
      worker's replica pid (the PR-4 lane convention); `label_worker`
      names that lane ``worker-N`` so the merged trace reads as a fleet,
      and the worker's own ``replicaN`` process_name meta is dropped in
      favour of it. Cross-process trace_id propagation is untouched —
      a SIGKILL-failover request's pre-crash spans (streamed before the
      kill) and its survivor spans share the original trace_id, so it
      renders as ONE timeline.
    - **Clock alignment.** Every event timestamp is shifted by the
      worker's measured offset (ClockOffsetEstimator, fed by the
      handle's ping/poll round trips) at merge time; the current
      offset/bound is recorded as a ``clock_offset`` instant on the
      worker's lane whenever the estimate improves, so the exported
      trace carries its own skew model (tools/check_traces.py --fleet
      reads it back as the causality tolerance).
    - **At-most-once, any order.** Frames carry a per-incarnation
      sequence number; duplicates (transport retry / stream+poll
      overlap) are skipped, out-of-order frames merge fine because
      every record carries absolute timestamps (the exporter sorts).
      `on_worker_restart` resets seq dedup and the offset — a new
      process is a new stream and a new clock.
    - **Loss is counted, never silent.** Frames carry the worker's
      cumulative dropped count (bounded buffer + full push queues);
      the delta folds into the fleet recorder's `dropped` (and the
      optional ``trace_events_dropped_total`` counter), which the
      export stamps into its metadata.
    """

    def __init__(self, recorder: TraceRecorder, *,
                 registry=None) -> None:
        self.recorder = recorder
        if registry is not None and recorder._drop_counter is None:
            recorder._drop_counter = registry.counter(
                "trace_events_dropped_total"
            )
        self._estimators: Dict[int, ClockOffsetEstimator] = {}
        self._seen: Dict[int, set] = {}        # applied frame seqs
        self._last_dropped: Dict[int, int] = {}  # worker cumulative
        self._labelled: set = set()
        self.frames = 0
        self.events = 0
        self.duplicates = 0
        # merged span/async/instant events per worker — observable
        # progress of each worker's stream (tests gate chaos on it: a
        # kill is only meaningful once the victim's spans ARRIVED)
        self.events_by_worker: Dict[int, int] = {}

    # --------------------------------------------------- clock alignment
    def estimator(self, worker: int) -> ClockOffsetEstimator:
        est = self._estimators.get(worker)
        if est is None:
            est = self._estimators[worker] = ClockOffsetEstimator()
        return est

    def add_clock_sample(self, worker: int, t0: float, t_remote: float,
                         t3: float) -> None:
        est = self.estimator(worker)
        if est.add(t0, t_remote, t3):
            # the estimate improved: stamp the skew model into the
            # timeline itself (local clock domain — t3 just happened)
            self.recorder.record_instant(
                "clock_offset", t3, pid=worker,
                attrs={"offset_s": est.offset, "bound_s": est.bound,
                       "rtt_s": est.min_rtt, "samples": est.total_samples},
            )

    def offset(self, worker: int) -> float:
        est = self._estimators.get(worker)
        return est.offset if est is not None else 0.0

    def skew_bound(self, worker: Optional[int] = None) -> Optional[float]:
        """The measured worst-case skew — one worker's, or the fleet
        max (the causality tolerance check_traces --fleet applies)."""
        if worker is not None:
            est = self._estimators.get(worker)
            return est.bound if est is not None else None
        bounds = [e.bound for e in self._estimators.values()
                  if e.bound is not None]
        return max(bounds) if bounds else None

    # ----------------------------------------------------------- labels
    def label_worker(self, worker: int, max_slots: int) -> None:
        """Name the worker's merged lanes (pid=worker, the same
        engine/slot tid layout label_replica stamps in-process)."""
        self._labelled.add(worker)
        self.recorder.set_process_name(worker, f"worker-{worker}")
        self.recorder.set_thread_name(worker, ENGINE_LANE, "engine")
        for s in range(max_slots):
            self.recorder.set_thread_name(
                worker, SLOT_LANE_BASE + s, f"slot{s}")

    # ------------------------------------------------------ the ingest
    def on_worker_restart(self, worker: int) -> None:
        """A new incarnation numbers its own frames and runs its own
        clock: forget the old stream's dedup set, offset, and drop
        baseline (cumulative counts restart at 0)."""
        self._seen.pop(worker, None)
        self._last_dropped.pop(worker, None)
        est = self._estimators.get(worker)
        if est is not None:
            est.reset()

    def ingest(self, worker: int, frame: dict) -> int:
        """Merge one ``trace`` push frame; returns events applied
        (0 for a duplicate)."""
        seq = frame.get("seq")
        if seq is not None:
            seen = self._seen.setdefault(worker, set())
            if seq in seen:
                self.duplicates += 1
                return 0
            seen.add(seq)
            if len(seen) > 8192:   # bounded dedup window, newest kept
                cut = max(seen) - 8192
                self._seen[worker] = {s for s in seen if s > cut}
        dropped = frame.get("dropped")
        if dropped is not None:
            delta = dropped - self._last_dropped.get(worker, 0)
            if delta > 0:
                self.recorder.count_external_drops(delta)
            self._last_dropped[worker] = dropped
        if not self.recorder.enabled:
            # plane toggled off: the frame is consumed (seq marked,
            # drops booked) but nothing merges — record_* would no-op
            # silently, and counting phantom events would make
            # `events_by_worker` overstate what the timeline holds
            return 0
        off = self.offset(worker)
        rec = self.recorder
        # sampling coherence: a worker only streams spans for requests
        # it decided belong in the timeline (head-sampled or tail-kept).
        # If the router staged its own records for such a trace (its
        # dispatch/failover instants), honor the worker's keep decision
        # — one request, one verdict, fleet-wide.
        if rec.sampler is not None:
            for t in {ev.get("trace_id") for ev in frame.get("events", ())
                      if ev.get("trace_id") is not None}:
                rec.note_keep(t, "remote")
        n = 0
        for ev in frame.get("events", ()):
            kind = ev.get("kind")
            if kind == "span":
                rec.record_span(
                    ev["name"], ev["t0"] - off, ev["t1"] - off,
                    trace_id=ev.get("trace_id"), pid=ev.get("pid", worker),
                    tid=ev.get("tid", 0), attrs=ev.get("attrs"),
                )
            elif kind == "async":
                rec.record_async(
                    ev["name"], ev["t0"] - off, ev["t1"] - off,
                    trace_id=ev.get("trace_id"),
                    pid=ev.get("pid", worker), attrs=ev.get("attrs"),
                )
            elif kind == "instant":
                rec.record_instant(
                    ev["name"], ev["t"] - off,
                    trace_id=ev.get("trace_id"),
                    pid=ev.get("pid", worker), tid=ev.get("tid", 0),
                    attrs=ev.get("attrs"),
                )
            elif kind == "meta":
                # the collector's worker-N lane names win over the
                # worker's own replicaN process label; thread names
                # (engine/slotK) pass through for lanes not yet named
                if ev.get("meta") == "process_name":
                    if ev.get("pid") not in self._labelled:
                        rec.set_process_name(ev["pid"], ev["name"])
                elif ev.get("meta") == "thread_name":
                    key = (ev.get("pid"), ev.get("tid"))
                    if key not in rec._thread_names:
                        rec.set_thread_name(ev["pid"], ev["tid"],
                                            ev["name"])
                n -= 1  # meta is bookkeeping, not a merged event
            else:
                n -= 1
            n += 1
        self.frames += 1
        self.events += max(0, n)
        self.events_by_worker[worker] = (
            self.events_by_worker.get(worker, 0) + max(0, n)
        )
        return max(0, n)
