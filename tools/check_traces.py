"""Chrome trace-event JSON validator (utils/trace.py exports).

A trace that loads in Perfetto is not necessarily a *correct* trace —
the viewer silently drops unmatched E events, reorders by ts, and
invents rows for unknown pids, so a broken exporter can look fine until
the one debugging session that depends on it. This validator makes the
schema a checkable contract, used two ways:

- from tests: ``from tools.check_traces import validate`` — returns a
  list of error strings (empty = clean), asserted empty by
  tests/test_trace.py on every exported trace;
- as a CLI for eyeballing bench artifacts::

      python tools/check_traces.py t.json [more.json ...]

  prints a per-file verdict + span summary, exit 1 on any error.

Two input forms, auto-detected per file:

- the single-JSON Chrome dump `TraceRecorder.save()` writes at exit;
- a STREAMED telemetry JSONL (utils/telemetry.py TelemetryExporter):
  one kind-tagged event per line. `parse_stream_text` re-assembles the
  trace-shaped lines (meta/span/async/instant; flight/metrics/alert
  lines are telemetry, not trace, and are skipped) into a Chrome trace
  — spans become complete "X" events, so streaming needs no B/E
  matching — and tolerates EXACTLY ONE truncated line at EOF (the line
  a SIGKILL cut mid-write); garbage anywhere else is an error.

Checks (each one a real corruption mode of the exporter):

- top level is ``{"traceEvents": [...]}``; every event has name/ph/pid/
  tid, and (except metadata) a finite ts >= 0;
- **known pids**: every event's pid carries a ``process_name`` metadata
  record — an undeclared pid means an instrumentation site bypassed the
  lane conventions (utils/trace.py label_replica/label_router);
- **matched B/E pairs** per (pid, tid) lane: stack discipline, E names
  match the open B, nothing left open at EOF;
- **monotonic ts** within each lane's B/E stream in file order — a
  violation means the exporter emitted crossing (non-nested) intervals;
- **matched async b/e** per (pid, id): b before e, same name, ts
  ordered, nothing left open;
- only known phases (B E b e i M X C) appear;
- **parent linkage**: a lane span that names a `parent` (args.parent =
  the args.link of the span that caused it, on whatever lane) must lie
  inside that parent's interval — self time is duration minus
  children, which only holds if children are inside. A parent that is
  not in the file (sampled out, evicted from the ring) is no error.

FLEET mode (``--fleet [--skew-s S]``): the extra contracts of a MERGED
cross-process timeline (utils/trace.py TraceCollector):

- **cross-process causality**: every router ``dispatch`` instant
  (pid=router, args replica/trace_id) must precede that worker's
  ``queued``/``request`` span start for the same trace_id — within the
  clock-skew tolerance. The tolerance is the trace's own measured skew
  model (the ``clock_offset`` instants the collector stamps, worst
  bound across workers) unless ``--skew-s`` overrides it;
- a killed worker's TRUNCATED stream is tolerated: missing worker-side
  spans are not an error (the spans that did arrive pre-crash still
  validate), only an out-of-order one is;
- dropped-event metadata (``trace_events_dropped``) prints as a WARNING
  either way — a lossy timeline is usable but must say so;
- a SAMPLED timeline (``metadata.sampling``, utils/trace.py
  TraceSampler) is a *partial by policy* timeline: a dispatch whose
  worker lane is absent is exactly what a 1% head rate produces, so
  the missing-lane tolerance above is load-bearing, not charity. The
  sampling header prints as an INFO line — suppressed-by-policy spans
  are an operator choice and must never be confused with
  dropped-by-buffer spans (data loss), which keep their WARNING.
"""

from __future__ import annotations

import json
import sys
from collections import Counter, defaultdict
from typing import List

_KNOWN_PH = {"B", "E", "b", "e", "i", "M", "X", "C"}


def validate(trace) -> List[str]:
    """Validate a parsed Chrome trace object; return error strings."""
    errors: List[str] = []
    if not isinstance(trace, dict) or not isinstance(
            trace.get("traceEvents"), list):
        return ["top level must be an object with a 'traceEvents' list"]
    events = trace["traceEvents"]
    known_pids = {
        ev.get("pid") for ev in events
        if isinstance(ev, dict) and ev.get("ph") == "M"
        and ev.get("name") == "process_name"
    }
    lane_stacks = defaultdict(list)     # (pid, tid) -> [(name, ts)]
    lane_last_ts = {}                   # (pid, tid) -> last B/E ts seen
    async_open = defaultdict(list)      # (pid, id) -> [(name, ts)]
    linked = {}                         # link -> (name, t0, t1, parent)
    for i, ev in enumerate(events):
        where = f"event {i}"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        name = ev.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: missing/empty name")
            continue
        where = f"event {i} ({ph} {name!r})"
        if ph not in _KNOWN_PH:
            errors.append(f"{where}: unknown ph {ph!r}")
            continue
        if "pid" not in ev or "tid" not in ev:
            errors.append(f"{where}: missing pid/tid")
            continue
        pid, tid = ev["pid"], ev["tid"]
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or not (
                ts == ts and abs(ts) != float("inf")):
            errors.append(f"{where}: ts must be a finite number, got {ts!r}")
            continue
        if ts < 0:
            errors.append(f"{where}: negative ts {ts}")
        if pid not in known_pids:
            errors.append(
                f"{where}: pid {pid!r} has no process_name metadata"
            )
        if ph in ("B", "E"):
            lane = (pid, tid)
            last = lane_last_ts.get(lane)
            if last is not None and ts < last:
                errors.append(
                    f"{where}: lane {lane} ts went backwards "
                    f"({last} -> {ts}) — crossing intervals?"
                )
            lane_last_ts[lane] = ts
            if ph == "B":
                lane_stacks[lane].append((name, ts, ev.get("args") or {}))
            else:
                if not lane_stacks[lane]:
                    errors.append(f"{where}: E with no open B on {lane}")
                else:
                    open_name, open_ts, args = lane_stacks[lane].pop()
                    if "link" in args:
                        linked[args["link"]] = (open_name, open_ts, ts,
                                               args.get("parent"))
                    if open_name != name:
                        errors.append(
                            f"{where}: E closes {open_name!r} "
                            f"(B/E name mismatch on {lane})"
                        )
                    elif ts < open_ts:
                        errors.append(
                            f"{where}: span ends before it starts "
                            f"({open_ts} -> {ts})"
                        )
        elif ph in ("b", "e"):
            aid = ev.get("id")
            if aid is None:
                errors.append(f"{where}: async event without id")
                continue
            key = (pid, aid)
            if ph == "b":
                async_open[key].append((name, ts))
            else:
                if not async_open[key]:
                    errors.append(
                        f"{where}: async e with no open b for id {aid!r}"
                    )
                else:
                    open_name, open_ts = async_open[key].pop()
                    if open_name != name:
                        errors.append(
                            f"{where}: async e closes {open_name!r} "
                            f"(name mismatch for id {aid!r})"
                        )
                    elif ts < open_ts:
                        errors.append(
                            f"{where}: async span for id {aid!r} ends "
                            f"before it starts ({open_ts} -> {ts})"
                        )
    for link, (name, t0, t1, parent) in linked.items():
        if parent in linked:
            p_name, p0, p1, _ = linked[parent]
            if t0 < p0 or t1 > p1:
                errors.append(
                    f"span {name!r} (link {link}, {t0}..{t1}) lies outside "
                    f"its parent {p_name!r} (link {parent}, {p0}..{p1})"
                )
    for lane, stack in lane_stacks.items():
        if stack:
            errors.append(
                f"lane {lane}: {len(stack)} unclosed B "
                f"(top: {stack[-1][0]!r})"
            )
    for key, stack in async_open.items():
        if stack:
            errors.append(
                f"async id {key[1]!r} (pid {key[0]}): "
                f"{len(stack)} unclosed b"
            )
    return errors


def measured_skew(trace) -> dict:
    """Per-pid worst-case clock-skew bound from the ``clock_offset``
    instants the TraceCollector stamps (empty when the trace carries
    no skew model — a single-process trace, or offsets never measured)."""
    bounds: dict = {}
    for ev in trace.get("traceEvents", []):
        if not (isinstance(ev, dict) and ev.get("ph") == "i"
                and ev.get("name") == "clock_offset"):
            continue
        b = (ev.get("args") or {}).get("bound_s")
        if isinstance(b, (int, float)):
            pid = ev.get("pid")
            # the estimate improves over the run, but events merged
            # EARLY were shifted under the then-current (cruder)
            # offset: the honest per-pid tolerance is the WORST bound
            # that was ever in effect, not the final tightest one
            bounds[pid] = max(b, bounds.get(pid, 0.0))
    return bounds


def validate_fleet(trace, skew_s=None) -> List[str]:
    """Fleet-merge causality checks on top of `validate` (run both).

    For every router ``dispatch`` instant targeting (replica R,
    trace_id T): if worker R recorded any ``queued``/``request`` span
    start for T, at least one must start at-or-after the dispatch
    minus the skew tolerance — time cannot flow backwards across the
    RPC hop by more than the measured clock uncertainty. A worker with
    NO spans for a dispatched trace_id is tolerated (SIGKILL truncates
    streams mid-run; the merged timeline stays valid, just shorter).
    `skew_s` None = use the trace's own measured bounds (plus a small
    floor for quantization), falling back to 50 ms when unmeasured.
    """
    errors: List[str] = []
    events = trace.get("traceEvents", [])
    if not isinstance(events, list):
        return errors
    bounds = measured_skew(trace)
    default_skew = max(bounds.values()) if bounds else 0.05
    dispatches = []          # (ts_us, replica, trace_id)
    starts = {}              # (pid, trace_id) -> [start_ts_us, ...]
    for ev in events:
        if not isinstance(ev, dict):
            continue
        args = ev.get("args") or {}
        if ev.get("ph") == "i" and ev.get("name") == "dispatch":
            if "replica" in args and "trace_id" in args:
                dispatches.append(
                    (ev.get("ts"), args["replica"], args["trace_id"])
                )
        elif ev.get("ph") in ("b", "X") and ev.get("name") in (
                "queued", "request"):
            tid = args.get("trace_id", ev.get("id"))
            if tid is not None:
                key = (ev.get("pid"), tid)
                starts.setdefault(key, []).append(ev.get("ts"))
    if not dispatches:
        return errors
    for ts, replica, trace_id in dispatches:
        got = starts.get((replica, trace_id))
        if not got:
            continue  # truncated worker stream: tolerated
        skew = skew_s if skew_s is not None else max(
            bounds.get(replica, default_skew), 0.001
        )
        tol_us = skew * 1e6
        if max(got) < ts - tol_us:
            errors.append(
                f"causality: dispatch of {trace_id!r} to replica "
                f"{replica} at {ts}us but every worker-side span "
                f"starts before it (latest {max(got)}us, "
                f"tolerance {tol_us:.0f}us) — merge offsets wrong?"
            )
    return errors


def chrome_from_stream(records) -> dict:
    """Assemble streamed telemetry records into a Chrome trace object.

    Lane spans arrive COMPLETE (the recorder streams at span end), so
    they export as ph "X" (ts + dur) — no B/E pairing to get wrong;
    async spans become adjacent b/e pairs keyed by trace_id; instants
    and lane-label metadata map 1:1. Non-trace kinds (flight, metrics,
    alert, telemetry_close) are skipped: they ride the same JSONL but
    belong to tools/check_slo.py.
    """
    events = []

    def us(t):
        return round(float(t) * 1e6, 3)

    def args_of(r):
        args = dict(r.get("attrs") or {})
        if r.get("trace_id") is not None:
            args["trace_id"] = r["trace_id"]
        return args

    for r in records:
        kind = r.get("kind")
        if kind == "meta":
            if r.get("meta") == "process_name":
                events.append({
                    "name": "process_name", "ph": "M",
                    "pid": r["pid"], "tid": 0,
                    "args": {"name": r["name"]},
                })
            else:
                events.append({
                    "name": "thread_name", "ph": "M",
                    "pid": r["pid"], "tid": r["tid"],
                    "args": {"name": r["name"]},
                })
        elif kind == "span":
            ev = {"name": r["name"], "ph": "X", "ts": us(r["t0"]),
                  "dur": round((r["t1"] - r["t0"]) * 1e6, 3),
                  "pid": r["pid"], "tid": r["tid"]}
            args = args_of(r)
            if args:
                ev["args"] = args
            events.append(ev)
        elif kind == "async":
            base = {"name": r["name"], "cat": "request",
                    "id": r["trace_id"], "pid": r["pid"], "tid": 0}
            b = dict(base, ph="b", ts=us(r["t0"]))
            args = args_of(r)
            if args:
                b["args"] = args
            events.append(b)
            events.append(dict(base, ph="e", ts=us(r["t1"])))
        elif kind == "instant":
            ev = {"name": r["name"], "ph": "i", "s": "t",
                  "ts": us(r["t"]), "pid": r["pid"],
                  "tid": r.get("tid", 0)}
            args = args_of(r)
            if args:
                ev["args"] = args
            events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def iter_stream_records(text: str):
    """Tail-tolerant telemetry JSONL loader -> (records, truncated,
    errors).

    THE parsing rule of the streaming format, shared with
    tools/check_slo.py: `truncated` is True when the LAST line failed
    to parse — the signature of a run killed mid-write, tolerated by
    design. An unparseable line anywhere ELSE lands in `errors`: the
    line-by-line format means a crash can only ever damage the tail.
    A file whose ONLY line is the truncated one yields no records and
    an error — that is a corrupt single-JSON artifact, not a stream.
    """
    errors = []
    records = []
    truncated = False
    lines = text.split("\n")
    # drop trailing empty strings from the final newline
    while lines and not lines[-1].strip():
        lines.pop()
    for i, ln in enumerate(lines):
        ln = ln.strip()
        if not ln:
            continue
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                truncated = True
            else:
                errors.append(f"line {i + 1}: unparseable JSONL (only "
                              "the final line may be crash-truncated)")
            continue
        if not isinstance(rec, dict) or "kind" not in rec:
            errors.append(f"line {i + 1}: not a kind-tagged object")
            continue
        records.append(rec)
    if truncated and not records:
        errors.append(
            "no parseable line at all — a truncated single-JSON dump, "
            "not a telemetry stream"
        )
    elif not records and not errors:
        errors.append("empty file — neither a trace dump nor a stream")
    return records, truncated, errors


def parse_stream_text(text: str):
    """Parse telemetry JSONL -> (chrome_trace, truncated_tail, errors)."""
    records, truncated, errors = iter_stream_records(text)
    return chrome_from_stream(records), truncated, errors


def summarize(trace) -> dict:
    """Counts for the CLI report: events by phase, spans by name."""
    events = trace.get("traceEvents", [])
    by_ph = Counter(ev.get("ph") for ev in events if isinstance(ev, dict))
    spans = Counter(
        ev.get("name") for ev in events
        if isinstance(ev, dict) and ev.get("ph") in ("B", "b", "X")
    )
    pids = sorted({
        ev.get("pid") for ev in events
        if isinstance(ev, dict) and "pid" in ev
    }, key=str)
    return {"events": len(events), "by_ph": dict(by_ph),
            "spans": dict(spans), "pids": pids}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else list(argv)
    if not args or args[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    fleet = False
    skew_s = None
    paths = []
    it = iter(args)
    for a in it:
        if a == "--fleet":
            fleet = True
        elif a == "--skew-s":
            try:
                skew_s = float(next(it))
            except (StopIteration, ValueError):
                print("--skew-s wants a number (seconds)")
                return 1
        else:
            paths.append(a)
    if not paths:
        print("no trace files given")
        return 1
    rc = 0
    for path in paths:
        try:
            with open(path) as f:
                text = f.read()
        except OSError as e:
            print(f"{path}: UNREADABLE — {e}")
            rc = 1
            continue
        # auto-detect: a Chrome dump is ONE JSON object; anything that
        # doesn't parse whole is treated as streamed JSONL
        trace = None
        note = ""
        if text.lstrip().startswith("{"):
            try:
                parsed = json.loads(text)
                # a one-line JSONL file also parses whole — only a
                # traceEvents object is actually the dump form
                if isinstance(parsed, dict) and "traceEvents" in parsed:
                    trace = parsed
            except json.JSONDecodeError:
                trace = None
        if trace is None:
            trace, truncated, errors = parse_stream_text(text)
            if truncated:
                note = " (crash-truncated tail line skipped)"
        else:
            errors = []
        errors += validate(trace)
        if fleet:
            errors += validate_fleet(trace, skew_s)
        dropped = 0
        sampling = None
        if isinstance(trace, dict):
            md = trace.get("metadata")
            if isinstance(md, dict):
                dropped = md.get("trace_events_dropped", 0) or 0
                if isinstance(md.get("sampling"), dict):
                    sampling = md["sampling"]
        s = summarize(trace)
        if errors:
            rc = 1
            print(f"{path}: INVALID ({len(errors)} error(s); "
                  f"{s['events']} events)")
            for e in errors[:20]:
                print(f"  - {e}")
            if len(errors) > 20:
                print(f"  ... and {len(errors) - 20} more")
        else:
            top = sorted(s["spans"].items(), key=lambda kv: -kv[1])[:8]
            spans = ", ".join(f"{n} x{c}" for n, c in top) or "none"
            print(f"{path}: OK — {s['events']} events, "
                  f"pids {s['pids']}, spans: {spans}{note}")
        if sampling:
            # informational, NOT a warning: suppressed spans are an
            # operator policy (head rate), not data loss — the tail
            # keep-rules promoted every anomalous trace regardless
            kept = sampling.get("kept_reasons") or {}
            reasons = ", ".join(
                f"{k}={v}" for k, v in sorted(kept.items())) or "none"
            print(f"{path}: INFO — sampled timeline (head rate "
                  f"{sampling.get('head_rate')}): "
                  f"{sampling.get('spans_suppressed', 0)} span(s) "
                  f"suppressed by policy, "
                  f"{sampling.get('traces_kept', 0)} trace(s) "
                  f"tail-kept ({reasons}); partial lanes here are "
                  f"sampling, not loss")
        if dropped:
            # a warning, not a verdict: the timeline is valid but has a
            # hole — whoever reads it should know before trusting gaps
            print(f"{path}: WARNING — {dropped} trace event(s) were "
                  f"dropped (bounded buffers, distinct from sampling "
                  f"suppression); the timeline is truncated, not "
                  f"corrupt")
    return rc


if __name__ == "__main__":
    sys.exit(main())
