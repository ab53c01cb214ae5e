"""The offline tools exercised over CHECKED-IN bench artifacts, via
their real CLIs (subprocess, exit codes asserted) — so tools/
check_traces.py and tools/check_slo.py cannot silently rot while the
modules they validate move on (ISSUE 5 CI satellite).

The artifacts are a deterministic FakeClock 2-replica chaos run
(nan_logits fault plan, SLO watchdog armed):

- tests/data/bench_trace.json      — the exit-time Chrome dump
- tests/data/bench_telemetry.jsonl — the STREAMED telemetry of the same
  run (trace events, flight records, alert edges, metrics snapshots)

Both forms must stay validator-clean; the JSONL must render an SLO
verdict both ways (the chaos run violates a tight error-rate SLO and
meets a loose one).
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = os.path.join(ROOT, "tests", "data", "bench_trace.json")
TELEMETRY = os.path.join(ROOT, "tests", "data", "bench_telemetry.jsonl")
# federated-fleet /healthz snapshots (ScrapeFederator output shape):
# _ok is a 2-worker healthy fleet (full-plane wrapper form, metrics
# included); _bad has one FAILED slot (restart budget spent) and one
# heartbeat-stale worker — the two verdicts check_fleet exists to catch
FLEET_OK = os.path.join(ROOT, "tests", "data", "fleet_healthz_ok.json")
FLEET_BAD = os.path.join(ROOT, "tests", "data", "fleet_healthz_bad.json")
# the elastic pair (ISSUE 14): a fleet mid-scale-down whose draining
# worker has gone quiet ON PURPOSE, and the same snapshot with the
# drain flag unset + an autoscaler size outside [min, max]
ELASTIC_OK = os.path.join(ROOT, "tests", "data",
                          "fleet_healthz_autoscale_ok.json")
ELASTIC_BAD = os.path.join(ROOT, "tests", "data",
                           "fleet_healthz_autoscale_bad.json")
# the cache-aware pair (ISSUE 15): _ok is a 2-worker fleet whose
# heartbeats carry the full kv summary + prefix digest (one full
# frame, one delta frame — both wire forms rendered); _bad has a
# worker claiming more blocks in use than its pool holds — the
# accounting the affinity router scores against is lying
CACHE_OK = os.path.join(ROOT, "tests", "data",
                        "fleet_healthz_cache_ok.json")
CACHE_BAD = os.path.join(ROOT, "tests", "data",
                         "fleet_healthz_cache_bad.json")
# streaming exactly-once audit artifacts: a deterministic FakeClock
# 2-replica run with a scripted mid-stream crash (so the PASSING
# artifact contains resumed markers — failover is part of the
# contract, not a violation); _bad is the same run with one chunk line
# replayed (duplicate seq + token overlap) and one stream's terminal
# dropped (ended in silence)
STREAM_OK = os.path.join(ROOT, "tests", "data", "stream_chunks_ok.jsonl")
STREAM_BAD = os.path.join(ROOT, "tests", "data", "stream_chunks_bad.jsonl")

# the SLO the artifact run was recorded against (it violates this one)
TIGHT_SLO = json.dumps({
    "error_rate": 0.05, "fast_window_s": 0.3, "slow_window_s": 1.0,
    "trip_burn": 2.0, "resolve_burn": 1.0, "min_events": 3,
})
LOOSE_SLO = json.dumps({"error_rate": 0.5})


def _run(*argv):
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        cwd=ROOT, timeout=120,
    )


def test_check_traces_cli_accepts_both_artifact_forms():
    r = _run("tools/check_traces.py", TRACE, TELEMETRY)
    assert r.returncode == 0, r.stdout + r.stderr
    # one OK verdict per file, and the stream form found real spans
    assert r.stdout.count(": OK") == 2
    assert "decode_burst" in r.stdout


def test_check_traces_cli_exit_code_on_corruption(tmp_path):
    # mid-file corruption is an error (only the TAIL may be truncated)
    lines = open(TELEMETRY).read().strip().split("\n")
    lines[2] = lines[2][: len(lines[2]) // 2]
    bad = tmp_path / "corrupt.jsonl"
    bad.write_text("\n".join(lines) + "\n")
    r = _run("tools/check_traces.py", str(bad))
    assert r.returncode == 1
    assert "INVALID" in r.stdout
    # a truncated FINAL line alone is tolerated (the SIGKILL signature)
    tail_cut = tmp_path / "tail.jsonl"
    tail_cut.write_text("\n".join(lines[:2]) + "\n" + lines[3][:20])
    r = _run("tools/check_traces.py", str(tail_cut))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "crash-truncated" in r.stdout
    # a Chrome dump truncated mid-save is ONE broken line: it must not
    # slip through as an "empty but OK" stream — and nor may an empty
    # file
    cut_dump = tmp_path / "cut_dump.json"
    cut_dump.write_text(open(TRACE).read()[:200])
    assert _run("tools/check_traces.py", str(cut_dump)).returncode == 1
    empty = tmp_path / "empty.json"
    empty.write_text("")
    assert _run("tools/check_traces.py", str(empty)).returncode == 1


def test_check_slo_cli_renders_violation_and_pass():
    r = _run("tools/check_slo.py", "--slo", TIGHT_SLO, TELEMETRY)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "SLO VIOLATED" in r.stdout
    assert "error_rate" in r.stdout and "VIOLATED" in r.stdout
    assert "trip" in r.stdout  # the recorded alert timeline is shown
    r = _run("tools/check_slo.py", "--slo", LOOSE_SLO, TELEMETRY)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_check_slo_cli_json_mode_and_bad_inputs(tmp_path):
    r = _run("tools/check_slo.py", "--slo", TIGHT_SLO, "--json", TELEMETRY)
    assert r.returncode == 1
    report = json.loads(r.stdout)[TELEMETRY]
    assert report["ok"] is False and report["trips"] == 1
    assert report["objectives"]["error_rate"]["measured"] > 0.05
    # unreadable input and a bad --slo are distinguishable from a
    # violation (exit 2, not 1)
    assert _run("tools/check_slo.py", "--slo", TIGHT_SLO,
                str(tmp_path / "missing.jsonl")).returncode == 2
    assert _run("tools/check_slo.py", "--slo", "{not json",
                TELEMETRY).returncode == 2


def test_check_fleet_cli_exit_codes_over_artifacts(tmp_path):
    """ISSUE-7 CI satellite: both verdicts pinned through the real CLI.
    exit 0 = healthy fleet, 1 = dead/stale/FAILED worker, 2 =
    unreadable probe input — an operator's cron can tell a broken
    fleet from a broken probe."""
    r = _run("tools/check_fleet.py", FLEET_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    assert ": OK" in r.stdout
    r = _run("tools/check_fleet.py", FLEET_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FLEET UNHEALTHY" in r.stdout
    assert "restart budget exhausted" in r.stdout
    assert "heartbeat stale" in r.stdout
    # a generous heartbeat budget forgives staleness but NOT the
    # failed slot — the exit code stays 1
    r = _run("tools/check_fleet.py", "--max-heartbeat-age", "100",
             FLEET_BAD)
    assert r.returncode == 1 and "restart budget" in r.stdout
    # --json is machine-readable and keeps the code
    r = _run("tools/check_fleet.py", "--json", FLEET_BAD)
    assert r.returncode == 1
    rep = json.loads(r.stdout)[FLEET_BAD]
    assert rep["ok"] is False and rep["workers"]["0"] == "dead"
    # unreadable inputs are exit 2, not a fake verdict
    assert _run("tools/check_fleet.py",
                str(tmp_path / "missing.json")).returncode == 2
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    assert _run("tools/check_fleet.py", str(garbage)).returncode == 2
    notfleet = tmp_path / "notfleet.json"
    notfleet.write_text('{"status": "HEALTHY"}')
    assert _run("tools/check_fleet.py", str(notfleet)).returncode == 2


def test_check_fleet_verdict_as_library_too():
    from tools.check_fleet import fleet_verdict, load_snapshot

    ok, problems = fleet_verdict(load_snapshot(FLEET_OK))
    assert ok and problems == []
    ok, problems = fleet_verdict(load_snapshot(FLEET_BAD))
    assert not ok and len(problems) >= 3  # dead + failed + stale
    # the OK artifact also carries the federated /metrics text: the
    # worker relabel is pinned so the rollup format can't drift
    snap = json.load(open(FLEET_OK))
    assert 'fleet_worker_up{worker="0"} 1' in snap["metrics"]
    assert 'serve_tokens_total{worker="1"}' in snap["metrics"]


def test_check_fleet_autoscale_exit_codes_both_ways(tmp_path):
    """ISSUE-14 satellite: the elastic verdict pinned both ways over
    checked-in artifacts. A draining worker's dead probe and stale
    heartbeat are the drain WORKING (exit 0, worker skipped); the same
    silence without the drain flag pages, and an autoscaler size
    outside [min, max] — the control loop and the supervisor
    disagreeing about the world — is a problem in its own right."""
    r = _run("tools/check_fleet.py", ELASTIC_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    assert ": OK" in r.stdout
    assert "[draining]" in r.stdout          # listed, annotated, skipped
    assert "autoscaler: size 2 (min 1, max 3)" in r.stdout
    assert "last event: down (slo_resolved)" in r.stdout
    r = _run("tools/check_fleet.py", ELASTIC_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FLEET UNHEALTHY" in r.stdout
    assert "worker 2: status dead" in r.stdout
    assert "fleet size 4 above max 3" in r.stdout
    # --json carries the autoscaler block for machine consumers
    r = _run("tools/check_fleet.py", "--json", ELASTIC_OK)
    assert r.returncode == 0
    rep = json.loads(r.stdout)[ELASTIC_OK]
    assert rep["ok"] is True
    assert rep["autoscaler"]["size"] == 2
    assert rep["autoscaler"]["draining"] == [2]


def test_check_fleet_autoscale_verdict_as_library():
    from tools.check_fleet import fleet_verdict, load_snapshot

    ok, problems = fleet_verdict(load_snapshot(ELASTIC_OK))
    assert ok and problems == []
    ok, problems = fleet_verdict(load_snapshot(ELASTIC_BAD))
    assert not ok
    assert any("above max" in p for p in problems)
    assert any("worker 2" in p for p in problems)


def test_check_fleet_cache_exit_codes_both_ways():
    """ISSUE-15 satellite: the heartbeat-carried cache summary rendered
    per worker (blocks used/shared, hit rate, digest version/age — the
    very payload serve/affinity.py scores against) and judged: a worker
    claiming more blocks in use than its pool holds is a page, because
    an affinity router trusting that summary routes into a lie."""
    r = _run("tools/check_fleet.py", CACHE_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    assert ": OK" in r.stdout
    # both workers render a cache line; worker 0 published a full
    # digest frame, worker 1 a delta frame — n counts entries either way
    assert "cache: blocks 31/47 (9 shared)" in r.stdout
    assert "hit rate 80.0%" in r.stdout
    assert "digest v7 (4 prefixes, age 0.18s)" in r.stdout
    assert "cache: blocks 18/47 (4 shared)" in r.stdout
    assert "digest v3 (2 prefixes, age 0.27s)" in r.stdout
    r = _run("tools/check_fleet.py", CACHE_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FLEET UNHEALTHY" in r.stdout
    assert ("worker 0: cache accounting broken (61 blocks used of 47)"
            in r.stdout)
    # a kv summary WITHOUT a digest is fine (pre-ISSUE-15 worker, or
    # digests disabled): rendered without the digest suffix, no page
    assert "worker 1: cache accounting" not in r.stdout


def test_check_fleet_cache_verdict_as_library():
    from tools.check_fleet import fleet_verdict, load_snapshot

    ok, problems = fleet_verdict(load_snapshot(CACHE_OK))
    assert ok and problems == []
    ok, problems = fleet_verdict(load_snapshot(CACHE_BAD))
    assert not ok
    assert any("cache accounting broken" in p for p in problems)
    # a size below min pages the other way too
    snap = load_snapshot(ELASTIC_OK)
    snap["autoscaler"]["size"] = 0
    ok, problems = fleet_verdict(snap)
    assert not ok and any("below min" in p for p in problems)
    # the OK artifact carries the scale ledger in its /metrics text:
    # the labelled counter and both gauges are pinned against drift
    doc = json.load(open(ELASTIC_OK))
    assert 'serve_scale_events_total{direction="up"' in doc["metrics"]
    assert "serve_fleet_size 2" in doc["metrics"]
    assert "serve_standby_ready 1" in doc["metrics"]


def test_artifacts_validate_as_library_too():
    """Belt to the CLI suspenders: the library entry points the tests
    use agree with the CLIs."""
    from tools.check_slo import load_events, slo_report
    from tools.check_traces import parse_stream_text, validate

    trace = json.load(open(TRACE))
    assert validate(trace) == []
    streamed, truncated, errors = parse_stream_text(open(TELEMETRY).read())
    assert errors == [] and not truncated
    assert validate(streamed) == []
    names = {ev["name"] for ev in streamed["traceEvents"]}
    assert {"slo_alert", "slo_resolve", "prefill", "decode_burst"} <= names

    from ddp_practice_tpu.serve.slo import SLOConfig

    records, _ = load_events(TELEMETRY)
    report = slo_report(records, SLOConfig.from_json(TIGHT_SLO))
    assert not report["ok"] and report["trips"] == 1
    assert {r["kind"] for r in records} >= {
        "flight", "metrics", "alert", "span", "meta",
    }


# ------------------------------------------- ISSUE 8: fleet trace artifact
# a REAL 2-worker SIGKILL run's merged timeline (cli.py serve --procs 2
# --fault-plan kill --trace-out): router dispatch/failover instants plus
# worker-streamed spans under pid=worker-N lanes, clock_offset skew
# model stamped by the collector
FLEET_TRACE = os.path.join(ROOT, "tests", "data", "fleet_trace.json")


def test_check_traces_fleet_mode_exit_codes_both_ways(tmp_path):
    # the merged 2-worker chaos timeline validates clean in fleet mode
    r = _run("tools/check_traces.py", "--fleet", FLEET_TRACE)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    # break causality: shift every router dispatch instant 1s LATER so
    # each precedes nothing — fleet mode must fail where plain validate
    # still passes (instants have no lane ordering of their own)
    trace = json.load(open(FLEET_TRACE))
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "i" and ev.get("name") == "dispatch":
            ev["ts"] += 1_000_000
    bad = tmp_path / "bad_fleet.json"
    bad.write_text(json.dumps(trace))
    assert _run("tools/check_traces.py", str(bad)).returncode == 0
    r = _run("tools/check_traces.py", "--fleet", str(bad))
    assert r.returncode == 1
    assert "causality" in r.stdout


def test_fleet_trace_artifact_contracts():
    """The artifact itself keeps the merge contract visible: worker
    lanes, a measured skew model, and failover trace_id linkage."""
    from tools.check_traces import measured_skew, validate_fleet

    trace = json.load(open(FLEET_TRACE))
    assert validate_fleet(trace) == []
    ev = trace["traceEvents"]
    lanes = {e["args"]["name"] for e in ev
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"router", "worker-0", "worker-1"} <= lanes
    skew = measured_skew(trace)
    assert skew and all(b < 0.05 for b in skew.values())
    fo = [e for e in ev if e.get("ph") == "i" and e["name"] == "failover"]
    assert fo, "the chaos artifact must contain a failover"
    # at least one migrated request's spans span BOTH worker lanes
    linked = False
    for e in fo:
        tid = e["args"]["trace_id"]
        pids = {x.get("pid") for x in ev
                if (x.get("args") or {}).get("trace_id") == tid
                or x.get("id") == tid}
        linked = linked or ({0, 1} <= pids)
    assert linked


def test_check_stream_exit_codes_both_ways(tmp_path):
    """The exactly-once audit over its checked-in artifact pair: the
    real chaos run (resume markers included) passes, the corrupted
    copy fails on BOTH planted violations, garbage is UNREADABLE (2) —
    a broken audit input must never read as a broken stream."""
    r = _run("tools/check_stream.py", STREAM_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "STREAMS OK" in r.stdout
    assert "VIOLATION" not in r.stdout

    r = _run("tools/check_stream.py", STREAM_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "STREAM CONTRACT BROKEN" in r.stdout
    assert "duplicate seq" in r.stdout          # the replayed line
    assert "no terminal marker" in r.stdout     # the silenced ending

    garbage = tmp_path / "garbage.jsonl"
    garbage.write_text("{not json\n")
    assert _run("tools/check_stream.py", str(garbage)).returncode == 2
    assert _run("tools/check_stream.py",
                str(tmp_path / "missing.jsonl")).returncode == 2
    # a telemetry file with no chunk lines at all is a VIOLATION, not a
    # silent pass (wrong file / streaming was off)
    empty = tmp_path / "nochunks.jsonl"
    empty.write_text('{"kind": "flight", "rid": 0}\n')
    r = _run("tools/check_stream.py", str(empty))
    assert r.returncode == 1 and "no chunk lines" in r.stdout

    # --json emits the machine-readable verdict
    r = _run("tools/check_stream.py", "--json", STREAM_OK)
    v = json.loads(r.stdout)
    assert v["ok"] is True and v["streams"] > 0


# --------------------------------------- ISSUE 11: OTLP artifact pair
# a deterministic SAMPLED mini-fleet timeline (1% head rate): one
# head-sampled request (r64 — a crc32 pin, see utils/trace.head_keep),
# one tail-kept failover (r3), two clean suppressed requests — exported
# BOTH ways from one recorder, so the pair must round-trip forever;
# _bad is the OTLP form with one planted instance of every failure
# class the validator names (bad hex, int timestamp, duplicate spanId,
# orphaned parent)
OTLP_OK = os.path.join(ROOT, "tests", "data", "otlp_trace.json")
OTLP_CHROME = os.path.join(ROOT, "tests", "data",
                           "otlp_trace_chrome.json")
OTLP_BAD = os.path.join(ROOT, "tests", "data", "otlp_trace_bad.json")


def test_check_otlp_exit_codes_both_ways(tmp_path):
    # the good export validates AND round-trips against its chrome twin
    r = _run("tools/check_otlp.py", OTLP_OK, "--chrome", OTLP_CHROME)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout and "round-trip" in r.stdout
    # the corrupted copy fails on every planted class, by name
    r = _run("tools/check_otlp.py", OTLP_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "INVALID" in r.stdout
    assert "lowercase hex" in r.stdout
    assert "digit-string" in r.stdout
    assert "duplicate spanId" in r.stdout
    assert "orphaned" in r.stdout
    # a round-trip mismatch is a failure even when both files are
    # individually well-formed (the chrome twin of a DIFFERENT run)
    r = _run("tools/check_otlp.py", OTLP_OK, "--chrome", TRACE)
    assert r.returncode == 1
    # unreadable input is exit 2, not a fake verdict
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{broken")
    assert _run("tools/check_otlp.py", str(garbage)).returncode == 2
    assert _run("tools/check_otlp.py",
                str(tmp_path / "missing.json")).returncode == 2
    # --json appends the machine-readable report after the verdict line
    r = _run("tools/check_otlp.py", "--json", OTLP_OK)
    assert r.returncode == 0
    rep = json.loads(r.stdout.split("\n", 1)[1])[0]
    assert rep["ok"] is True and rep["spans"] == 10
    assert rep["traces"] == 2


def test_check_otlp_sampling_metadata_in_artifact():
    """The checked-in export carries the sampling header as resource
    attributes — a collector can tell a 1%-sampled partial timeline
    from span loss without any side channel."""
    otlp = json.load(open(OTLP_OK))
    res = {kv["key"]: kv["value"] for kv in
           otlp["resourceSpans"][0]["resource"]["attributes"]}
    assert res["service.name"] == {"stringValue": "ddp-serve"}
    assert res["ddp.sampling.head_rate"] == {"doubleValue": 0.01}
    assert res["ddp.sampling.traces_suppressed"] == {"intValue": "2"}
    # ...and the chrome twin says the same thing in its metadata block
    chrome = json.load(open(OTLP_CHROME))
    assert chrome["metadata"]["sampling"]["head_rate"] == 0.01
    assert chrome["metadata"]["sampling"]["kept_reasons"] == {
        "failover": 1}


# ---------------------------------- ISSUE 12: push-capture artifacts
# what the stub OTLP collector wrote during a real at-least-once push
# run: one payload file per POST. The OK capture holds 3 payloads but
# only 2 batches — the middle batch was delivered, its 200 was dropped
# (the SIGKILL-shaped failure), and the retry landed a byte-identical
# duplicate that batch-id dedup must fold away. The BAD capture is the
# other failure: the SAME spans re-delivered under a fresh batch id (a
# drain that re-emits), which dedup cannot save — the merged export
# fails on duplicate spanIds.
OTLP_PUSH_OK = os.path.join(ROOT, "tests", "data",
                            "otlp_push_capture_ok")
OTLP_PUSH_BAD = os.path.join(ROOT, "tests", "data",
                             "otlp_push_capture_bad")


def test_check_otlp_push_capture_dir_both_ways(tmp_path):
    r = _run("tools/check_otlp.py", OTLP_PUSH_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    assert "1 duplicate(s)" in r.stdout          # the retried batch
    assert "2 batch(es) from 3 payload(s)" in r.stdout
    r = _run("tools/check_otlp.py", OTLP_PUSH_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "INVALID" in r.stdout
    assert "duplicate spanId" in r.stdout
    # an empty capture directory is unreadable input, not a clean pass
    empty = tmp_path / "empty_capture"
    empty.mkdir()
    assert _run("tools/check_otlp.py", str(empty)).returncode == 2
    # a payload that parses but isn't an export is named, and fails
    mixed = tmp_path / "mixed_capture"
    mixed.mkdir()
    (mixed / "batch-0000.json").write_text('{"not": "otlp"}')
    r = _run("tools/check_otlp.py", str(mixed))
    assert r.returncode == 1
    assert "not an OTLP export" in r.stdout
    # --json carries the batch accounting
    r = _run("tools/check_otlp.py", "--json", OTLP_PUSH_OK)
    assert r.returncode == 0
    rep = json.loads(r.stdout.split("\n", 1)[1])[0]
    assert rep["unique_batches"] == 2 and rep["duplicate_batches"] == 1


def test_check_otlp_push_capture_as_library():
    from tools.check_otlp import (load_push_capture, push_batch_id,
                                  validate_otlp)

    export, info = load_push_capture(OTLP_PUSH_OK)
    assert validate_otlp(export) == []
    assert info["files"] == 3 and info["unique_batches"] == 2
    assert info["duplicate_batches"] == 1 and info["errors"] == []
    # every surviving batch id is unique and pusher-stamped
    bids = set()
    for name in sorted(os.listdir(OTLP_PUSH_OK)):
        bids.add(push_batch_id(
            json.load(open(os.path.join(OTLP_PUSH_OK, name)))))
    assert len(bids) == 2  # 3 files, one duplicated id
    export, info = load_push_capture(OTLP_PUSH_BAD)
    errs = validate_otlp(export)
    assert any("duplicate spanId" in e for e in errs)


def test_check_durations_exit_codes(tmp_path):
    """ISSUE 11 satellite: the tier-1 duration auditor's verdicts
    pinned through its real CLI — fits (0), projects past the 870 s
    wrapper timeout (1), unreadable ledger (2)."""
    fits = tmp_path / "fits.json"
    fits.write_text(json.dumps({
        "markexpr": "not slow", "wall_s": 500.0, "budget_s": 870.0,
        "tests": {"tests/test_a.py::t1": 3.0,
                  "tests/test_b.py::t2": 12.5},
    }))
    r = _run("tools/check_durations.py", str(fits))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    # the 12.5 s test inside a 'not slow' run draws the marker warning
    assert "mark it" in r.stdout and "test_b" in r.stdout
    # ...which --strict-slow escalates to a failure
    assert _run("tools/check_durations.py", "--strict-slow",
                str(fits)).returncode == 1
    over = tmp_path / "over.json"
    over.write_text(json.dumps({
        "markexpr": "not slow", "wall_s": 900.0, "budget_s": 870.0,
        "tests": {"tests/test_a.py::t1": 880.0},
    }))
    r = _run("tools/check_durations.py", str(over))
    assert r.returncode == 1
    assert "OVER BUDGET" in r.stdout and "truncates" in r.stdout
    # no wall_s: projection falls back to padded sum
    nowall = tmp_path / "nowall.json"
    nowall.write_text(json.dumps({
        "markexpr": "not slow",
        "tests": {"tests/test_a.py::t1": 850.0},
    }))
    assert _run("tools/check_durations.py",
                str(nowall)).returncode == 1
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{broken")
    assert _run("tools/check_durations.py",
                str(garbage)).returncode == 2
    assert _run("tools/check_durations.py",
                str(tmp_path / "missing.json")).returncode == 2
    notledger = tmp_path / "notledger.json"
    notledger.write_text('{"tests": "oops"}')
    assert _run("tools/check_durations.py",
                str(notledger)).returncode == 2


# ------------------------------------ ISSUE 19: tenant QoS artifacts
# the qos bench's SIGKILL leg (fair fleet x2, hostile "bulk" flooding
# compliant "acme", one worker SIGKILLed mid-run), slimmed to the
# record kinds check_qos judges (flight/alert/instant — chunk and
# metrics-dump lines stripped for size); _bad is the same file with
# the burn-alert edge reattributed to the compliant tenant, which
# breaks BOTH isolation claims at once (a compliant trip appears, the
# hostile trip vanishes)
QOS_TELEMETRY = os.path.join(ROOT, "tests", "data",
                             "qos_telemetry.jsonl")
QOS_TELEMETRY_BAD = os.path.join(ROOT, "tests", "data",
                                 "qos_telemetry_bad.jsonl")
# federated snapshots with the /tenants rollup riding next to healthz:
# _ok is a near-even two-tenant split, _bad a starved tenant (Jain
# ~0.51) on an otherwise HEALTHY fleet — only --min-fairness pages it
QOS_FLEET_OK = os.path.join(ROOT, "tests", "data",
                            "fleet_healthz_qos_ok.json")
QOS_FLEET_BAD = os.path.join(ROOT, "tests", "data",
                             "fleet_healthz_qos_bad.json")
# the failure budget the artifact run was recorded against: 5x the
# steady-state 0.5s TTFT target, because a mid-run worker SIGKILL
# makes the steady-state budget unmeetable by ANY scheduler
QOS_SLO = json.dumps({"ttft_p99_s": 2.5, "fast_window_s": 0.5,
                      "slow_window_s": 1.0})


_QOS_HOSTILE = ("--hostile", "bulk", "--min-fairness", "0.9",
                "--expect-hostile-trip")


@pytest.mark.parametrize("case", ["held", "broken", "no_exemption",
                                  "missing_file", "bad_slo", "json"])
def test_check_qos_exit_codes_both_ways(tmp_path, case):
    """ISSUE-19 satellite: the per-tenant verdict pinned through the
    real CLI over the checked-in SIGKILL-leg telemetry. exit 0 = every
    isolation claim held, 1 = a claim broke, 2 = unreadable input.
    (One run of the CLI a case: six in one test read 39.5 s beside five
    workers, at the tier-1 line.)"""
    if case == "held":
        r = _run("tools/check_qos.py", "--slo", QOS_SLO, *_QOS_HOSTILE,
                 QOS_TELEMETRY)
        assert r.returncode == 0, r.stdout + r.stderr
        assert ": OK" in r.stdout
        assert "[hostile]" in r.stdout
        assert "violated (hostile, not judged)" in r.stdout
    elif case == "broken":
        # the corrupted copy fails BOTH isolation claims, by name
        r = _run("tools/check_qos.py", "--slo", QOS_SLO, *_QOS_HOSTILE,
                 QOS_TELEMETRY_BAD)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "QOS VIOLATED" in r.stdout
        assert "alert trip(s) on a compliant tenant" in r.stdout
        assert "no hostile tenant tripped" in r.stdout
    elif case == "no_exemption":
        # without the hostile exemption the flooder's own pain pages too
        r = _run("tools/check_qos.py", "--slo", QOS_SLO, QOS_TELEMETRY)
        assert r.returncode == 1
        assert "violated ttft_p99" in r.stdout
    elif case == "missing_file":
        # unreadable input / bad --slo are exit 2, not a fake verdict
        assert _run("tools/check_qos.py", "--slo", QOS_SLO,
                    str(tmp_path / "missing.jsonl")).returncode == 2
    elif case == "bad_slo":
        assert _run("tools/check_qos.py", "--slo", "{not json",
                    QOS_TELEMETRY).returncode == 2
    else:
        # --json carries the per-tenant reports + fairness
        r = _run("tools/check_qos.py", "--slo", QOS_SLO, "--hostile",
                 "bulk", "--json", QOS_TELEMETRY)
        assert r.returncode == 0
        rep = json.loads(r.stdout)[QOS_TELEMETRY]
        assert rep["ok"] is True
        assert rep["fairness_index"] >= 0.9
        assert rep["tenants"]["bulk"]["hostile"] is True
        assert rep["tenants"]["acme"]["trips"] == 0


def test_check_qos_as_library():
    """qos_report() is the seam the bench's SIGKILL leg calls
    in-process — pinned on the same artifact the CLI sees, including
    the contended-window rule that makes the fairness number mean
    something (a drained run delivers everyone's totals eventually;
    only tokens finished before the last arrival show who was served
    during the fight)."""
    from ddp_practice_tpu.serve.slo import SLOConfig
    from tools.check_qos import qos_report
    from tools.check_slo import load_events

    records, truncated = load_events(QOS_TELEMETRY)
    assert not truncated
    rep = qos_report(records, SLOConfig.from_json(QOS_SLO),
                     hostile=["bulk"], min_fairness=0.9,
                     expect_hostile_trip=True)
    assert rep["ok"], rep["problems"]
    # the window bound bites: the flooder's full token count is far
    # larger than what it got during the contended window, and the
    # fairness verdict is computed over the latter
    bulk = rep["tenants"]["bulk"]
    assert bulk["window_tokens"] < bulk["output_tokens"]
    assert rep["service_tokens"]["bulk"] == bulk["window_tokens"]
    # per-tenant trips come from the live registry's attributed alert
    # edges in the stream, not offline recomputation
    assert bulk["trips"] == 1
    assert rep["tenants"]["acme"]["trips"] == 0
    # no flights at all is unreadable-grade, not an empty pass
    try:
        qos_report([], SLOConfig.from_json(QOS_SLO))
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_check_fleet_qos_exit_codes_both_ways():
    """ISSUE-19 satellite: the federated /tenants rollup rendered and
    judged. Without --min-fairness the rollup is a VIEW (the starved
    snapshot still exits 0 — every worker is healthy); with it, a
    collapsed Jain's index pages even though no worker is sick,
    because a starved tenant is an outage for THAT tenant."""
    r = _run("tools/check_fleet.py", QOS_FLEET_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tenants (fleet rollup, fairness index" in r.stdout
    assert "acme" in r.stdout and "bulk" in r.stdout
    assert "ttft p99" in r.stdout
    r = _run("tools/check_fleet.py", QOS_FLEET_BAD)
    assert r.returncode == 0, r.stdout + r.stderr  # view only
    r = _run("tools/check_fleet.py", "--min-fairness", "0.9",
             QOS_FLEET_OK)
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run("tools/check_fleet.py", "--min-fairness", "0.9",
             QOS_FLEET_BAD)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FLEET UNHEALTHY" in r.stdout
    assert "most-starved tenant: acme" in r.stdout
    # asking for the fairness judgment on a fleet that publishes no
    # rollup is a misconfigured probe, not a silent pass
    r = _run("tools/check_fleet.py", "--min-fairness", "0.9", FLEET_OK)
    assert r.returncode == 1
    assert "no /tenants rollup" in r.stdout
    # --json carries the rollup summary for machine consumers
    r = _run("tools/check_fleet.py", "--json", QOS_FLEET_BAD)
    assert r.returncode == 0
    rep = json.loads(r.stdout)[QOS_FLEET_BAD]
    assert rep["tenants"]["names"] == ["acme", "bulk"]
    assert rep["tenants"]["fairness_index"] < 0.6


def test_check_fleet_qos_verdict_as_library():
    from tools.check_fleet import load_snapshot_doc, tenant_problems

    _hz, _fl, tenants = load_snapshot_doc(QOS_FLEET_OK)
    assert tenant_problems(tenants, 0.9) == []
    assert tenant_problems(tenants, 0.0) == []  # 0 disables
    _hz, _fl, bad = load_snapshot_doc(QOS_FLEET_BAD)
    probs = tenant_problems(bad, 0.9)
    assert probs and "most-starved tenant: acme" in probs[0]
    assert tenant_problems(None, 0.9)  # no rollup + gate = problem
    # the rollup's pooled percentiles federate per the /flight rule —
    # the snapshot's p99 must come from the pooled samples, never a
    # percentile of percentiles
    assert tenants["tenants"]["acme"]["ttft_s"]["p99"] > 0


def test_check_stream_as_library():
    """stream_verdict() is the pure seam the bench's chaos rep calls
    in-process — pinned on the same artifacts the CLI sees."""
    sys.path.insert(0, ROOT)
    try:
        from tools.check_stream import load_jsonl, stream_verdict
    finally:
        sys.path.pop(0)
    ok, report = stream_verdict(load_jsonl(STREAM_OK))
    assert ok and not report["violations"]
    assert report["streams"] == 5 and report["tokens"] == 40
    ok, report = stream_verdict(load_jsonl(STREAM_BAD))
    assert not ok
    assert set(report["violations"]) == {"r0", "r3"}
