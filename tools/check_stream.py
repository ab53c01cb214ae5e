"""Offline exactly-once audit over telemetry chunk lines.

The router's streaming plane (serve/router.py TokenStream) claims an
exactly-once contract: per request, token chunks reach the consumer
with contiguous sequence numbers, no duplicated and no missing token
offsets, resume markers at failover splices, and exactly one typed
terminal event. The chaos tests assert that IN-process; this tool
re-derives it from the telemetry JSONL alone — the artifact a
production incident would actually have in hand:

    python tools/check_stream.py telemetry.jsonl
    python tools/check_stream.py --json run.jsonl

Audited lines are ``{"kind": "chunk", ...}`` as written by
Router._stream_emit (consumer-side stream events, ``event`` =
tokens/resumed/end) or by Scheduler._emit_chunk (single-replica
serving, ``final`` marks the terminal). Per trace_id the checks are:

- ``seq`` contiguous from 0 — a duplicate seq is a replayed delivery,
  a hole is a lost one;
- token-offset continuity — every token-carrying line must start
  exactly where the previous one ended (``start`` == tokens delivered
  so far): an overlap means the consumer saw tokens twice, a gap means
  it silently missed some;
- exactly ONE terminal marker, and nothing after it — a stream that
  ends twice (or keeps emitting past its end) broke the close
  contract; a stream with no terminal at all ended in silence, the
  exact failure mode the typed ``end`` event exists to prevent.

exit 0 = every stream holds the contract; 1 = at least one violation;
2 = input unreadable/malformed — a broken audit must be
distinguishable from a broken stream (same convention as
tools/check_slo.py).

``--sse`` audits the OTHER side of the wire: a JSONL capture of SSE
frames as a socket consumer actually parsed them (one line per frame:
``{"stream": key, "id": int, "event": kind, "data": {...}}`` — the
shape serve/frontdoor.py `sse_request` returns, which the bench and
the socket tests dump verbatim). The frames are mapped onto the same
chunk-line schema (wire ``id`` IS the seq, ``data.start``/``tokens``
are the offsets) and judged by the identical rules — the front door's
claim is precisely that the wire consumer sees the in-process
contract, so the wire capture must pass the in-process audit.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple

OK, VIOLATION, UNREADABLE = 0, 1, 2


def _is_terminal(line: dict) -> bool:
    return line.get("event") == "end" or bool(line.get("final"))


def _carries_tokens(line: dict) -> bool:
    # router "resumed"/"end" events carry n=0; scheduler final chunks
    # may carry a tail. Offset continuity is judged only where tokens
    # actually flowed.
    return int(line.get("n", 0)) > 0


def audit_stream(lines: List[dict]) -> List[str]:
    """Violations for ONE trace_id's chunk lines (empty = contract
    holds). `lines` must be in file order — the delivery order."""
    problems: List[str] = []
    seen_seq = set()
    expected_seq = 0
    delivered = 0
    ended_at = None
    for ln in lines:
        seq = ln.get("seq")
        if not isinstance(seq, int):
            problems.append(f"line without integer seq: {ln!r}")
            continue
        if seq in seen_seq:
            problems.append(f"duplicate seq {seq}")
        elif seq != expected_seq:
            problems.append(
                f"seq jumped to {seq}, expected {expected_seq}"
            )
            expected_seq = seq + 1
        else:
            expected_seq += 1
        seen_seq.add(seq)
        if ended_at is not None:
            problems.append(
                f"seq {seq} emitted after terminal seq {ended_at}"
            )
        if _carries_tokens(ln):
            start = int(ln.get("start", 0))
            n = int(ln["n"])
            if start < delivered:
                problems.append(
                    f"seq {seq}: tokens overlap — start {start} "
                    f"below delivered {delivered} (duplicate delivery)"
                )
            elif start > delivered:
                problems.append(
                    f"seq {seq}: token gap — start {start} above "
                    f"delivered {delivered} (missing delivery)"
                )
            delivered = max(delivered, start + n)
        if _is_terminal(ln):
            if ended_at is not None:
                problems.append(
                    f"second terminal at seq {seq} "
                    f"(first at {ended_at})"
                )
            else:
                ended_at = seq
    if ended_at is None:
        problems.append("no terminal marker — the stream ended in "
                        "silence")
    return problems


def stream_verdict(lines: List[dict]) -> Tuple[bool, dict]:
    """(ok, report) over every chunk line in a telemetry run — the
    pure function the CLI and the artifact tests share. Non-chunk
    lines are ignored (the telemetry stream interleaves flight/alert/
    watchdog kinds on purpose)."""
    streams: Dict[str, List[dict]] = {}
    for ln in lines:
        if ln.get("kind") != "chunk":
            continue
        key = ln.get("trace_id") or f"rid:{ln.get('rid')}"
        streams.setdefault(key, []).append(ln)
    violations: Dict[str, List[str]] = {}
    tokens_total = 0
    for key, chunk_lines in streams.items():
        probs = audit_stream(chunk_lines)
        if probs:
            violations[key] = probs
        tokens_total += sum(int(ln.get("n", 0)) for ln in chunk_lines)
    report = {
        "streams": len(streams),
        "tokens": tokens_total,
        "violations": violations,
    }
    return (len(streams) > 0 and not violations), report


def sse_to_chunks(records: List[dict]) -> List[dict]:
    """Captured SSE frames -> chunk-line schema, losslessly enough for
    the audit: wire id -> seq, event name -> event, payload start/
    token-count/status carried through. A frame whose ``data`` is not
    an object (malformed payload on the wire) maps to a line with no
    seq — audit_stream flags it rather than this converter hiding it."""
    out: List[dict] = []
    for rec in records:
        data = rec.get("data")
        if not isinstance(data, dict):
            data = {}
        key = (rec.get("stream")
               or data.get("trace_id")
               or f"rid:{rec.get('rid')}")
        out.append({
            "kind": "chunk",
            "trace_id": key,
            "seq": rec.get("id"),
            "event": rec.get("event"),
            "start": data.get("start", 0),
            "n": len(data.get("tokens") or ()),
            "status": data.get("status"),
        })
    return out


def load_jsonl(path: str) -> List[dict]:
    out: List[dict] = []
    with open(path) as f:
        for i, raw in enumerate(f, 1):
            raw = raw.strip()
            if not raw:
                continue
            try:
                obj = json.loads(raw)
            except ValueError as e:
                raise ValueError(f"{path}:{i}: bad JSON ({e})") from e
            if not isinstance(obj, dict):
                raise ValueError(f"{path}:{i}: line is not an object")
            out.append(obj)
    return out


def render(source: str, ok: bool, report: dict) -> str:
    lines = [
        f"  {report['streams']} stream(s), "
        f"{report['tokens']} token(s) audited"
    ]
    for key, probs in sorted(report["violations"].items()):
        for p in probs:
            lines.append(f"  VIOLATION  {key}: {p}")
    if report["streams"] == 0:
        lines.append("  VIOLATION  no chunk lines at all — nothing "
                     "streamed (or the wrong file)")
    lines.append(f"{source}: "
                 + ("STREAMS OK" if ok else "STREAM CONTRACT BROKEN"))
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "check_stream",
        description="audit telemetry JSONL chunk lines for the "
                    "exactly-once streaming contract (contiguous seq, "
                    "no duplicate/missing tokens, one typed terminal "
                    "per stream)",
    )
    p.add_argument("telemetry", help="telemetry JSONL path (or, with "
                                     "--sse, an SSE frame capture)")
    p.add_argument("--sse", action="store_true",
                   help="input is a wire-side SSE frame capture "
                        "(frontdoor sse_request records), audited "
                        "under the same exactly-once rules")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    try:
        lines = load_jsonl(args.telemetry)
        if args.sse:
            lines = sse_to_chunks(lines)
    except (OSError, ValueError) as e:
        print(f"UNREADABLE — {e}", file=sys.stderr)
        return UNREADABLE
    ok, report = stream_verdict(lines)
    if args.json:
        print(json.dumps({"ok": ok, **report}))
    else:
        print(render(args.telemetry, ok, report))
    return OK if ok else VIOLATION


if __name__ == "__main__":
    sys.exit(main())
