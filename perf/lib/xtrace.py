"""From a profiler trace to numbers: the reduction every PR shares.

`load(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData` into plain
lists; everything after that is arithmetic on those lists, so the tests run it
on a small recorded trace (`tests/perf/data/`) with no profiler at all.

A loaded trace is {"planes": [{"name", "lines": [{"name", "events":
[[name, start_s, dur_s], ...]}]}]}, times in seconds on the trace's own clock.

What a TPU trace of this installation holds (looked at by hand, PR 23): one
plane per chip, "/device:TPU:<n>", with the lines "XLA Ops" (one event per
executed HLO op; a `while` or a fused computation's parent spans its
children), "XLA Modules" (one event per executed program, named
"jit_<fn>(<fingerprint>)") and "Steps"; and "/host:CPU" with one line per
thread, which holds `jax.profiler.TraceAnnotation` regions by name.
"""

from __future__ import annotations

import glob
import os
import re

from perf.lib.stats import gaps, union_seconds

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str, *, keep_host: str = r"^(perf:|serve:|train)") -> dict:
    """Device planes whole; of the host planes only the events whose name
    matches `keep_host` (annotations), which keeps the result small."""
    from jax.profiler import ProfileData

    keep = re.compile(keep_host)
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE, "Steps"):
                continue
            events = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                      for e in line.events
                      if device or keep.search(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            out.append({"name": plane.name, "lines": lines})
    return {"planes": out}


def op_name(raw: str) -> str:
    """"%fusion.123 = ..." and "fusion.123" both read "fusion"."""
    name = raw.split(" = ")[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")]


def line_events(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_events(trace: dict, prefix: str) -> list:
    """[name, start_s, dur_s] of host annotations whose name starts so."""
    return sorted(
        (e for p in trace["planes"] if p["name"].startswith("/host:")
         for line in p["lines"] for e in line["events"]
         if e[0].startswith(prefix)),
        key=lambda e: e[1])


def leaves(events: list) -> list:
    """Events that contain no other event of the same line: the ops that
    really occupy the device, without the `while` / call parents that span
    them. Zero-length events are dropped."""
    evs = sorted((e for e in events if e[2] > 0),
                 key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(evs):
        end = e[1] + e[2]
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end + 1e-12:
            continue  # a parent: the next event starts and ends inside it
        out.append(e)
    return out


def clip(events: list, t0: float, t1: float) -> list:
    """(start, end) pairs of the events, cut to [t0, t1]."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def window_of(trace: dict, marker: str = "perf:traced") -> tuple:
    """(t0, t1) of the benchmark's own marker annotation, on the trace's
    clock; without one, the span of all device ops."""
    marks = host_events(trace, marker)
    if marks:
        return marks[0][1], marks[0][1] + marks[0][2]
    evs = [e for p in device_planes(trace)
           for e in line_events(p, OPS_LINE)]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def busy(trace: dict, t0: float, t1: float) -> dict:
    """Per-chip busy seconds inside [t0, t1] (union of op intervals), their
    mean, and the window: what the result line's `device` carries."""
    per = [union_seconds(clip(line_events(p, OPS_LINE), t0, t1))
           for p in device_planes(trace)]
    if not per:
        raise ValueError("the trace holds no device plane")
    return {"per_chip_s": per, "busy_s": sum(per) / len(per),
            "window_s": t1 - t0}


def op_seconds(trace: dict, t0: float, t1: float, chip: int = 0) -> dict:
    """{op name: seconds} of leaf ops on one chip inside [t0, t1]."""
    plane = device_planes(trace)[chip]
    out = {}
    for e in leaves(line_events(plane, OPS_LINE)):
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a:
            key = op_name(e[0])
            out[key] = out.get(key, 0.0) + (b - a)
    return out


def module_runs(trace: dict, pattern: str, t0: float, t1: float,
                chip: int = 0) -> list:
    """Device seconds of each execution of the programs whose name matches
    `pattern`, wholly inside [t0, t1], on one chip."""
    rx = re.compile(pattern)
    plane = device_planes(trace)[chip]
    return [e[2] for e in line_events(plane, MODULES_LINE)
            if rx.search(e[0]) and e[1] >= t0 and e[1] + e[2] <= t1]


def exposed_collective_seconds(trace: dict, t0: float, t1: float) -> float:
    """Seconds, averaged over chips, in which a collective op runs on a chip
    and no other op runs there."""
    per = []
    for plane in device_planes(trace):
        ops = leaves(line_events(plane, OPS_LINE))
        coll = clip([e for e in ops if COLLECTIVE.search(e[0])], t0, t1)
        comp = clip([e for e in ops if not COLLECTIVE.search(e[0])], t0, t1)
        both = union_seconds(coll + comp)
        per.append(both - union_seconds(comp))
    return sum(per) / len(per) if per else 0.0


def idle_gaps_by_span(trace: dict, spans: list, t0: float, t1: float,
                      chip: int = 0) -> dict:
    """{span name: idle seconds}: every stretch of [t0, t1] in which chip
    `chip` runs nothing, given to the innermost host span that covers its
    middle ("no_span" if none). `spans` are (name, start_s, end_s) on the
    TRACE's clock."""
    plane = device_planes(trace)[chip]
    out = {}
    for a, b in gaps(clip(line_events(plane, OPS_LINE), t0, t1), t0, t1):
        mid = 0.5 * (a + b)
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "no_span"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]
