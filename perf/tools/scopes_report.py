#!/usr/bin/env python3
"""A traced run's device time by class and direction, for PERF.md section 5.

    python3 perf/tools/scopes_report.py perf_out/<cell>/seed<n>_trace1

Reads the run's `xplane/` with the harness's own loader and `lib/scopes.py`
(no chip, no program) and prints one JSON object: chip 0's busy seconds in
the traced slice, the seconds and share of every (class, direction), what
reads `unscoped` by XLA name, where each of the XLA names that took most time
went, the runs of each program in the slice (to turn seconds into
milliseconds a step), and the seconds the readers' one parse took. With
`--sample N [--program REGEX] [--min-us U]` also the first N leaf ops (of at
least U microseconds) of the slice, or of the first run in it of the program
whose name matches, with their names UNCUT and their paths: what
`tests/perf/data/xplane_scopes_head.json` was cut from.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.lib import scopes, xtrace  # noqa: E402


def report(run_dir: str, sample: int = 0, program: str = "",
           min_us: float = 0.0) -> dict:
    path = xtrace.find_xplane(os.path.join(run_dir, "xplane"))
    trace = xtrace.load(path)
    obs = {"trace": trace, "traced": (0.0, 0.0), "xplane": path}
    t_read = time.monotonic()
    by = scopes.seconds_by(obs)
    t_read = time.monotonic() - t_read
    if by is None:
        return {"xplane": path, "paths": None}
    t0, t1 = xtrace.window_of(trace, "perf:traced")
    busy = by["busy"]
    table = {f"{k[0]}.{k[1]}": [v, 100.0 * v / busy]
             for k, v in by.items() if isinstance(k, tuple)}
    events = scopes.events_with_paths(obs, trace)
    names = {}
    for e, secs in scopes.leaf_seconds(events, t0, t1):
        cls = scopes.classify(scopes.scope_of(e))
        row = names.setdefault(xtrace.op_name(e[0]), {})
        key = ".".join(cls) if cls else scopes.UNSCOPED
        row[key] = row.get(key, 0.0) + secs
    plane = xtrace.device_planes(trace)[0]
    programs = {}
    for name, start, dur in xtrace.line_events(plane, xtrace.MODULES_LINE):
        if start >= t0 and start + dur <= t1:
            row = programs.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0.0])
            row[0] += 1
            row[1] += dur
    out = {
        "xplane": path, "busy_s": busy, "window_s": t1 - t0,
        "reader_s": t_read, "xplane_bytes": os.path.getsize(path),
        "ops_events": len(events),
        "by_class_direction": dict(sorted(table.items(),
                                          key=lambda kv: -kv[1][0])),
        "unscoped_s": by[scopes.UNSCOPED],
        "unscoped_pct": 100.0 * by[scopes.UNSCOPED] / busy,
        "unscoped_ops": xtrace.top(by["unscoped_ops"], 20),
        "xla_names": {k: v for k, v in sorted(
            names.items(), key=lambda kv: -sum(kv[1].values()))[:16]},
        "programs": programs,
    }
    if sample:
        runs = [(start, start + dur) for name, start, dur
                in xtrace.line_events(plane, xtrace.MODULES_LINE)
                if re.search(program, name) and t0 <= start
                and start + dur <= t1]
        a, b = runs[0] if runs else (t0, t1)
        out["sample_of"] = [program, a, b]
        out["sample"] = [
            [e[0], e[1], e[2], scopes.scope_of(e) or ""]
            for e, _ in scopes.leaf_seconds(events, a, b)
            if e[2] >= min_us * 1e-6][:sample]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("run_dir")
    ap.add_argument("--sample", type=int, default=0)
    ap.add_argument("--program", default="")
    ap.add_argument("--min-us", type=float, default=0.0)
    args = ap.parse_args(argv)
    print(json.dumps(report(args.run_dir, args.sample, args.program,
                            args.min_us)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
