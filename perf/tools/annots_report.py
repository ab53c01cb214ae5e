#!/usr/bin/env python3
"""A traced run's annotation rows against its device lines, for PERF.md.

    python3 perf/tools/annots_report.py perf_out/<cell>/seed<n>_trace1

Reads the run's `xplane/` with `lib/annots.py` and the harness's loader (no
chip, no program) and prints one JSON object: how many `serve:*` / `train:*`
events of each name lie inside the `perf:traced` marker and which attributes
they carry; the `serve:decode_burst` events beside the `jit__decode_burst`
runs of chip 0's "XLA Modules" line inside the marker, and the `serve:prefill`
/ `serve:prefill_chunk` events beside the prefill programs' runs; in how many
ticks a burst's `active` differs from its tick's `decoding`; every
`serve:slow_tick` with its phases; the five readers' numbers with the sums
they were made from; and the seconds the one parse took.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.lib import annots, xtrace  # noqa: E402


def report(run_dir: str) -> dict:
    path = xtrace.find_xplane(os.path.join(run_dir, "xplane"))
    t_read = time.monotonic()
    rows = annots.events(path)
    t_read = time.monotonic() - t_read
    trace = xtrace.load(path)
    t0, t1 = xtrace.window_of(trace, annots.MARKER)
    obs = {"trace": trace, "xplane": path, "annots": rows}

    def runs(pattern: str) -> list:
        """Chip 0's runs of the programs so named, inside the marker."""
        if not xtrace.device_planes(trace):
            return []
        return xtrace.module_runs(trace, pattern, t0, t1)

    names = {}
    for name, _, _, attrs in rows:
        row = names.setdefault(name, {"events": 0, "attrs": set()})
        row["events"] += 1
        row["attrs"] |= set(attrs)
    ticks = annots.named(rows, "serve:tick", "decoding")
    bursts = annots.named(rows, "serve:decode_burst", "active")
    differ = orphans = 0
    for b in bursts:
        owner = [t for t in ticks
                 if t[1] <= b[1] and b[1] + b[2] <= t[1] + t[2]]
        if len(owner) != 1:
            orphans += 1
        elif owner[0][3]["decoding"] != b[3]["active"]:
            differ += 1
    calls, prefill_runs = annots.prefill_calls(obs), runs(
        annots.PREFILL_PROGRAMS)
    admits = annots.named(rows, "serve:prefill", "prompt_len") \
        + annots.named(rows, "serve:chunk_admit", "prompt_len")
    return {
        "xplane": path, "xplane_bytes": os.path.getsize(path),
        "reader_s": t_read, "window_s": t1 - t0,
        "names": {k: {"events": v["events"], "attrs": sorted(v["attrs"])}
                  for k, v in sorted(names.items())},
        "decode_burst": {
            "events": len(annots.named(rows, "serve:decode_burst")),
            "runs": len(runs(r"decode_burst")),
            "active_differs_from_its_ticks_decoding": differ,
            "bursts_in_no_one_tick": orphans},
        "prefill": {
            "events": len(annots.named(rows, "serve:prefill"))
            + len(annots.named(rows, "serve:prefill_chunk")),
            "runs": len(prefill_runs), "device_s": sum(prefill_runs),
            "positions_run": sum(b for b, _ in calls),
            "positions_real": sum(r for _, r in calls)},
        "admissions": {
            "events": len(admits),
            "prompt_tokens": sum(e[3]["prompt_len"] for e in admits),
            "prefix_hit_tokens": sum(e[3].get("prefix_hit", 0)
                                     for e in admits)},
        "ticks": {
            "events": len(annots.named(rows, "serve:tick")),
            "seconds": sum(t[2] for t in ticks),
            "decoding_by_count_mean": sum(
                t[3]["decoding"] for t in ticks) / max(len(ticks), 1)},
        "slow_ticks": [[e[1] - t0, e[3]]
                       for e in annots.named(rows, "serve:slow_tick")],
        "readers": {
            "flood_slots_decoding_pct":
                annots.slot_seconds_pct(obs, "decoding"),
            "flood_slots_prefilling_pct":
                annots.slot_seconds_pct(obs, "prefilling"),
            "flood_prefill_pad_pct": annots.prefill_pad_pct(obs),
            "flood_prefill_dev_tok_s": annots.prefill_dev_tok_s(obs),
            "flood_prefix_hit_pct": annots.prefix_hit_pct(obs)},
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1])))
