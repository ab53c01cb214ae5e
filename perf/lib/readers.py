"""What the per-layer readers share: one run's observations to numbers.

`obs` is what a driver hands over after a `--trace 1` run:

    kind        "train" | "serve"
    window      (t0, t1) of the measured window, monotonic seconds
    traced      (t0, t1) of the profiled slice of it, or None
    trace       the loaded profiler trace (`lib/xtrace.py`) or None
    spans       [name, t0, t1] of the PROGRAM's own spans (monotonic)
    compiles_in_window
    train:      segments, rates, steps_per_segment, median_mfu_pct
    serve:      requests, ticks, burst, t_origin, decode_bytes
    config, peaks

A reader that finds nothing to read returns None, and the harness leaves its
metric out of the line.
"""

from __future__ import annotations

import re

from perf.lib import flops, stats, xtrace

MARKER = "perf:traced"


def _slice(obs: dict):
    """(trace, t0, t1, offset): the traced slice on the trace's clock and
    what to add to a monotonic time to land on that clock."""
    trace, traced = obs.get("trace"), obs.get("traced")
    if trace is None or traced is None:
        return None
    t0, t1 = xtrace.window_of(trace, MARKER)
    return trace, t0, t1, t0 - traced[0]


def device_busy(obs: dict) -> dict:
    """`busy_s` and `window_s` for the result line's `device`."""
    sl = _slice(obs)
    if sl is None:
        raise RuntimeError("a --trace 1 run produced no profiler trace")
    b = xtrace.busy(sl[0], sl[1], sl[2])
    return {"busy_s": b["busy_s"], "window_s": b["window_s"]}


def device_idle_pct(obs: dict):
    sl = _slice(obs)
    if sl is None:
        return None
    b = xtrace.busy(sl[0], sl[1], sl[2])
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


def breakdown(obs: dict) -> dict:
    """The ops that took most device time and the longest idle gaps by the
    program span that covered them (chip 0 of the traced slice)."""
    sl = _slice(obs)
    if sl is None:
        return {"device_ops": [], "idle_gaps": []}
    trace, t0, t1, off = sl
    spans = [(n, a + off, b + off) for n, a, b in obs.get("spans", [])]
    return {
        "device_ops": xtrace.top(xtrace.op_seconds(trace, t0, t1)),
        "idle_gaps": xtrace.top(
            xtrace.idle_gaps_by_span(trace, spans, t0, t1)),
    }


def compiles_in_window(obs: dict):
    return float(obs["compiles_in_window"])


# ------------------------------------------------------------------ train
def span_ms_per_step(obs: dict, name: str):
    """Milliseconds a step spends in the program's span `name`, over the
    whole window."""
    w0, w1 = obs["window"]
    hits = [b - a for n, a, b in obs.get("spans", [])
            if n == name and w0 <= a <= w1]
    if not hits:
        return None
    steps = len(obs["segments"]) * obs["steps_per_segment"]
    return 1e3 * sum(hits) / steps


def main_program_runs(obs: dict):
    """Device seconds of each run, in the traced slice, of the program that
    holds most of the device's time (the train step)."""
    sl = _slice(obs)
    if sl is None:
        return None
    trace, t0, t1, _ = sl
    plane = xtrace.device_planes(trace)[0]
    by_name = {}
    for name, s, d in xtrace.line_events(plane, xtrace.MODULES_LINE):
        if s >= t0 and s + d <= t1:
            by_name.setdefault(re.sub(r"\(.*$", "", name), []).append(d)
    if not by_name:
        return None
    return max(by_name.values(), key=sum)


def step_dev_ms(obs: dict):
    runs = main_program_runs(obs)
    if runs:
        return 1e3 * stats.median(runs)
    sl = _slice(obs)
    if sl is None:
        return None
    busy = xtrace.busy(sl[0], sl[1], sl[2])["per_chip_s"][0]
    return 1e3 * busy / obs["steps_per_segment"]


def coll_exposed_pct(obs: dict):
    sl = _slice(obs)
    if sl is None or obs["chips"] < 2:
        return None
    trace, t0, t1, _ = sl
    return 100.0 * xtrace.exposed_collective_seconds(trace, t0, t1) \
        / (t1 - t0)


# ------------------------------------------------------------------ serve
def request_percentile(obs: dict, key: str, q: float):
    vals = [r[key] for r in obs["requests"] if r.get(key) is not None]
    return stats.percentile(vals, q) if vals else None


def decode_slots_mean(obs: dict):
    w = obs["window"][1] - obs["window"][0]
    slots = [k["slots"] for k in obs["ticks"]
             if k["slots"] > 0 and k["t"] <= w]
    return sum(slots) / len(slots) if slots else None


def program_dev_seconds(obs: dict, pattern: str):
    sl = _slice(obs)
    if sl is None:
        return None
    runs = xtrace.module_runs(sl[0], pattern, sl[1], sl[2])
    return runs or None


def prefill_dev_ms_p50(obs: dict):
    runs = program_dev_seconds(obs, r"prefill")
    return 1e3 * stats.median(runs) if runs else None


def decode_step_dev_ms(obs: dict):
    runs = program_dev_seconds(obs, r"decode_burst")
    return 1e3 * sum(runs) / (len(runs) * obs["burst"]) if runs else None


def paged_decode_roofline_pct(obs: dict):
    """Least time the chip could take to move the K and V of the live
    tokens (and q, out) of every decode step in the traced slice, over the
    time the paged decode kernel took there. Memory-bound: bytes / HBM
    bandwidth."""
    sl = _slice(obs)
    if sl is None:
        return None
    trace, t0, t1, off = sl
    ops = xtrace.op_seconds(trace, t0, t1)
    kernel = sum(v for k, v in ops.items() if "paged_decode" in k)
    if kernel <= 0:
        return None
    origin, k = obs["t_origin"] + off, obs["burst"]
    live = slot_steps = 0.0
    for tick in obs["ticks"]:
        a = origin + tick["t"]
        if tick["slots"] and t0 <= a and a + tick["dt"] <= t1:
            live += k * tick["live"] + tick["slots"] * k * (k + 1) / 2
            slot_steps += k * tick["slots"]
    if slot_steps == 0:
        return None
    kv, qo = obs["decode_bytes"]
    least = flops.paged_decode_least_bytes(kv, qo, live, slot_steps) \
        / obs["peaks"]["hbm_bytes_s"]
    return 100.0 * least / kernel
