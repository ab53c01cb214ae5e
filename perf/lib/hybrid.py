"""What the readers of a hybrid (recurrent state + held experts) serving cell
share: a kernel's time inside the DECODE program alone, and the decode steps
and decoding slots of the traced slice.

A kernel's events are summed by NAME, not through `xtrace.leaves`: an async
copy that the compiler overlaps with a kernel (`copy-done`, `slice-done`)
is an event inside the kernel's interval on the same line, and `leaves`
then takes the kernel for a parent and drops it (seen on the chip, PR 26:
`moe_gmm` read 105% of its roofline that way). A custom call has no
children of its own, so its events are its time.

A prefill runs `moe_gmm` too (and streams every held expert for one prompt),
so a roofline share of the decode step takes only the ops that ran while a
`decode_burst` program did: on one chip programs run one after another, so
an op belongs to the program whose "XLA Modules" event holds it.
"""

from __future__ import annotations

import importlib

from perf.lib import readers, xtrace


def family_of(obs: dict):
    return importlib.import_module(
        f"perf.families.{obs['config']['family']}")


def kernel_events(plane: dict, name: str) -> list:
    """[start, duration] of the ops whose name starts with `name`."""
    return sorted([s, d] for n, s, d
                  in xtrace.line_events(plane, xtrace.OPS_LINE)
                  if d > 0 and xtrace.op_name(n).startswith(name))


def kernel_dev_pct(obs: dict, name: str):
    """Share of chip 0's busy time inside the traced slice spent in the
    ops whose name starts with `name`, in percent; None where none ran."""
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, _ = sl
    secs = sum(b - a for a, b in xtrace.clip(
        [[name, s, d] for s, d in kernel_events(
            xtrace.device_planes(trace)[0], name)], t0, t1))
    if secs <= 0:
        return None
    return 100.0 * secs / xtrace.busy(trace, t0, t1)["per_chip_s"][0]


def decode_kernel(obs: dict, name: str):
    """(device seconds of the leaf ops whose name starts with `name` inside
    the slice's `decode_burst` runs, decode steps of those runs, mean
    decoding slots of the slice's ticks), or None without a trace, such an
    op, or a decoding tick."""
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, off = sl
    plane = xtrace.device_planes(trace)[0]
    runs = sorted((s, s + d) for n, s, d
                  in xtrace.line_events(plane, xtrace.MODULES_LINE)
                  if "decode_burst" in n and s >= t0 and s + d <= t1)
    if not runs:
        return None
    secs, at = 0.0, 0
    for s, d in kernel_events(plane, name):
        while at < len(runs) and runs[at][1] < s:
            at += 1
        if at < len(runs) and runs[at][0] <= s and s + d <= runs[at][1]:
            secs += d
    origin = obs["t_origin"] + off
    slots = [k["slots"] for k in obs["ticks"]
             if k["slots"] and t0 <= origin + k["t"]
             and origin + k["t"] + k["dt"] <= t1]
    if secs <= 0 or not slots:
        return None
    return secs, len(runs) * obs["burst"], sum(slots) / len(slots)


def experts_touched_a_step(obs: dict):
    """Held experts with at least one row, summed over the expert layers,
    a decode step: the mean over the decode bursts of the traced slice, as
    the program counted them (`obs["expert_bursts"]`, driver
    `serve_by_leaf`); None without such counts."""
    sl = readers._slice(obs)
    bursts = obs.get("expert_bursts")
    if sl is None or not bursts:
        return None
    _, t0, t1, off = sl
    seen = [n for t, n in bursts if t0 <= t + off <= t1]
    if not seen:
        return None
    return sum(seen) / (len(seen) * obs["burst"])

