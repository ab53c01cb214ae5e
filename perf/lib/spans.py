"""The program's span tree to numbers: what the readers of PR 24 share.

`obs["spans"]` carries [name, t0, t1] only (monotonic seconds), so a span's
children are found here by NAME and INTERVAL: a `burst_readback` belongs to
the `tick` whose interval holds it. (The program's own export links spans by
`parent`; that is for its traces and its tests.) A program without these
spans — the parent commit of the PR that added them — gives every reader
here nothing to read, and it returns None.

Serving readers look only at spans that begin inside the window and end
before the traced slice closes: closing the profiler stalls the loop for
seconds, and the driver cuts its `ticks` there for the same reason.
"""

from __future__ import annotations

from bisect import bisect_right

from perf.lib import readers, xtrace

# what a step's host time is split into under `train_epoch`, by name
TRAIN_PHASES = ("data", "dispatch", "block")


def named(obs: dict, name: str, t0: float, t1: float) -> list:
    """(start, end) of the spans called `name` that begin at or after t0
    and end by t1, in order."""
    return sorted((a, b) for n, a, b in obs.get("spans", [])
                  if n == name and t0 <= a and b <= t1)


def inside(spans: list, parents: list) -> list:
    """Those of `spans` that lie within one of `parents` (sorted, disjoint
    (start, end) pairs)."""
    starts = [p[0] for p in parents]
    out = []
    for a, b in spans:
        i = bisect_right(starts, a) - 1
        if i >= 0 and b <= parents[i][1]:
            out.append((a, b))
    return out


def seconds(spans: list) -> float:
    return sum(b - a for a, b in spans)


# ------------------------------------------------------------------ serve
def serve_range(obs: dict) -> tuple:
    w0, w1 = obs["window"]
    traced = obs.get("traced")
    return w0, (min(w1, traced[1]) if traced else w1)


def ticks(obs: dict) -> list:
    """The `tick` spans (one a `Scheduler.step()`) the serving readers
    count."""
    return named(obs, "tick", *serve_range(obs))


def ms_per_tick(obs: dict, *names: str):
    """Mean milliseconds a tick spends in its spans called `names`."""
    tk = ticks(obs)
    if not tk:
        return None
    lo, hi = serve_range(obs)
    total = sum(seconds(inside(named(obs, n, lo, hi), tk)) for n in names)
    return 1e3 * total / len(tk)


def tick_max_ms(obs: dict):
    tk = ticks(obs)
    return 1e3 * max(b - a for a, b in tk) if tk else None


def outside_tick_ms(obs: dict):
    """Mean milliseconds between the end of one tick and the start of the
    next: the caller's own loop."""
    tk = ticks(obs)
    if len(tk) < 2:
        return None
    return 1e3 * sum(b[0] - a[1] for a, b in zip(tk, tk[1:])) / (len(tk) - 1)


# ------------------------------------------------------------------ train
def host_other_ms_per_step(obs: dict):
    """Milliseconds of host time a step spends inside `train_epoch` and
    outside `data`, `dispatch` and `block`: `epoch_open`, `after_group`
    less its `block`, and whatever has no name."""
    w0, w1 = obs["window"]
    epochs = [(a, b) for n, a, b in obs.get("spans", [])
              if n == "train_epoch" and w0 <= a <= w1]
    if not epochs:
        return None
    epochs.sort()
    lo, hi = epochs[0][0], epochs[-1][1]
    named_s = sum(seconds(inside(named(obs, n, lo, hi), epochs))
                  for n in TRAIN_PHASES)
    steps = len(obs["segments"]) * obs["steps_per_segment"]
    return 1e3 * (seconds(epochs) - named_s) / steps


# ----------------------------------------------------------------- clocks
def clock_skew_us(obs: dict, prefix: str):
    """How far the one offset that joins the two clocks is off, at worst:
    over the profiler's annotations `<prefix>:<span name>` inside the traced
    slice (the program mirrors each of its spans into one), the distance
    between the annotation's start and its program span's start moved onto
    the trace's clock. Microseconds. The idle gaps' attribution to spans is
    only as good as this.

    The k-th annotation of a name inside the marker goes with the k-th
    program span of that name inside `traced`: both are counted from the
    marker in the order the program ran them, on their own clocks, so an
    offset that is off by more than the distance between two spans is still
    read in full. A name whose two counts differ (an event the profiler
    lost) cannot be paired so and is left out."""
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, off = sl
    a0, a1 = obs["traced"]
    mine, theirs = {}, {}
    for n, a, b in obs.get("spans", []):
        if a0 <= a and b <= a1:
            mine.setdefault(n, []).append(a + off)
    for name, at, dur in xtrace.host_events(trace, prefix + ":"):
        if t0 <= at and at + dur <= t1:
            theirs.setdefault(name[len(prefix) + 1:], []).append(at)
    gaps = [abs(at - s) for name, ats in theirs.items()
            if len(ats) == len(mine.get(name, ()))
            for at, s in zip(sorted(ats), sorted(mine[name]))]
    return 1e6 * max(gaps) if gaps else None


# ---------------------------------------------------------------- kernels
def kernel_dev_pct(obs: dict, prefix: str):
    """Share of chip 0's busy time inside the traced slice spent in leaf
    ops whose name starts with `prefix`, in percent; None where no such op
    ran (the kernel has no name of its own there)."""
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, _ = sl
    kernel = sum(v for k, v in xtrace.op_seconds(trace, t0, t1).items()
                 if k.startswith(prefix))
    if kernel <= 0:
        return None
    return 100.0 * kernel / xtrace.busy(trace, t0, t1)["per_chip_s"][0]
