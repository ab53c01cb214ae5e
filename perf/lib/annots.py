"""The program's own counts, off the profiler's host line: what a span knew,
on the clock the device ops are on.

**What the events are.** The program mirrors every lane span of its
`TraceRecorder` into the profiler as an annotation `serve:<span name>` /
`train:<span name>` (`utils/trace.py set_annotate`), and since PR 49 hands
that annotation the span's scalar attributes as the span ENDS
(`set_metadata`), so what a readback filled in is there. In an `.xplane.pb`
each is then one event of a host thread's line of the plane `/host:CPU`, with
a start and a duration in nanoseconds and the attributes as the event's OWN
stats, which `jax.profiler.ProfileData` yields (`event.stats`: `('active', 96),
('pages_walked', 31744), ...`). The same file holds the device planes, so a
`serve:decode_burst` event and the `jit__decode_burst` run it dispatched stand
on ONE clock: no offset between a host clock and the trace's is taken or
needed here, and none of a driver's objects is read. A `slow_tick` or
`chunk_admit` instant is a zero-length event of the same kind.

**How the slice is cut.** The drivers open the benchmark's marker annotation
`perf:traced` right after the profiler and leave it right before they stop
it. `events()` keeps the events that lie wholly inside that marker (all of
them where a trace has no marker: a test's), in the order they began.

**What a reader gets.** `[name, start_s, dur_s, attrs]` rows, `attrs` a dict
(empty where the program handed nothing over: the parent commit of PR 49
mirrors the spans without their attributes, and every reader below then has
nothing to read and returns None, so the result line leaves its metric out).
The xplane is found as `scopes.events_with_paths` finds it: `obs["xplane"]`
where a caller gives it, else the newest one under `perf_out/` (the process
that asks wrote exactly one). It is parsed ONCE a process and path however
many readers ask, and only its host planes are walked.

**The five readers** (`perf/layer_metrics/flood_slots_decoding_pct.py`,
`flood_slots_prefilling_pct.py`, `flood_prefill_pad_pct.py`,
`flood_prefill_dev_tok_s.py`, `flood_prefix_hit_pct.py`) are the functions at
the end of this file; a later `benchmark` PR repoints the side-channel
rooflines (`obs["expert_bursts"]`, `obs["admits"]`, `obs["chunks"]`, ...) to
the same rows and retires the fork drivers that stamp them.
"""

from __future__ import annotations

import functools

from perf.lib import readers, scopes, xtrace

MARKER = readers.MARKER
PREFIXES = ("serve:", "train:")
# the jitted admission programs, by the names `serve/engine.py` holds as a
# contract ("XLA Modules" line: `jit__prefill_admit(...)`)
PREFILL_PROGRAMS = r"prefill_admit|prefix_prefill"


@functools.lru_cache(maxsize=2)
def load(path: str) -> tuple:
    """Every `serve:*` / `train:*` / `perf:traced` event of the host planes
    of one `.xplane.pb`, (name, start_s, dur_s, attrs), in the order they
    began. One `ProfileData.from_file` a process and path."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == MARKER or e.name.startswith(PREFIXES):
                    out.append((e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9, dict(e.stats)))
    return tuple(sorted(out, key=lambda e: e[1]))


def clip(rows, marker: str = MARKER) -> list:
    """The rows wholly inside the first `marker` row (the marker itself
    left out), or all of them where there is none."""
    mark = next((e for e in rows if e[0] == marker), None)
    if mark is None:
        return [list(e) for e in rows]
    t0, t1 = mark[1], mark[1] + mark[2]
    return [list(e) for e in rows
            if e[0] != marker and t0 <= e[1] and e[1] + e[2] <= t1]


def events(path: str | None = None):
    """The traced slice's annotation rows (module doc), or None where no
    xplane is found."""
    path = path or scopes.newest_xplane()
    return None if path is None else clip(load(path))


def of(obs: dict):
    """The rows of one run's observations: `obs["annots"]` where a caller
    (a test) hands them, else read from the run's xplane once and kept."""
    if "annots" not in obs:
        obs["annots"] = events(obs.get("xplane")) \
            if obs.get("trace") is not None else None
    return obs["annots"]


def named(rows, name: str, *keys: str) -> list:
    """The rows called `name` that carry every one of `keys`."""
    return [e for e in rows or ()
            if e[0] == name and all(k in e[3] for k in keys)]


# ---------------------------------------------------------------- readers
def slot_seconds_pct(obs: dict, key: str):
    """Share of the slice's slot-seconds spent as the `tick` attribute
    `key` counts them (`decoding`: in the tick's burst; `prefilling`: still
    mid-prompt as it ends): sum of key x the tick's duration over sum of
    `slots` x duration, in percent. Weighted by TIME: a tick of 0.1 s with
    3 of 4 slots beside one of 0.3 s with 1 of 4 reads 37.5, not 50."""
    ticks = named(of(obs), "serve:tick", "slots", key)
    whole = sum(e[3]["slots"] * e[2] for e in ticks)
    if whole <= 0:
        return None
    return 100.0 * sum(e[3][key] * e[2] for e in ticks) / whole


def prefill_calls(obs: dict) -> list:
    """(positions run, positions that hold a token) of each admission call
    that began inside the slice. A `prefill_chunk` runs its `bucket` for
    `take` tokens. A `prefill` runs its `bucket` for the prompt's tokens the
    prefix cache did NOT have, `prompt_len - prefix_hit`: the matched blocks
    join the slot's table and only the suffix goes through the program
    (`prefix_hit` is 0 without the cache, and the whole prompt is real)."""
    rows = of(obs)
    return [(e[3]["bucket"], e[3]["take"]) for e in named(
        rows, "serve:prefill_chunk", "bucket", "take")] \
        + [(e[3]["bucket"], e[3]["prompt_len"] - e[3]["prefix_hit"])
           for e in named(rows, "serve:prefill", "bucket", "prompt_len",
                          "prefix_hit")]


def prefill_pad_pct(obs: dict):
    """Positions the slice's admission calls ran that hold no token, over
    the positions they ran, in percent."""
    calls = prefill_calls(obs)
    ran = sum(b for b, _ in calls)
    if ran <= 0:
        return None
    return 100.0 * (ran - sum(r for _, r in calls)) / ran


def prefill_dev_tok_s(obs: dict):
    """Real prompt positions of the admission calls that began inside the
    slice over the device seconds of the admission programs that ran inside
    it (chip 0's "XLA Modules" line): both ends on the trace's clock. A
    call dispatched at the slice's edge may run outside it and one from
    before may run inside: one or two calls of a slice's dozens."""
    calls, trace = prefill_calls(obs), obs.get("trace")
    if not calls or trace is None or not xtrace.device_planes(trace):
        return None
    t0, t1 = xtrace.window_of(trace, MARKER)
    secs = sum(xtrace.module_runs(trace, PREFILL_PROGRAMS, t0, t1))
    if secs <= 0:
        return None
    return sum(r for _, r in calls) / secs


def prefix_hit_pct(obs: dict):
    """Prompt tokens the radix cache had at admission over prompt tokens
    admitted inside the slice, in percent. An admission is ONE event: its
    `prefill` span, or the `chunk_admit` instant of a prompt taken in by
    chunks (whose `prefill_chunk` spans all repeat the hit and are not
    counted). Blocks a chunked slot adopts later from another request are
    not in it (the radix's own counters move those from miss to hit)."""
    rows = of(obs)
    admits = named(rows, "serve:prefill", "prompt_len", "prefix_hit") \
        + named(rows, "serve:chunk_admit", "prompt_len", "prefix_hit")
    tokens = sum(e[3]["prompt_len"] for e in admits)
    if tokens <= 0:
        return None
    return 100.0 * sum(e[3]["prefix_hit"] for e in admits) / tokens
