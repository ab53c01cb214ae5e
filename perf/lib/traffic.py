"""One general generator for serving traffic, driven by a data file.

A traffic file (`perf/traffic/<name>.json`, `"driver": "serve"`) gives the
arrival process and the length distributions of one or more tenants; this
module expands it into a schedule of requests drawn BEFORE the window.

Every seed gets the same work. The set of (prompt length, new tokens) pairs
and the set of gaps between arrivals are drawn once from the file's own
`shape_seed`; `--seed` only permutes their order and draws the token ids. So
two seeds offer the same tokens at the same mean rate with the same bursts of
closeness, in another order, and a difference between their runs is the
system's and not the dice's. (Copied in spirit from the program's
`serve/workload.py` — Lewis thinning for bursts, lognormal lengths, sessions
that re-feed their history — so that a later PR to `serve/` cannot change the
offered load.)
"""

from __future__ import annotations

import math

import numpy as np

ARRIVALS = ("poisson", "bursty")


def _rate_at(t: float, spec: dict) -> float:
    if spec.get("arrivals", "poisson") == "bursty":
        in_burst = (t % spec["burst_every_s"]) < spec["burst_len_s"]
        return spec["rate_rps"] * (spec["burst_mult"] if in_burst else 1.0)
    return spec["rate_rps"]


def _peak(spec: dict) -> float:
    if spec.get("arrivals", "poisson") == "bursty":
        return spec["rate_rps"] * spec["burst_mult"]
    return spec["rate_rps"]


def _arrival_times(spec: dict, duration_s: float, rng) -> np.ndarray:
    """Lewis thinning against the peak rate; for plain Poisson this is the
    homogeneous process itself."""
    kind = spec.get("arrivals", "poisson")
    if kind not in ARRIVALS:
        raise ValueError(f"arrivals {kind!r}: one of {ARRIVALS}")
    peak, out, t = _peak(spec), [], 0.0
    while True:
        t += float(rng.exponential(1.0 / peak))
        if t >= duration_s:
            return np.asarray(out)
        if float(rng.random()) * peak <= _rate_at(t, spec):
            out.append(t)


def _lognormal(rng, n: int, median: float, sigma: float, lo: int,
               cap: int) -> np.ndarray:
    draw = median * np.exp(rng.normal(0.0, sigma, size=n)) if sigma > 0 \
        else np.full(n, float(median))
    return np.clip(np.rint(draw), lo, cap).astype(np.int64)


def build_schedule(traffic: dict, *, seed: int, duration_s: float,
                   vocab: int) -> list:
    """Requests due in [0, duration_s), sorted by due time.

    Each is {"rid", "due_s", "prompt" (list of int), "max_new", "tenant"}.
    """
    rows = []
    for ti, spec in enumerate(traffic["tenants"]):
        shape = np.random.default_rng(
            np.random.SeedSequence([int(traffic["shape_seed"]), ti]))
        times = _arrival_times(spec, duration_s, shape)
        n = len(times)
        prompt_len = _lognormal(shape, n, spec["prompt_len_median"],
                                spec["prompt_len_sigma"], 1,
                                spec["prompt_len_cap"])
        max_new = _lognormal(shape, n, spec["max_new_median"],
                             spec["max_new_sigma"], 2, spec["max_new_cap"])
        order = np.random.default_rng(
            np.random.SeedSequence([int(seed), ti, 0x0DE2]))
        if spec.get("arrivals", "poisson") == "poisson":
            # the same gaps in another order: same count, same last arrival
            gaps = np.diff(np.concatenate([[0.0], times]))
            times = np.cumsum(order.permutation(gaps))
        pick = order.permutation(n)
        prompt_len, max_new = prompt_len[pick], max_new[pick]
        toks = np.random.default_rng(
            np.random.SeedSequence([int(seed), ti, 0x70C5]))
        sessions = int(spec.get("sessions", 0))
        if sessions:
            prefix = [toks.integers(0, vocab,
                                    spec["session_prefix_len"]).tolist()
                      for _ in range(sessions)]
            history = [list(p) for p in prefix]
            turns = [0] * sessions
        for k in range(n):
            tail = toks.integers(0, vocab, int(prompt_len[k])).tolist()
            if sessions:
                s = k % sessions
                if turns[s] >= spec["turns_per_session"]:
                    history[s], turns[s] = list(prefix[s]), 0
                prompt = (history[s] + tail)[-spec["prompt_len_cap"]:]
                history[s] = history[s] + tail
                turns[s] += 1
            else:
                prompt = tail
            rows.append({"due_s": float(times[k]), "prompt": prompt,
                         "max_new": int(max_new[k]),
                         "tenant": spec.get("name", f"t{ti}")})
    rows.sort(key=lambda r: r["due_s"])
    for i, r in enumerate(rows):
        r["rid"] = i
    return rows


def offered_summary(rows: list, duration_s: float) -> dict:
    """What the schedule offers, for the run's series file."""
    if not rows:
        return {"requests": 0}
    p = [len(r["prompt"]) for r in rows]
    m = [r["max_new"] for r in rows]
    gaps = np.diff([r["due_s"] for r in rows])
    return {"requests": len(rows), "duration_s": duration_s,
            "rate_rps": len(rows) / duration_s,
            "prompt_tokens": int(sum(p)), "new_tokens": int(sum(m)),
            "prompt_len_max": int(max(p)), "max_new_max": int(max(m)),
            "context_max": int(max(a + b for a, b in zip(p, m))),
            "new_tokens_per_s": sum(m) / duration_s,
            "first_due_s": rows[0]["due_s"], "last_due_s": rows[-1]["due_s"],
            "gap_cv": float(np.std(gaps) / np.mean(gaps))
            if len(gaps) > 1 else math.nan}
