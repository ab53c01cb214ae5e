"""Tier-1 runtime-budget sentinel (runs LAST by alphabetical order).

The tier-1 gate wraps pytest in ``timeout -k 10 870`` — a suite that
outgrows the budget is TRUNCATED, and truncation reads as "fewer dots",
not as a failure. This file is the in-run alarm: z-named so the
``-p no:randomly`` alphabetical collection order schedules it after
every other test, when the conftest duration ledger is complete, it
projects the full-session wall time and fails LOUDLY while there is
still budget left to report in.

Offline twin: tools/check_durations.py audits the JSON ledger the
conftest writes at sessionfinish (env ``DDP_T1_DURATIONS_OUT``,
default /tmp/_t1_durations.json) — same projection, same budget.
"""

import pytest

# the tier-1 wrapper's hard timeout (also in conftest.T1_BUDGET_S;
# tests/ is not a package, so the constant is repeated, not imported)
T1_BUDGET_S = 870.0

# projection model: summed per-test durations undercount collection,
# imports, and fixture teardown still to come — pad by 5% plus a flat
# tail allowance before comparing against the hard timeout
OVERHEAD_FACTOR = 1.05
TAIL_ALLOWANCE_S = 45.0
# a partial run (-k, a single file) proves nothing about the suite;
# only audit when the ledger looks like the real tier-1 population
MIN_REPORTS = 100


# a ledger that holds fewer tests than the session collected is a
# SHARE of the suite: a pytest-xdist worker collects every test and runs
# (and keeps the durations of) its own files only, timed beside the
# other workers (the same test read 15.5 s alone and 20.4-25.3 s beside
# five). There the sum says nothing and the clock says it all: the
# workers run side by side, so the wall time this process has run is
# what the timeout cuts. A single duration there is judged against a
# line doubled once more for the load.
SHARED_LOAD_MARGIN = 2.0


def _is_a_share(request, ledger) -> bool:
    # the two sentinels themselves are collected and not yet reported
    return len(ledger) + 2 < request.session.testscollected


def test_t1_suite_fits_the_timeout(request, t1_duration_ledger,
                                   t1_session_wall_s):
    markexpr = getattr(request.config.option, "markexpr", "") or ""
    if "not slow" not in markexpr.replace("(", "").replace(")", ""):
        pytest.skip("budget sentinel audits only the tier-1 "
                    "(-m 'not slow') run")
    if len(t1_duration_ledger) < MIN_REPORTS:
        pytest.skip(f"partial run ({len(t1_duration_ledger)} reports "
                    f"< {MIN_REPORTS}) — not the tier-1 population")
    total = sum(t1_duration_ledger.values())
    if _is_a_share(request, t1_duration_ledger):
        total = t1_session_wall_s()
    projected = total * OVERHEAD_FACTOR + TAIL_ALLOWANCE_S
    slowest = sorted(t1_duration_ledger.items(),
                     key=lambda kv: -kv[1])[:10]
    detail = "\n".join(f"  {d:7.2f}s  {n}" for n, d in slowest)
    assert projected < T1_BUDGET_S, (
        f"tier-1 projects to {projected:.0f}s against the hard "
        f"{T1_BUDGET_S:.0f}s timeout ({total:.0f}s measured across "
        f"{len(t1_duration_ledger)} tests) — the timeout TRUNCATES "
        f"silently, so shed load now: mark the slowest tests "
        f"@pytest.mark.slow (>10 s belongs there).\nslowest:\n{detail}"
    )


# the in-run marker gate hard-fails only past this multiple of the 10 s
# line: this 1-core box shows >2x run-to-run variance on individual
# tests (7.8 s and 18.4 s for the SAME test in back-to-back clean
# runs), so a test in the 1x-2x band is load noise, not a budget
# threat — it surfaces as a pytest warning instead of flapping tier-1.
# The PR-9-class offenders this gate exists for ran 12-188 s each,
# far past any noise band. tools/check_durations.py --strict-slow
# stays EXACT at 10 s for offline audits on quiet machines.
NOISE_MARGIN = 2.0


def test_t1_no_unmarked_slow_tests(request, t1_duration_ledger):
    """The marker contract, enforced in-run: any test over 10 s inside
    the tier-1 (``not slow``) population belongs behind
    ``@pytest.mark.slow``. This is tools/check_durations.py
    ``--strict-slow`` wired into the suite itself — the offline auditor
    only runs when someone remembers to, and an unmarked 30 s test
    erodes the 870 s budget three PRs before the projection sentinel
    above starts failing. Same ``audit()`` code path, so the CLI and
    the in-run gate cannot drift on what counts as an offender; the
    in-run gate only adds the NOISE_MARGIN band above."""
    import warnings as warnings_mod

    from tools.check_durations import SLOW_MARK_S, audit

    markexpr = getattr(request.config.option, "markexpr", "") or ""
    if "not slow" not in markexpr.replace("(", "").replace(")", ""):
        pytest.skip("marker-hygiene sentinel audits only the tier-1 "
                    "(-m 'not slow') run")
    if len(t1_duration_ledger) < MIN_REPORTS:
        pytest.skip(f"partial run ({len(t1_duration_ledger)} reports "
                    f"< {MIN_REPORTS}) — not the tier-1 population")
    ledger = dict(t1_duration_ledger)
    errors, warnings, _ = audit({
        "markexpr": markexpr,
        "tests": ledger,
    })
    assert not errors, "\n".join(errors)
    hard_line = SLOW_MARK_S * NOISE_MARGIN
    if _is_a_share(request, t1_duration_ledger):
        hard_line *= SHARED_LOAD_MARGIN
    hard = [w for w in warnings
            if ledger.get(w.split(" took", 1)[0], 0.0) > hard_line]
    for w in warnings:
        if w not in hard:
            warnings_mod.warn(
                f"near the tier-1 slow line (noise band "
                f"{SLOW_MARK_S:.0f}-{hard_line:.0f}s): {w}")
    assert not hard, (
        f"{len(hard)} unmarked test(s) over {hard_line:.0f}s "
        f"({NOISE_MARGIN:.0f}x the {SLOW_MARK_S:.0f}s line — past any "
        "load-noise band) inside the tier-1 run — each line below is "
        "a one-line @pytest.mark.slow diff:\n  " + "\n  ".join(hard)
    )
