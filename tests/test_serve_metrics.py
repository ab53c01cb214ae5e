"""Metrics registry (utils/metrics.py) + process-0 emission gate
(utils/logging.py emit_metrics) + serving adapter (serve/metrics.py).

The multi-host invariant pinned here: metric lines are a rank-0 side
effect like every other print/save in the framework — a non-0 process
calling emit_metrics produces NOTHING (no log record, None return), so
an N-host serving deployment emits one line per snapshot, not N.
"""

import logging

import pytest

from ddp_practice_tpu.utils.logging import emit_metrics, get_logger
from ddp_practice_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    labelled,
)


@pytest.mark.fast
def test_counter_gauge_histogram(devices):
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = Gauge()
    g.set(3)
    g.set(1.5)
    assert g.value == 1.5

    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.mean == pytest.approx(50.5)
    assert h.percentile(50) == pytest.approx(50.0, abs=1.0)
    assert h.percentile(99) == pytest.approx(99.0, abs=1.0)
    s = h.summary()
    assert s["count"] == 100 and "p99" in s


@pytest.mark.fast
def test_histogram_reservoir_bounds_memory(devices):
    h = Histogram(max_samples=8)
    for v in range(1000):
        h.observe(float(v))
    assert h.count == 1000            # exact count survives the bound
    assert h.sum == pytest.approx(sum(range(1000)))
    assert len(h._samples) == 8       # reservoir stays bounded
    # quantiles reflect recent traffic (the last ring-buffer writes)
    assert h.percentile(50) >= 900


@pytest.mark.fast
def test_registry_create_or_get_and_snapshot(devices):
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    r.counter("a").inc(2)
    r.gauge("b").set(7)
    r.histogram("c").observe(1.0)
    snap = r.snapshot()
    assert snap["a"] == 2 and snap["b"] == 7
    assert snap["c_count"] == 1 and snap["c_mean"] == 1.0


@pytest.mark.fast
def test_emit_metrics_process0_gate(devices, monkeypatch, caplog):
    """Process 0 emits one line; any other process index emits nothing."""
    import jax

    # a name OUTSIDE the package hierarchy: get_logger("ddp_practice_tpu")
    # (created at import by train/elastic.py and friends) sets
    # propagate=False, so a child like ddp_practice_tpu.serve.* would
    # have its records swallowed at that parent before caplog's root
    # handler — whenever any train test is merely COLLECTED in the same
    # session, this test would flake on hierarchy, not on the gate
    logger = get_logger("serve_test_gate")
    logger.propagate = True  # let caplog's root handler see it

    with caplog.at_level(logging.INFO, logger="serve_test_gate"):
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        line = emit_metrics({"serve_tokens_total": 5}, logger)
        assert line.startswith("metrics ")
        assert '"serve_tokens_total": 5' in line
        assert any("serve_tokens_total" in r.message for r in caplog.records)

        caplog.clear()
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        assert emit_metrics({"serve_tokens_total": 5}, logger) is None
        assert not caplog.records


@pytest.mark.fast
def test_serve_metrics_report(devices):
    """The adapter names/types serving metrics and folds in tokens/sec."""
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.scheduler import Completion

    m = ServeMetrics()
    m.tokens_total.inc(40)
    m.on_complete(
        Completion(rid=0, tokens=[1, 2], status="eos", arrival=0.0,
                   finish=1.0, ttft=0.5, tpot=0.1),
        scheduler=None,
    )
    rep = m.report(elapsed_s=2.0)
    assert rep["serve_tokens_per_sec"] == pytest.approx(21.0)  # 42 / 2
    assert rep["serve_requests_eos"] == 1
    assert rep["serve_ttft_s_count"] == 1
    assert rep["serve_tpot_s_p50"] == pytest.approx(0.1)


@pytest.mark.fast
def test_paged_pool_metrics_export(devices, engine_at_rest):
    """The PR-6 pool observables (kv_blocks_in_use / kv_blocks_shared
    gauges, prefix-cache hit/miss token counters, preemptions_total)
    flow from the engine's cumulative fields into the registry as
    DELTAS per tick — and therefore onto /metrics (render_text) and the
    telemetry JSONL like every other metric. Host-pure via a stub
    engine mirroring PagedEngine's observable surface."""
    from ddp_practice_tpu.serve.metrics import ServeMetrics

    class _Blocks:
        num_blocks, num_used, num_shared, num_free = 9, 5, 2, 3

    class _Radix:
        hit_tokens, miss_tokens = 24, 8

        def evictable(self):
            return 1

    class _Sched:
        engine = engine_at_rest(
            blocks=_Blocks(), radix=_Radix(), num_active=2,
            blocks_available=4,   # free + evictable
            preemptions=3)
        queue = ()

    m = ServeMetrics()
    m.on_tick(_Sched())
    rep = m.report()
    assert rep["kv_blocks_in_use"] == 5
    assert rep["kv_blocks_shared"] == 2
    assert rep["prefix_cache_hit_tokens_total"] == 24
    assert rep["prefix_cache_miss_tokens_total"] == 8
    assert rep["preemptions_total"] == 3
    # a second tick with no movement adds NOTHING (delta export, so the
    # counters stay counters even though the engine fields are gauges
    # of cumulative state)
    m.on_tick(_Sched())
    rep = m.report()
    assert rep["prefix_cache_hit_tokens_total"] == 24
    assert rep["preemptions_total"] == 3
    # and the names render on the Prometheus exposition
    text = m.registry.render_text()
    for name in ("kv_blocks_in_use", "kv_blocks_shared",
                 "prefix_cache_hit_tokens_total", "preemptions_total"):
        assert name in text


@pytest.mark.fast
def test_render_text_exposition(devices):
    """Prometheus text format: TYPE lines per family, labelled() names
    re-rendered as name{k="v"}, histograms as summaries with exact
    count/sum. Byte-stable ordering (families and label sets sorted)."""
    r = MetricsRegistry()
    r.counter("req_total").inc(7)
    r.counter(labelled("sheds_total", reason="brownout")).inc(2)
    r.counter(labelled("sheds_total", reason="queue_full")).inc()
    r.gauge(labelled("replica_state", replica=1)).set(2)
    h = r.histogram("ttft_s")
    for v in (0.1, 0.2, 0.4):
        h.observe(v)
    text = r.render_text()
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "# TYPE req_total counter" in lines
    assert "req_total 7" in lines
    # one TYPE line per family, not per labelled child
    assert lines.count("# TYPE sheds_total counter") == 1
    i = lines.index("# TYPE sheds_total counter")
    # children sorted by rendered labels, values quoted
    assert lines[i + 1] == 'sheds_total{reason="brownout"} 2'
    assert lines[i + 2] == 'sheds_total{reason="queue_full"} 1'
    assert 'replica_state{replica="1"} 2' in lines
    assert 'ttft_s{quantile="0.5"} 0.2' in lines
    assert "ttft_s_count 3" in lines
    assert any(ln.startswith("ttft_s_sum 0.7") for ln in lines)
    # deterministic: same registry state -> identical bytes
    assert r.render_text() == text


@pytest.mark.fast
def test_render_text_escaping_and_label_ordering(devices):
    """Label values escape backslash/quote/newline; multi-label names
    render with keys sorted however the caller spelled the kwargs."""
    r = MetricsRegistry()
    r.counter(labelled("esc_total", path='say "hi"\nnow', d="a\\b")).inc()
    # same label SET spelled in the other kwarg order -> same metric
    r.counter(labelled("esc_total", d="a\\b", path='say "hi"\nnow')).inc()
    text = r.render_text()
    assert (
        'esc_total{d="a\\\\b",path="say \\"hi\\"\\nnow"} 2' in
        text.splitlines()
    )
