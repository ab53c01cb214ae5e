"""Front door wire surface (serve/frontdoor.py + sse.py + admission.py).

Pinned in three tiers, cheapest first:

- host-pure units: the SSE codec survives arbitrary TCP re-chunking,
  the admission controller's token bucket and concurrency cap replay on
  a FakeClock, and a wire capture bridges into the same stream audit
  (tools/check_stream.py) the in-process benches use.
- `net` + stub router: every refusal path (404/400/401/429/503) and
  /healthz run against a real socket but a router that never has to
  exist — the door turns these away before the engine is touched, so
  the test should not pay for an engine either.
- `net` e2e: a real router behind the door. Greedy tokens over
  the wire are bit-identical to `router.stream()` in-process, frame ids
  are contiguous with exactly one terminal, drain finishes in-flight
  streams while refusing new ones, and (`slow`) a deliberately throttled
  reader (tiny buffers at every layer) is SHED with a typed `slow_consumer`
  terminal while its request decodes to completion anyway.
"""

import http.client
import json
import threading
import time

import pytest

from ddp_practice_tpu.serve import (
    AdmissionController,
    EngineConfig,
    FakeClock,
    Frontdoor,
    FrontdoorConfig,
    FrontdoorMetrics,
    Request,
    TenantPolicy,
    make_router,
    sse_request,
)
from ddp_practice_tpu.serve.sse import KINDS, SSEParser, encode_event

VOCAB = 32


# ------------------------------------------------------ host-pure units
@pytest.mark.fast
def test_sse_codec_roundtrip_any_chunking():
    """encode_event -> SSEParser is identity no matter how TCP slices
    the byte stream — including one byte at a time."""
    events = [("tokens", 0, {"start": 0, "tokens": [3, 1]}),
              ("resumed", 1, {"start": 2, "tokens": []}),
              ("end", 2, {"start": 2, "tokens": [], "status": "eos"})]
    assert all(k in KINDS for k, _, _ in events)
    wire = b"".join(encode_event(*ev) for ev in events)

    for step in (1, 3, len(wire)):  # pathological, odd, single segment
        p = SSEParser()
        got = []
        for i in range(0, len(wire), step):
            got.extend(p.feed(wire[i:i + step]))
        assert [(e["event"], e["id"], e["data"]) for e in got] == [
            (k, i, d) for k, i, d in events
        ]


@pytest.mark.fast
def test_sse_parser_crlf_comments_and_malformed_payload():
    p = SSEParser()
    # \r\n framing, keep-alive comment line, unknown field — all per
    # spec; a non-JSON data payload surfaces as the raw string so the
    # audit can distinguish malformed from absent
    raw = (b": keep-alive\r\n\r\n"
           b"id: 0\r\nevent: tokens\r\nretry: 5\r\n"
           b"data: {\"tokens\":[7]}\r\n\r\n"
           b"event: end\ndata: not json\n\n")
    got = p.feed(raw)
    assert [(e["id"], e["event"]) for e in got] == [(0, "tokens"),
                                                   (None, "end")]
    assert got[0]["data"] == {"tokens": [7]}
    assert got[1]["data"] == "not json"


@pytest.mark.fast
def test_admission_token_bucket_replays_on_fake_clock():
    clock = FakeClock()
    adm = AdmissionController(
        {"t": TenantPolicy(rate_rps=2.0, burst=2)}, clock=clock
    )
    got = [adm.try_acquire("t") for _ in range(3)]
    assert [g[0] for g in got] == [True, True, False]
    assert got[2][1] == "rate" and adm.refused["rate"] == 1
    clock.advance(0.5)            # exactly one token refilled at 2 rps
    assert adm.try_acquire("t") == (True, None)
    assert adm.try_acquire("t")[1] == "rate"


@pytest.mark.fast
def test_admission_concurrency_cap_checked_before_rate():
    clock = FakeClock()
    adm = AdmissionController(
        {"t": TenantPolicy(rate_rps=100.0, burst=1, max_concurrent=1)},
        clock=clock,
    )
    assert adm.try_acquire("t") == (True, None)
    # over the cap: refused as "concurrency" and must NOT burn the rate
    # token the request was never going to use
    assert adm.try_acquire("t") == (False, "concurrency")
    adm.release("t")
    clock.advance(1.0)
    assert adm.try_acquire("t") == (True, None)
    # unknown tenants fall under the default policy (admit-everything)
    assert adm.try_acquire("someone-else") == (True, None)
    assert adm.inflight("t") == 1


@pytest.mark.fast
def test_wire_capture_bridges_into_stream_audit():
    """The bench's SSE capture format feeds tools/check_stream.py's
    verdict unchanged — one audit for both sides of the socket."""
    from tools.check_stream import sse_to_chunks, stream_verdict

    def rec(stream, i, kind, data):
        return {"stream": stream, "id": i, "event": kind, "data": data}

    good = [
        rec("rid:1", 0, "tokens", {"start": 0, "tokens": [5, 2]}),
        rec("rid:1", 1, "end",
            {"start": 2, "tokens": [9], "status": "length"}),
    ]
    ok, audit = stream_verdict(sse_to_chunks(good))
    assert ok, audit

    gap = [good[0], rec("rid:1", 2, "end",
                        {"start": 2, "tokens": [], "status": "eos"})]
    ok, audit = stream_verdict(sse_to_chunks(gap))
    assert not ok


# ------------------------------------------- refusal paths, stub router
class _StubRouter:
    """The slice of Router the door touches before submit: enough for
    every refusal path and /healthz, with no engine behind it."""

    def __init__(self):
        self.tracked = {}
        self.streams = {}
        self.idle = True
        self._pending = 0
        self.clock = FakeClock()

    def step(self):
        pass

    def states(self):
        return [{"replica": 0, "state": "up"}]


@pytest.fixture
def stub_door():
    adm = AdmissionController(
        {"capped": TenantPolicy(max_concurrent=1)}
    )
    fd = Frontdoor(
        _StubRouter(),
        config=FrontdoorConfig(auth_token="sekrit", max_prompt_len=64),
        admission=adm,
        metrics=FrontdoorMetrics(),
    )
    fd.start()
    yield fd, adm
    fd.close()


@pytest.mark.net
def test_door_refusals_are_typed_json(stub_door):
    fd, adm = stub_door
    auth = {"Authorization": "Bearer sekrit"}

    status, ev = sse_request("127.0.0.1", fd.port, {"prompt": [1, 2]})
    assert status == 401

    # correct token, bad bodies: the 400s prove auth ran first and the
    # validator names the offending field
    for body, needle in (
        ({"prompt": []}, "prompt"),
        ({"prompt": [1, -2]}, "prompt"),
        ({"prompt": [1] * 65}, "too long"),
        ({"prompt": [1, 2], "max_new_tokens": 0}, "max_new_tokens"),
    ):
        status, ev = sse_request("127.0.0.1", fd.port, body, headers=auth)
        assert status == 400, (body, status, ev)
        assert needle in ev[0]["data"]["error"], (body, ev)

    # per-tenant concurrency: hold the only slot, watch the 429
    ok, _ = adm.try_acquire("capped")
    assert ok
    status, ev = sse_request(
        "127.0.0.1", fd.port, {"prompt": [1], "tenant": "capped"},
        headers=auth)
    assert status == 429 and ev[0]["data"]["reason"] == "concurrency"
    adm.release("capped")


@pytest.mark.net
def test_door_fairness_refusal_is_typed_429():
    """The weighted-fair gate at the door (serve/fairshare.py VTC +
    fair_max_inflight): under pressure the MOST-over-served tenant's
    request bounces as a typed 429 "fairness" before it costs a queue
    slot; the starved tenant's identical request still 503s PAST
    admission (no replica) — the refusal is tenant-shaped, not load-
    shaped."""
    from ddp_practice_tpu.serve.fairshare import VirtualTokenCounter

    vtc = VirtualTokenCounter()
    vtc.charge("bulk", decode=100)
    vtc.touch("acme")
    adm = AdmissionController(vtc=vtc, fair_max_inflight=2)
    fd = Frontdoor(_StubRouter(), config=FrontdoorConfig(),
                   admission=adm, metrics=FrontdoorMetrics())
    fd.start()
    try:
        for t in ("bulk", "acme"):   # reach the pressure threshold
            assert adm.try_acquire(t) == (True, None)
        status, ev = sse_request(
            "127.0.0.1", fd.port, {"prompt": [1], "tenant": "bulk"})
        assert status == 429 and ev[0]["data"]["reason"] == "fairness"
        status, ev = sse_request(
            "127.0.0.1", fd.port, {"prompt": [1], "tenant": "acme"})
        assert status != 429    # admitted; fails later for other reasons
        assert adm.refused["fairness"] == 1
    finally:
        fd.close()


@pytest.mark.net
def test_healthz_and_drain_refusal(stub_door):
    fd, _ = stub_door
    conn = http.client.HTTPConnection("127.0.0.1", fd.port, timeout=10)
    conn.request("GET", "/healthz")
    resp = conn.getresponse()
    hz = json.loads(resp.read())
    assert resp.status == 200 and hz["status"] == "ok"
    assert hz["inflight_streams"] == 0 and hz["replicas"]

    conn = http.client.HTTPConnection("127.0.0.1", fd.port, timeout=10)
    conn.request("GET", "/nope")
    assert conn.getresponse().status == 404

    fd.begin_drain()
    status, ev = sse_request(
        "127.0.0.1", fd.port, {"prompt": [1, 2]},
        headers={"Authorization": "Bearer sekrit"})
    assert status == 503 and ev[0]["data"]["error"] == "draining"
    assert fd.drain(timeout_s=5)   # nothing in flight: immediate


# ----------------------------------------------------------- socket e2e
@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.models import create_model

    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=128, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.mark.net
def test_wire_identity_contiguity_and_drain(lm, devices):
    """One router, both sides: greedy reference tokens via
    `router.stream()` in-process, then the SAME router behind the door
    — the socket consumer must see bit-identical tokens, contiguous
    frame ids, exactly one terminal. Then drain: an in-flight stream
    finishes while a new request bounces with 503."""
    import numpy as np

    model, params = lm
    rng = np.random.default_rng(11)
    router = make_router(
        model, params, 1,
        EngineConfig(max_slots=4, prompt_buckets=(8, 16), max_len=96),
    )
    router.warmup()
    prompts = [rng.integers(1, VOCAB, int(rng.integers(4, 14))).tolist()
               for _ in range(5)]
    for i, p in enumerate(prompts):
        router.submit(Request(rid=i, prompt=p, max_new_tokens=8, seed=0))
    router.run_until_idle()
    ref = {i: router.stream(i).tokens() for i in range(len(prompts))}

    fd = Frontdoor(router, config=FrontdoorConfig(max_buffered_events=64))
    fd.start()
    try:
        for i, p in enumerate(prompts):
            status, events = sse_request(
                "127.0.0.1", fd.port,
                {"prompt": p, "max_new_tokens": 8, "seed": 0})
            assert status == 200, (status, events)
            assert [e["id"] for e in events] == list(range(len(events)))
            kinds = [e["event"] for e in events]
            assert kinds.count("end") == 1 and kinds[-1] == "end"
            assert events[-1]["data"]["status"] in ("eos", "length",
                                                    "stop")
            toks = [t for e in events if e["event"] == "tokens"
                    for t in e["data"]["tokens"]]
            toks += events[-1]["data"]["tokens"]
            assert toks == ref[i], (i, toks, ref[i])

        # ---- drain: started stream completes, new request refused
        results = []

        def consume():
            results.append(sse_request(
                "127.0.0.1", fd.port,
                {"prompt": prompts[0], "max_new_tokens": 24, "seed": 0},
                read_delay_s=0.02))

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.2)
        fd.begin_drain()
        status, _ = sse_request("127.0.0.1", fd.port, {"prompt": [1, 2]})
        assert status == 503
        t.join()
        status, events = results[0]
        assert status == 200 and events[-1]["event"] == "end"
        assert fd.drain(timeout_s=15)
    finally:
        fd.close()


@pytest.mark.net
@pytest.mark.slow
def test_slow_consumer_is_shed_not_obeyed(devices):
    """Tiny buffers at every layer (subscriber ring, transport
    watermark, both socket buffers) + a reader sipping one byte at a
    time: delivery is cut with a single typed `slow_consumer` terminal,
    the shed counter ticks, and the request keeps decoding — the router
    drains to idle with no socket holding a KV slot hostage."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_practice_tpu.models import create_model

    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=512, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    router = make_router(
        model, params, 1,
        EngineConfig(max_slots=2, prompt_buckets=(16,), max_len=400),
    )
    router.warmup()
    fd = Frontdoor(router, config=FrontdoorConfig(
        max_buffered_events=2, write_buffer_bytes=256, sndbuf=1))
    fd.start()
    try:
        prompt = np.random.default_rng(0).integers(
            1, VOCAB, 12).tolist()
        status, events = sse_request(
            "127.0.0.1", fd.port,
            {"prompt": prompt, "max_new_tokens": 380, "seed": 0},
            read_delay_s=0.15, rcvbuf=1)
        assert status == 200
        assert events[-1]["event"] == "end"
        assert events[-1]["data"]["status"] == "slow_consumer"
        assert fd.driver.sheds >= 1
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not router.idle:
            time.sleep(0.05)
        assert router.idle, "shed request did not decode to completion"
    finally:
        fd.close()
