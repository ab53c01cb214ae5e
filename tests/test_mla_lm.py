"""The latent-attention + gated-experts decoder (models/mla_lm.py) against
the plain reference (perf/reference/deepseek_v3.py), at a small size on the
CPU: the full forward; prefill then decode through `PagedEngine` on every
admission path (LOGITS, not tokens); absorbed against un-absorbed attention;
the two kernels in interpret mode; preemption, copy-on-write and the sharing
of a context that is still being prefilled."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import deepseek_toy  # noqa: E402
from ddp_practice_tpu.inference import decode_apply  # noqa: E402
from ddp_practice_tpu.ops import decode_attention as da, moe  # noqa: E402
from ddp_practice_tpu.serve.engine import (  # noqa: E402
    EngineConfig,
    PagedEngine,
)
from ddp_practice_tpu.serve.kv_pages import make_paged_cache  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402
from perf.reference import deepseek_v3 as reference  # noqa: E402

CFG = deepseek_toy.config()
# float32 program against a float32 reference at the highest precision:
# what is left is the order of sums (absorbed against expanded attention,
# tiles against windows of sorted picks)
TOL = 2e-4
PROMPT = [int(t) for t in jax.random.randint(
    jax.random.PRNGKey(5), (37,), 0, CFG["vocab_size"])]


@pytest.fixture(scope="module")
def toy():
    return deepseek_toy.model_and_params(CFG, seed=4)


@jax.jit
def _ref_forward(params, tokens):
    with jax.default_matmul_precision("highest"):
        return reference.forward(params, tokens, CFG)


def engine_of(toy, **kw):
    kw = dict(dict(max_slots=3, prompt_buckets=(8, 16, 32, 64), block_size=8,
                   max_blocks_per_slot=10, decode_burst=1), **kw)
    return PagedEngine(*toy, EngineConfig(**kw))


def admitted(eng, prompt, **kw):
    slot = eng.admit(prompt, **kw)
    while eng.is_prefilling(slot):
        eng.prefill_step(slot)
    return slot


def served_logits(eng, slot, steps):
    """(tokens the slot emitted, the logits each was the argmax of)."""
    toks, rows = [], []
    for _ in range(steps):
        rows.append(np.asarray(eng._last_logits[slot], np.float32))
        toks.append(int(eng.step_burst()[0, slot]))
    return toks, np.stack(rows)


def test_full_forward_agrees_with_the_reference(toy):
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, tokens))
    np.testing.assert_allclose(got, np.asarray(_ref_forward(params, tokens)),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("path", ["scratch_scatter", "prefix_cold",
                                  "prefix_hit", "chunked",
                                  "prefix_hit_span16", "chunked_span16"])
def test_prefill_then_decode_through_pages_gives_the_references_logits(
        toy, path, monkeypatch):
    """Every admission path, then 6 absorbed decode steps: each step's
    logits against the reference's full forward over prompt + served.
    `_span16`: the several-token path folds the span 16 positions at a
    time (its running softmax over 3 to 5 blocks, as 1,024 at a time
    over an 8,960-position table)."""
    from ddp_practice_tpu.models import hybrid_lm

    if path.endswith("_span16"):
        monkeypatch.setattr(hybrid_lm, "_SPAN_TOKENS", 16)
        path = path[:-len("_span16")]
    kw = {"scratch_scatter": {}, "prefix_cold": dict(prefix_cache=True),
          "prefix_hit": dict(prefix_cache=True),
          "chunked": dict(prefix_cache=True, prefill_chunk=16)}[path]
    with jax.default_matmul_precision("highest"):
        eng = engine_of(toy, **kw)
        if path == "prefix_hit":
            # another request leaves the first 24 tokens' blocks behind
            eng.release(admitted(eng, PROMPT[:24] + [1, 2, 3],
                                 max_positions=8))
        slot = admitted(eng, PROMPT, max_positions=8)
        assert eng.last_prefix_hit == {
            "scratch_scatter": None, "prefix_hit": 24}.get(path, 0)
        toks, got = served_logits(eng, slot, 6)
    seq = jnp.asarray([PROMPT + toks])
    want = np.asarray(_ref_forward(toy[1], seq))[0, len(PROMPT) - 1:-1]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert toks == [int(t) for t in want.argmax(-1)]


def test_absorbed_step_agrees_with_the_expanded_one(toy):
    """The same weights and pages: token n alone (absorbed, the kernel's
    reference path) against tokens n-1, n as a suffix call (K and V
    expanded from the rows)."""
    model, params = toy
    pool = make_paged_cache(model, 12, 8)
    table = jnp.asarray([[3, 5, 7, 9, 11, 2]], jnp.int32)
    toks = jnp.asarray([PROMPT])
    # jitted: one trace a width, not the model op by op
    step = jax.jit(lambda pool, toks, at: decode_apply(
        model, params, pool, toks, page_table=table,
        kv_lengths=jnp.full((1,), at, jnp.int32)))
    with jax.default_matmul_precision("highest"):
        pool, _ = step(pool, toks[:, :30], 0)
        _, two = step(pool, toks[:, 30:32], 30)
        pool, _ = step(pool, toks[:, 30:31], 30)
        _, one = step(pool, toks[:, 31:32], 31)
    np.testing.assert_allclose(np.asarray(one[0, 0]), np.asarray(two[0, 1]),
                               atol=TOL, rtol=TOL)


# ------------------------------------------------------------ the kernels
def _mla_case(dtype=jnp.float32):
    """Ragged lengths, a start inside a page, a slot of one page, and a
    retired slot (table row of zeros, length pinned past its table)."""
    bs, mb, w, vl, heads = 8, 40, 256, 128, 8
    lengths = [5, 293, 77, 8, 4000]
    start = [0, 3, 20, 0, 0]
    b = len(lengths)
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(1 + b * mb, bs, w)), dtype)
    q = jnp.asarray(rng.normal(size=(b, heads, w)), dtype)
    table = rng.permutation(np.arange(1, 1 + b * mb)).reshape(b, mb)
    table[-1] = 0
    return (q, pool, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(start, jnp.int32),
            dict(v_lanes=vl, sm_scale=0.07))


@pytest.mark.parametrize("chunk", [64, 512])
def test_paged_decode_mla_matches_the_gather_reference(chunk, monkeypatch):
    monkeypatch.setattr(da, "_MLA_CHUNK_TOKENS", chunk)
    *args, kw = _mla_case()
    got = da.paged_decode_mla(*args, impl="kernel", **kw)
    want = da.paged_decode_mla(*args, impl="reference", **kw)
    live = slice(0, 4)   # the retired slot's row is garbage on both sides
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_paged_decode_mla_waits_for_what_it_reads(monkeypatch):
    """Under the TPU interpreter that lands a copy's bytes only at its
    wait and watches for races (tests/test_decode_attention.py)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pallas_call = pl.pallas_call

    def on_wait(*args, interpret, **kw):
        assert interpret is True
        return pallas_call(*args, **kw, interpret=pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))

    monkeypatch.setattr(pl, "pallas_call", on_wait)
    test_paged_decode_mla_matches_the_gather_reference(64, monkeypatch)
    assert not interpret_pallas_call.races.races_found


def test_paged_decode_mla_reads_live_pages_alone():
    q, pool, table, lengths, start, kw = _mla_case()
    # one trace of the interpreted kernel for the three pools
    walk = jax.jit(lambda pool: da.paged_decode_mla(
        q, pool, table, lengths, start, impl="kernel", **kw))
    want = np.asarray(walk(pool))
    t = np.asarray(table)
    dead = np.concatenate([t[0, 1:], t[1, 37:], t[2, :2], t[2, 10:], [0]])
    got = np.asarray(walk(pool.at[dead].set(jnp.nan)))
    np.testing.assert_array_equal(got[:4], want[:4])
    hit = np.asarray(walk(pool.at[t[1, 36]].set(jnp.nan)))
    assert np.isnan(hit[1]).all() and not np.isnan(hit[0]).any()


def test_glu_tile_kernel_matches_a_loop_over_picks():
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    n, top, experts, d, f = 40, 3, 8, 32, 48
    choices = jax.random.randint(k[0], (n, top), 0, experts)
    lay = moe.held_tile_layout(choices, offset=0, held=experts, tile=16)
    x = jax.random.normal(k[1], (n, d))
    wg, wu = (0.2 * jax.random.normal(kk, (experts, d, f)) for kk in k[2:4])
    wd = 0.2 * jax.random.normal(k[4], (experts, f, d))
    rows = jnp.where(lay["row_valid"][:, None], x[lay["row_token"]], 0)
    outs = [tiles(rows, wg, wu, wd, lay["tile_expert"], lay["tiles_used"],
                  tile=16)
            for tiles in (moe.expert_glu_tiles_kernel,
                          moe.expert_glu_tiles_reference)]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)
    got = outs[0][lay["pick_row"]].sum(1)
    want = np.zeros((n, d), np.float32)
    silu = lambda a: a / (1.0 + np.exp(-a))
    for i, picks in enumerate(np.asarray(choices)):
        for e in picks:
            xi = np.asarray(x[i])
            want[i] += (silu(xi @ np.asarray(wg[e])) * (xi @ np.asarray(
                wu[e]))) @ np.asarray(wd[e])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                out.extend(_pallas_names(inner))
    return out


def test_a_decode_step_is_one_mla_walk_and_one_glu_kernel_a_layer(
        monkeypatch):
    """The names the benchmark's readers sum by, and what the pool keeps:
    ONE 640-lane row a token and layer, no per-head K or V."""
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.utils import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    model = create_model(
        "deepseek_v3", vocab_size=256, hidden_dim=128, num_layers=3,
        num_heads=8, nope_dim=128, rope_dim=64, v_dim=128, latent_dim=512,
        mlp_dim=256, num_experts=8, experts_held=8, top_k=2, expert_dim=128,
        shared_dim=256)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 16))
    shapes = {jax.tree_util.keystr(p): a.shape for p, a
              in jax.tree_util.tree_flatten_with_path(pool)[0]}
    rows = {k: v for k, v in shapes.items() if "cached_latent" in k}
    assert len(rows) == 3 and set(rows.values()) == {(9, 16, 640)}
    assert not any("cached_key" in k or "cached_value" in k for k in shapes)

    def step(params, pool, toks, table, lengths):
        return decode_apply(model, params, pool, toks, page_table=table,
                            kv_lengths=lengths)

    names = _pallas_names(jax.make_jaxpr(step)(
        params, pool, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros((4, 2), jnp.int32), jnp.zeros((4,), jnp.int32)).jaxpr)
    # an expert layer is three kernels: its rows in, its experts, its sums
    assert sorted(names) == ["moe_gmm_glu"] * 2 + ["moe_rows_fill"] * 2 \
        + ["moe_rows_sum"] * 2 + ["paged_decode_mla"] * 3


# ------------------------------------------------------------- the engine
def test_a_preempted_and_readmitted_request_gives_the_same_logits(toy):
    with jax.default_matmul_precision("highest"):
        eng = engine_of(toy, prefix_cache=True)
        slot = admitted(eng, PROMPT, max_positions=8)
        toks, whole = served_logits(eng, slot, 6)
        eng.release(slot)
        slot = admitted(eng, PROMPT, max_positions=8)
        first, _ = served_logits(eng, slot, 3)
        eng.preempt(slot)
        assert eng.take_preempted() == [slot]
        slot = admitted(eng, PROMPT + first, max_positions=8)
        rest, again = served_logits(eng, slot, 3)
    assert first + rest == toks
    np.testing.assert_allclose(again, whole[3:], atol=TOL, rtol=TOL)


def test_copy_on_write_leaves_the_sharers_latent_page_untouched(toy):
    """A fork shares its parent's partly written tail page; they sample
    apart, and the first to write takes a private copy: each page keeps
    the three shared rows and only its own slot's new ones."""
    eng = engine_of(toy, decode_burst=2, prefix_cache=True, temperature=1.0)
    slot = admitted(eng, PROMPT[:19], max_positions=8, seed=1)
    tail = int(eng._pt[slot, 2])              # positions 16..23, 3 written
    child = eng.fork(slot, seed=2)
    assert int(eng._pt[child, 2]) == tail and eng.blocks.refcount(tail) == 2
    leaf = lambda: np.asarray(eng._cache["attn0"]["cached_latent"])
    before = leaf()[tail].copy()
    toks = eng.step_burst()
    assert (toks[:, slot] != toks[:, child]).any()
    pages = [int(eng._pt[s, 2]) for s in (slot, child)]
    assert sorted(pages)[0] != sorted(pages)[1] and tail in pages
    assert all(eng.blocks.refcount(b) == 1 for b in pages)
    after = leaf()
    for b in pages:
        np.testing.assert_array_equal(after[b][:3], before[:3])
        assert np.abs(after[b][3:5]).max() > 0
    assert np.abs(after[pages[0]][3:5] - after[pages[1]][3:5]).max() > 0


def _events(tracer, name):
    return [e for e in tracer.to_chrome_trace()["traceEvents"]
            if e.get("name") == name and "args" in e]


def test_a_context_still_being_prefilled_is_shared_chunk_by_chunk(toy):
    """Two requests over one 48-token context, admitted together under
    chunked prefill: each chunk's full blocks are published as the chunk
    ends and the other request adopts them, so the context is prefilled
    ONCE, and both serve what an engine of their own would."""
    context = (PROMPT + PROMPT)[:48]
    asks = [context + [7, 8, 9], context + [11, 12]]
    tracer = TraceRecorder()
    with jax.default_matmul_precision("highest"):
        eng = engine_of(toy, prefix_cache=True, prefill_chunk=16)
        eng.set_tracer(tracer)
        slots = [eng.admit(p, max_positions=8, trace_id=f"r{i}")
                 for i, p in enumerate(asks)]
        while any(eng.is_prefilling(s) for s in slots):
            for s in slots:
                if eng.is_prefilling(s):
                    eng.prefill_step(s)
        got = [np.asarray(eng._last_logits[s]) for s in slots]
        alone = []
        for p in asks:
            solo = engine_of(toy, prefix_cache=True)
            slot = admitted(solo, p, max_positions=8)
            alone.append(np.asarray(solo._last_logits[slot]))
    np.testing.assert_allclose(got, alone, atol=TOL, rtol=TOL)
    chunks = [e["args"] for e in _events(tracer, "prefill_chunk")]
    # 3 chunks of the context between them (not 6) and each one's own tail
    assert sorted(a["pos0"] for a in chunks) == [0, 16, 32, 48, 48]
    assert {a["chunk"] for a in chunks} == {0, 1, 2, 3}
    assert all(a["prefix_hit"] == 0 for a in chunks)
    assert eng.radix.hit_tokens == 48 and eng.radix.miss_tokens == 51 + 50 - 48
    shared = [int(b) for b in eng._pt[slots[0], :6]]
    assert shared == [int(b) for b in eng._pt[slots[1], :6]]
    assert all(eng.blocks.refcount(b) == 3 for b in shared)  # 2 slots + tree


def test_the_burst_span_carries_latent_pages_and_expert_counts(toy):
    tracer = TraceRecorder()
    eng = engine_of(toy, decode_burst=4)
    eng.set_tracer(tracer)
    admitted(eng, PROMPT, max_positions=8)
    eng.step_burst()
    (burst,) = _events(tracer, "decode_burst")
    a = burst["args"]
    # left-padded to the 64 bucket: 27 pads, so pages 3..8 over 4 steps
    assert a["pages_walked"] == 4 * 6 and "latent_pages_walked" not in a
    # 2 expert layers x 4 steps, 3 slots' rows x top-3 each (retired too)
    assert a["expert_rows"] == 2 * 4 * 3 * 3
    assert 0 < a["experts_touched"] <= 2 * 4 * 9 and a["expert_rows_max"] >= 1
    assert eng.last_burst_experts == (a["expert_rows"], a["experts_touched"],
                                      a["expert_rows_max"])
    # 3 layers x 31 blocks x 8 x 128 lanes (40 useful, padded) x float32
    assert eng.latent_cache_bytes == 3 * 31 * 8 * 128 * 4


def test_scheduler_serves_sessions_and_the_metrics_plane_counts_them(toy):
    """Through `Scheduler` with the prefix cache and chunks on: two turns
    of one context; the second hits what the first published. Gauge
    `latent_cache_bytes` and the prefix counters are in the snapshot."""
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler

    eng = engine_of(toy, prefix_cache=True, prefill_chunk=16, decode_burst=2)
    metrics = ServeMetrics()
    sched = Scheduler(eng, max_queue=8, metrics=metrics)
    turns = [PROMPT[:32] + [3, 4, 5], PROMPT[:32] + [3, 4, 5, 6, 7, 8, 9, 1]]
    for rid, prompt in enumerate(turns):
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=4,
                             seed=rid))
        for _ in range(8):
            sched.step()
    done = {c.rid: c for c in sched.completions}
    assert [done[i].status for i in (0, 1)] == ["length", "length"]
    assert done[1].flight["prefix_hit_tokens"] == 32
    snap = metrics.registry.snapshot()
    assert snap["latent_cache_bytes"] == eng.latent_cache_bytes > 0
    assert snap["prefix_cache_hit_tokens_total"] == 32
    assert snap["prefix_cache_miss_tokens_total"] == 35 + 40 - 32


def test_warm_engine_compiles_every_bucket_under_the_prefix_cache(toy):
    """A warm-up prompt left in the radix would be the next width's
    prefix, and that width's own bucket would compile inside the window."""
    from ddp_practice_tpu.serve.engine import warm_engine

    eng = engine_of(toy, prefix_cache=True, prefill_chunk=64)
    for w in eng.buckets:          # one call a width, as perf/ drives it
        warm_engine(eng, widths=[w])
    assert eng.compile_stats()["prefix_prefill_compiles"] == len(eng.buckets)
    assert len(eng.radix) == 0 and eng.blocks.num_used == 0
