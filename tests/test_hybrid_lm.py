"""The Mamba-2 + LatentMoE + grouped-query hybrid (models/hybrid_lm.py)
against the plain reference (perf/reference/nemotron_h.py), at a small size
on the CPU: the full forward, prefill then decode through `PagedEngine`
(two kinds of cache in one manager), the kernels in interpret mode, a
chip's share of the experts, and what the engine refuses."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import nemotron_toy  # noqa: E402
from ddp_practice_tpu.ops import moe, ssm  # noqa: E402
from ddp_practice_tpu.serve.engine import (  # noqa: E402
    EngineConfig,
    PagedEngine,
)
from perf.reference import nemotron_h as reference  # noqa: E402

CFG = nemotron_toy.config()
# float32 program against a float32 reference at the highest precision:
# what is left is the order of sums (chunked scan against sequential,
# tiles against a loop over experts)
TOL = 2e-4


@pytest.fixture(scope="module")
def toy():
    return nemotron_toy.model_and_params(CFG)


@jax.jit
def _ref_forward(params, tokens):
    with jax.default_matmul_precision("highest"):
        return reference.forward(params, tokens, CFG)


def ref_logits(params, seq):
    """The reference's logits over `seq`, through ONE compiled width (right
    padding is invisible to a causal model)."""
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(_ref_forward(params, jnp.asarray(tokens)))[0, :len(seq)]


@pytest.fixture(scope="module")
def engine(toy):
    """One engine for the tests that only admit, decode and release: its
    four programs compile once."""
    return make_engine(*toy)


def make_engine(model, params, **kw):
    opts = dict(max_slots=3, prompt_buckets=(8, 16, 32), block_size=8,
                decode_burst=1, max_blocks_per_slot=12, temperature=0.0)
    opts.update(kw)
    return PagedEngine(model, params, EngineConfig(**opts))


def decode(engine, slot, steps):
    """(logits before each token and after the last, tokens) of `steps`
    single-token bursts of `slot`."""
    logits, toks = [np.asarray(engine._last_logits[slot])], []
    for _ in range(steps):
        toks.append(int(engine.step_burst()[0, slot]))
        logits.append(np.asarray(engine._last_logits[slot]))
    return np.stack(logits), toks


def test_full_forward_matches_the_reference(toy):
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(_ref_forward(params, tokens))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("prompt_len", [5, 8, 13, 16, 30])
def test_prefill_then_decode_matches_the_reference(toy, engine, prompt_len):
    """A left-padded prompt of every bucket (full and partial), then 20
    tokens through the pages and the state pool: LOGITS against one full
    forward of the reference over prompt + tokens."""
    _, params = toy
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(1, 96, prompt_len).tolist()
    slot = engine.admit(prompt, max_positions=24)
    got, toks = decode(engine, slot, 20)
    engine.release(slot)
    want = ref_logits(params, prompt + toks)[prompt_len - 1:prompt_len + 20]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_a_state_outlives_the_toy_weights_prompt(toy):
    """The weights' point: with dt_bias near -4 the first prompt token
    still moves the logits 30 tokens on (a state that was dropped or reset
    at admission would pass every test above only if it did not)."""
    _, params = toy
    rng = np.random.default_rng(0)
    seq = rng.integers(1, 96, 40).tolist()
    other = [(seq[0] + 1) % 96] + seq[1:]
    a, b = ref_logits(params, seq)[-1], ref_logits(params, other)[-1]
    assert np.abs(a - b).max() > 100 * TOL


def test_chunked_scan_matches_the_recurrence_across_chunks():
    """37 positions in chunks of 16 (two boundaries and a partial chunk),
    from a state that is not zero, with masked positions (dt = 0)."""
    k = jax.random.split(jax.random.PRNGKey(1), 7)
    b, l, h, p, g, n = 2, 37, 8, 8, 2, 16
    x = jax.random.normal(k[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, l, h)) - 2)
    dt = dt.at[0, :5].set(0.0)
    a = -jnp.exp(0.3 * jax.random.normal(k[2], (h,)))
    bm = jax.random.normal(k[3], (b, l, g, n))
    cm = jax.random.normal(k[4], (b, l, g, n))
    d = jax.random.normal(k[5], (h,))
    h0 = jax.random.normal(k[6], (b, h, p, n))
    y1, f1 = jax.jit(ssm.ssm_scan, static_argnames="chunk")(
        x, dt, a, bm, cm, d, h0, chunk=16)
    y2, f2 = jax.jit(ssm.ssm_scan_sequential)(x, dt, a, bm, cm, d, h0)
    np.testing.assert_allclose(y1, y2, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(f1, f2, atol=2e-5, rtol=2e-5)
    # masked positions moved nothing: the state after them is h0
    _, f5 = ssm.ssm_scan(x[:1, :5], dt[:1, :5], a, bm[:1, :5], cm[:1, :5],
                         d, h0[:1], chunk=16)
    np.testing.assert_array_equal(f5, h0[:1])


def test_ssm_step_kernel_matches_the_plain_step():
    k = jax.random.split(jax.random.PRNGKey(2), 7)
    b, h, p, g, n = 3, 16, 64, 2, 128
    args = (jax.random.normal(k[0], (b, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, h))),
            -jnp.exp(0.3 * jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, g, n)),
            jax.random.normal(k[4], (b, g, n)),
            jax.random.normal(k[5], (h,)),
            jax.random.normal(k[6], (b, h, p, n)))
    y1, s1 = ssm.ssm_step_kernel(*args)
    y2, s2 = ssm.ssm_step_reference(*args)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(s1, s2, atol=1e-5, rtol=1e-5)


def test_a_released_slot_carries_nothing_over(toy):
    """Release leaves the state row as it was; the next owner's admission
    overwrites it: its logits are those of a fresh sequence."""
    model, params = toy
    engine = make_engine(model, params, max_slots=1, prompt_buckets=(16,))
    rng = np.random.default_rng(3)
    first = rng.integers(1, 96, 14).tolist()
    slot = engine.admit(first, max_positions=12)
    decode(engine, slot, 10)
    engine.release(slot)
    second = rng.integers(1, 96, 6).tolist()
    assert engine.admit(second, max_positions=12) == slot
    got, toks = decode(engine, slot, 10)
    want = ref_logits(params, second + toks)[5:16]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_preemption_and_readmission_give_the_same_logits(toy, engine):
    """A preempted slot of such a model is re-admitted with prompt + tokens
    so far (the scheduler's readmission path): the state is rebuilt by the
    prefill, and decoding goes on as if nothing had happened."""
    _, params = toy
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 96, 9).tolist()
    slot = engine.admit(prompt, max_positions=30)
    _, toks = decode(engine, slot, 6)
    engine.preempt(slot)
    assert engine.take_preempted() == [slot]
    slot = engine.admit(prompt + toks, max_positions=20)
    got, more = decode(engine, slot, 8)
    engine.release(slot)
    want = ref_logits(params, prompt + toks + more)[14:23]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_scheduler_serves_the_model_through_the_normal_path(toy, engine):
    """create_model -> PagedEngine -> Scheduler: the same entry points as
    lm_base. Every served token is the reference's own best, or within the
    tolerance of it."""
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler

    _, params = toy
    sched = Scheduler(engine, max_queue=16)
    rng = np.random.default_rng(6)
    prompts = {i: rng.integers(1, 96, n).tolist()
               for i, n in enumerate([3, 11, 20, 7, 29])}
    for rid, prompt in prompts.items():
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=9,
                             seed=rid))
    done = {}
    for _ in range(200):
        for c in sched.step():
            done[c.rid] = c
        if len(done) == len(prompts):
            break
    assert sorted(done) == sorted(prompts)
    for rid, c in done.items():
        assert c.status == "length" and len(c.tokens) == 9
        logits = ref_logits(params, prompts[rid] + list(c.tokens))
        rows = logits[len(prompts[rid]) - 1:len(prompts[rid]) + 8]
        gap = rows.max(-1) - rows[np.arange(9), np.asarray(c.tokens)]
        assert gap.max() <= TOL


@pytest.mark.parametrize("option, value", [
    ("prefix_cache", True), ("prefill_chunk", 8), ("spec_decode", True)])
def test_engine_refuses_what_needs_a_state_snapshot(toy, option, value):
    model, params = toy
    extra = {"prefix_cache": True} if option == "prefill_chunk" else {}
    with pytest.raises(ValueError, match="refused for a model with "
                                         "recurrent state"):
        make_engine(model, params, **{option: value}, **extra)


def test_fork_refuses_recurrent_state(engine):
    slot = engine.admit([1, 2, 3], max_positions=4)
    with pytest.raises(ValueError, match="fork is refused"):
        engine.fork(slot)
    engine.release(slot)


def test_chunks_over_the_state_give_the_logits_of_one_whole_prefill(toy):
    """`prefill_chunk` without `prefix_cache`: a prompt's chunks run in order
    through the slot's own table, each from the state the one before left
    (the last one right-padded, which the scans do not advance over), and
    the slot then decodes as after one whole prefill."""
    model, params = toy
    rng = np.random.default_rng(11)
    seq = rng.integers(1, 96, 29).tolist()
    chunked = make_engine(model, params, prefill_chunk=8)
    assert chunked.radix is None
    slot = chunked.admit(seq, max_positions=8)
    chunks = 0
    while chunked.is_prefilling(slot):
        chunked.prefill_step(slot)
        chunks += 1
    assert chunks == 4 and chunked.context_len(slot) == 29
    got, toks = decode(chunked, slot, 4)
    whole = make_engine(model, params)
    want, same = decode(whole, whole.admit(seq, max_positions=8), 4)
    assert toks == same
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_state_pool_is_a_slot_and_pages_are_the_kv_heads_wide(engine):
    shapes = {"/".join(str(k.key) for k in path): a.shape for path, a in
              jax.tree_util.tree_flatten_with_path(engine._cache)[0]}
    assert shapes["mamba0/ssm_state"] == (3, 8, 8, 16)
    assert shapes["mamba0/conv_state"] == (3, 3, 64 + 2 * 2 * 16)
    assert shapes["attn3/cached_key"] == (1 + 3 * 12, 8, 2 * 16)
    assert shapes["moe1/moe_stats"] == (3,)
    assert shapes["moe1/moe_rows"] == (2,)
    state = 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4) * 3   # three Mamba layers
    assert engine.ssm_state_bytes == state


def test_decode_burst_span_and_counters_say_what_the_experts_saw(toy):
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler
    from ddp_practice_tpu.utils.trace import TraceRecorder

    model, params = toy
    engine = make_engine(model, params, decode_burst=4,
                         prompt_buckets=(8,))
    tracer = TraceRecorder(max_events=1 << 12)
    engine.set_tracer(tracer)
    metrics = ServeMetrics()
    sched = Scheduler(engine, max_queue=8, tracer=tracer, metrics=metrics)
    sched.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=8, seed=0))
    for _ in range(6):
        sched.step()
    bursts = [e for e in tracer.to_chrome_trace()["traceEvents"]
              if e.get("name") == "decode_burst" and "args" in e]
    assert bursts
    picks = 3 * 3 * 3 * 4      # slots x top-k x expert layers x steps
    for e in bursts:
        a = e["args"]
        assert 0 < a["expert_rows"] <= picks
        assert 0 < a["experts_touched"] <= 4 * 3 * 4
        assert 0 < a["expert_rows_max"] <= 3
    last = bursts[-1]["args"]
    assert engine.last_burst_experts == (
        last["expert_rows"], last["experts_touched"],
        last["expert_rows_max"])
    snap = metrics.registry.snapshot()
    assert snap["moe_rows_routed_total"] == picks * len(bursts)
    assert snap["moe_rows_held_total"] == sum(
        e["args"]["expert_rows"] for e in bursts)
    assert snap["ssm_state_bytes"] == engine.ssm_state_bytes


def test_rows_moved_are_the_held_picks_rounded_to_tiles(toy):
    """`expert_rows_moved` / `expert_rows_layout` on `decode_burst` and the
    counters beside `moe_rows_held_total` (PR 39), on a routing known in
    advance: a selection bias that sends every token to held experts 0, 1
    and 2. A step's three slots then fill three tiles of 16 rows a layer and
    sum 9 rows out of them, where the layout that covers any routing holds
    (1 + 4) tiles and 9 picks; the admission's 8 positions fill the same
    three tiles and sum 24 rows, and are counted with the burst after them."""
    from ddp_practice_tpu.serve.metrics import ServeMetrics
    from ddp_practice_tpu.serve.scheduler import Request, Scheduler
    from ddp_practice_tpu.utils.trace import TraceRecorder

    model, params = toy
    bias = jnp.zeros((16,)).at[:3].set(10.0)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: bias.astype(a.dtype)
        if path[-1].key == "e_score_correction_bias" else a, params)
    engine = make_engine(model, params, decode_burst=4, prompt_buckets=(8,))
    tracer = TraceRecorder(max_events=1 << 12)
    engine.set_tracer(tracer)
    metrics = ServeMetrics()
    sched = Scheduler(engine, max_queue=8, tracer=tracer, metrics=metrics)
    sched.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=8, seed=0))
    for _ in range(6):
        sched.step()
    bursts = [e["args"] for e in tracer.to_chrome_trace()["traceEvents"]
              if e.get("name") == "decode_burst" and "args" in e]
    assert len(bursts) >= 2
    layers, steps, slots, k, tile, held = 3, 4, 3, 3, 16, 4
    for a in bursts:
        assert a["expert_rows"] == layers * steps * slots * k
        assert a["expert_rows_moved"] == layers * steps * (
            3 * tile + slots * k)
        assert a["expert_rows_layout"] == layers * steps * (
            (1 + held) * tile + slots * k)
        assert a["expert_rows_moved"] <= a["expert_rows_layout"]
    # the one admission (bucket 8) is read back with the first burst
    assert bursts[0]["prefill_rows_moved"] == layers * (3 * tile + 8 * k)
    assert bursts[0]["prefill_rows_layout"] == layers * (
        (2 + held) * tile + 8 * k)
    assert all(a["prefill_rows_moved"] == 0 for a in bursts[1:])
    snap = metrics.registry.snapshot()
    assert snap["moe_rows_moved_total"] == sum(
        a["expert_rows_moved"] + a["prefill_rows_moved"] for a in bursts)
    assert snap["moe_rows_layout_total"] == sum(
        a["expert_rows_layout"] + a["prefill_rows_layout"] for a in bursts)
    assert 0 < snap["moe_rows_moved_total"] < snap["moe_rows_layout_total"]


GROUPED = {
    "ragged_left_padded": ([3, 17, 100, 191, 64], [0, 5, 33, 0, 64]),
    "no_start": ([0, 15, 16, 127, 128], None),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
@pytest.mark.parametrize("heads, kv_heads", [(16, 2), (32, 2), (8, 1)])
def test_paged_walk_with_grouped_queries_matches_reference(
        case, heads, kv_heads):
    from ddp_practice_tpu.ops.decode_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    lengths, start = GROUPED[case]
    b, d, bs, mb = len(lengths), 128, 16, 12
    nb = 1 + b * mb
    rng = np.random.default_rng(heads)
    q = jnp.asarray(rng.normal(size=(b, 1, heads * d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kv_heads * d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kv_heads * d)), jnp.float32)
    pt = jnp.asarray(rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1,
                     jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    start = None if start is None else jnp.asarray(start, jnp.int32)
    kw = dict(n_heads=heads, n_kv_heads=kv_heads)
    want = paged_attention_reference(q, kp, vp, pt, lengths, start, **kw)
    got = paged_decode_attention(q, kp, vp, pt, lengths, start, **kw,
                                 impl="kernel")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_grouped_reference_reads_the_group_s_own_kv_head():
    """Query head i reads KV head i // group: against attention written
    out a head at a time."""
    from ddp_practice_tpu.ops.decode_attention import (
        paged_attention_reference,
    )

    rng = np.random.default_rng(9)
    heads, kvh, d, bs = 4, 2, 8, 4
    q = rng.normal(size=(1, 1, heads * d)).astype(np.float32)
    kp = rng.normal(size=(3, bs, kvh * d)).astype(np.float32)
    vp = rng.normal(size=(3, bs, kvh * d)).astype(np.float32)
    got = np.asarray(paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray([[1, 2]], jnp.int32), jnp.asarray([5], jnp.int32),
        n_heads=heads, n_kv_heads=kvh))[0, 0]
    keys = np.concatenate([kp[1], kp[2]])[:6].reshape(6, kvh, d)
    vals = np.concatenate([vp[1], vp[2]])[:6].reshape(6, kvh, d)
    for i in range(heads):
        s = keys[:, i // 2] @ q[0, 0, i * d:(i + 1) * d] / np.sqrt(d)
        w = np.exp(s - s.max())
        want = (w / w.sum()) @ vals[:, i // 2]
        np.testing.assert_allclose(got[i * d:(i + 1) * d], want, atol=1e-5)


@pytest.mark.parametrize("offset", [0, 4, 12])
def test_moe_tiles_hold_each_held_pick_once(offset):
    """The layout and the kernel (interpret mode) against a loop over
    picks written out: rows of absent experts are nowhere, every held pick
    is in exactly one row of its own expert's tile."""
    k = jax.random.split(jax.random.PRNGKey(offset), 4)
    n, top, experts, held, lat, f = 40, 3, 16, 4, 32, 48
    choices = jax.random.randint(k[0], (n, top), 0, experts)
    lay = moe.held_tile_layout(choices, offset=offset, held=held, tile=16)
    u = jax.random.normal(k[1], (n, lat))
    w1 = 0.2 * jax.random.normal(k[2], (held, lat, f))
    w2 = 0.2 * jax.random.normal(k[3], (held, f, lat))
    rows = jnp.where(lay["row_valid"][:, None], u[lay["row_token"]], 0)
    outs = [tiles(rows, w1, w2, lay["tile_expert"], lay["tiles_used"],
                  tile=16)
            for tiles in (moe.expert_mlp_tiles_kernel,
                          moe.expert_mlp_tiles_reference)]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)
    got = jnp.einsum("nk,nkd->nd", lay["pick_held"].astype(jnp.float32),
                     outs[0][lay["pick_row"]])
    want = np.zeros((n, lat), np.float32)
    for i, picks in enumerate(np.asarray(choices)):
        for e in picks - offset:
            if 0 <= e < held:
                hid = np.maximum(np.asarray(u[i]) @ np.asarray(w1[e]), 0) ** 2
                want[i] += hid @ np.asarray(w2[e])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    counts = np.bincount(
        np.asarray(choices).ravel() - offset + experts,
        minlength=3 * experts)[experts:experts + held]
    np.testing.assert_array_equal(lay["counts"], counts)
    assert int(lay["row_valid"].sum()) == counts.sum()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that the four held ranges
    give, with the shared expert counted once, add up to the uncut
    reference's expert layer (same router, same picks over all 16)."""
    from ddp_practice_tpu.ops.moe import LatentMoE

    whole_cfg = nemotron_toy.config(n_routed_experts_held=16,
                                    hybrid_override_pattern="E",
                                    num_hidden_layers=1)
    _, whole = nemotron_toy.model_and_params(whole_cfg, seed=3)
    p = whole["moe0"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 19, 64))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.moe(x, p, whole_cfg))
        shared = np.asarray(reference.shared_expert(
            x.reshape(-1, 64), p)).reshape(2, 19, 64)
        total = np.zeros_like(want)
        for off in (0, 4, 8, 12):
            layer = LatentMoE(16, 3, 32, 48, 96, experts_held=4,
                              expert_offset=off, routed_scaling=2.5)
            share = dict(p, expert_w1=p["expert_w1"][off:off + 4],
                         expert_w2=p["expert_w2"][off:off + 4])
            out = np.asarray(layer.apply({"params": share}, x))
            # the program's share against the reference's own share
            cut = nemotron_toy.config(expert_offset=off)
            np.testing.assert_allclose(
                out, np.asarray(reference.moe(x, share, cut)),
                atol=TOL, rtol=TOL)
            total += out - shared
    assert np.abs(want - shared).max() > 0.1     # the experts do something
    np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("name", ["nemotron_h", "jamba", "qwen3_next"])
def test_the_stream_scalars_default_to_the_layouts_as_they_were(name):
    """`embed_scale`, `residual_scale` and `head_scale` (MiniCPM-SALA's muP
    scalars) default to 1 and then add no op: each of the three older
    layouts traces to the SAME program, forward and paged decode step,
    whether they are left out or spelled out, so its logits are what they
    were to the bit; a scalar that is not 1 is another program."""
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.serve.kv_pages import make_paged_cache

    tokens = jnp.zeros((2, 16), jnp.int32)

    def programs(**kw):
        model = create_model(name, **kw)
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), tokens[:, :8]))["params"]
        pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 2))
        step = lambda p, c: decode_apply(
            model, p, c, tokens[:, :1],
            page_table=jnp.zeros((2, 4), jnp.int32),
            kv_lengths=jnp.zeros((2,), jnp.int32))
        return (str(jax.make_jaxpr(
            lambda p: model.apply({"params": p}, tokens))(params)),
            str(jax.make_jaxpr(step)(params, pool)))

    from ddp_practice_tpu.inference import decode_apply

    plain = programs()
    spelled = programs(embed_scale=1.0, residual_scale=1.0, head_scale=1.0)
    assert plain == spelled
    for option in ("embed_scale", "residual_scale", "head_scale"):
        assert programs(**{option: 0.5})[0] != plain[0], option
