"""The Jamba layout of `HybridLM` (Mamba-1 selective-scan mixers, one-KV-head
attention, a dense gated MLP a layer, tied head) against the plain reference
(perf/reference/jamba.py), at a small size on the CPU: the two kernels in
interpret mode against the sequential recurrence, the hand-off from a
prompt's scan to decode steps, prefill then decode through `PagedEngine`,
the published parameter count from shapes alone, and the tolerance a bf16
run meets and an e4m3 control fails."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import jamba_toy  # noqa: E402
import perf_toy  # noqa: E402
from ddp_practice_tpu.config import PrecisionPolicy  # noqa: E402
from ddp_practice_tpu.models import create_model  # noqa: E402
from ddp_practice_tpu.ops import ssm  # noqa: E402
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine  # noqa: E402
from ddp_practice_tpu.serve.metrics import ServeMetrics  # noqa: E402
from ddp_practice_tpu.serve.scheduler import Request, Scheduler  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402
from perf.families import jamba as family  # noqa: E402
from perf.reference import jamba as reference  # noqa: E402

CFG = jamba_toy.config()
PUBLISHED = perf_toy.load("perf/configs/jamba2_3b.json")
# float32 program against a float32 reference at the highest precision. One
# Mamba layer and its MLP agree to 2e-6 (the same sequential order of sums);
# the attention layer's other order of sums adds 5e-5 and the unit-scale toy
# weights amplify it through the sub-layers after (8e-5 at the worst logit
# here, 7e-4 over twice the depth; logits up to 4). A dropped or stale state
# reads 0.1 and more.
TOL = 5e-4
# the kernels against the plain recurrence: the same float32 products and
# sums in another order (a tree over the 16 states, not a chain)
KERNEL_TOL = 2e-5


@pytest.fixture(scope="module")
def toy():
    return jamba_toy.model_and_params(CFG)


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


def make_engine(model, params, **kw):
    opts = dict(max_slots=3, prompt_buckets=(8, 16, 32), block_size=8,
                decode_burst=1, max_blocks_per_slot=12, temperature=0.0)
    opts.update(kw)
    return PagedEngine(model, params, EngineConfig(**opts))


@pytest.fixture(scope="module")
def engine(toy):
    return make_engine(*toy)


def decode(engine, slot, steps):
    """(logits before each token and after the last, tokens) of `steps`
    single-token bursts of `slot`."""
    logits, toks = [np.asarray(engine._last_logits[slot])], []
    for _ in range(steps):
        toks.append(int(engine.step_burst()[0, slot]))
        logits.append(np.asarray(engine._last_logits[slot]))
    return np.stack(logits), toks


def scan_inputs(b, l, c, n, seed, h0_zero):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(k[0], (b, l, c))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, l, c)) - 1.0)
    a = -jnp.exp(0.3 * jax.random.normal(k[2], (c, n)))
    bm, cm = (jax.random.normal(k[i], (b, l, n)) for i in (3, 4))
    d = jax.random.normal(k[5], (c,))
    shape = ssm.sel_state_shape(b, c, n)
    h0 = jnp.zeros(shape) if h0_zero else jax.random.normal(k[6], shape)
    return u, dt, a, bm, cm, d, h0


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("h0_zero", [True, False])
@pytest.mark.parametrize("length", [37, 64])
def test_sel_scan_kernel_matches_the_sequential_recurrence(length, h0_zero):
    """Interpret mode, a chunk of 16: 37 positions is no multiple of it (the
    tail is padded with dt = 0), the first 5 of row 0 are left padding
    (dt = 0 there too), and the state starts at zero or not."""
    u, dt, a, bm, cm, d, h0 = scan_inputs(2, length, 256, 16, length, h0_zero)
    dt = dt.at[0, :5].set(0.0)
    want_y, want_h = ssm.sel_scan_reference(u, dt, a, bm, cm, d, h0)
    got_y, got_h = ssm.sel_scan_kernel(u, dt, a, bm, cm, d, h0, chunk=16)
    np.testing.assert_allclose(got_y, want_y, atol=KERNEL_TOL,
                               rtol=KERNEL_TOL)
    np.testing.assert_allclose(got_h, want_h, atol=KERNEL_TOL,
                               rtol=KERNEL_TOL)
    # left padding leaves the state alone: the 5 padded positions of row 0
    # give the state they were handed
    _, after_pad = ssm.sel_scan_kernel(
        u[:1, :5], dt[:1, :5], a, bm[:1, :5], cm[:1, :5], d, h0[:1])
    np.testing.assert_array_equal(after_pad, h0[:1])


@pytest.mark.parametrize("h0_zero", [True, False])
@pytest.mark.parametrize("slots", [3, 40])
def test_sel_step_kernel_matches_the_plain_step(slots, h0_zero):
    """3 slots are one grid cell; 40 are two cells of 20 (the largest
    divisor under the 32 a cell takes)."""
    u, dt, a, bm, cm, d, h0 = scan_inputs(slots, 1, 256, 16, slots, h0_zero)
    args = (u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, h0)
    want_y, want_h = ssm.sel_step_reference(*args)
    got_y, got_h = ssm.sel_step_kernel(*args)
    np.testing.assert_allclose(got_y, want_y, atol=KERNEL_TOL,
                               rtol=KERNEL_TOL)
    np.testing.assert_allclose(got_h, want_h, atol=KERNEL_TOL,
                               rtol=KERNEL_TOL)


@pytest.mark.parametrize("kernel", [False, True])
def test_scan_then_steps_equals_one_scan(kernel):
    """The hand-off of prefill to decode: a scan over 21 positions, then 9
    single steps from the state it left, against ONE scan over all 30."""
    scan = ssm.sel_scan_kernel if kernel else ssm.sel_scan_reference
    step = ssm.sel_step_kernel if kernel else ssm.sel_step_reference
    u, dt, a, bm, cm, d, h0 = scan_inputs(2, 30, 128, 16, 7, False)
    want_y, want_h = ssm.sel_scan_reference(u, dt, a, bm, cm, d, h0)
    ys, h = scan(u[:, :21], dt[:, :21], a, bm[:, :21], cm[:, :21], d, h0)
    ys = [ys]
    for t in range(21, 30):
        y, h = step(u[:, t], dt[:, t], a, bm[:, t], cm[:, t], d, h)
        ys.append(y[:, None])
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y,
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)
    np.testing.assert_allclose(h, want_h, atol=KERNEL_TOL, rtol=KERNEL_TOL)


def test_the_kernels_are_one_named_op_each():
    """`sel_scan` and `sel_step` are the `name=` of ONE `pallas_call` each:
    the names perf/layer_metrics/flood_sel_* sum device time by, and neither
    starts with `ssm_`, which `flood_ssm_dev_pct` sums."""
    u, dt, a, bm, cm, d, h0 = scan_inputs(2, 16, 128, 16, 3, True)
    for fn, args, name in (
            (ssm.sel_scan_kernel, (u, dt, a, bm, cm, d, h0), "sel_scan"),
            (ssm.sel_step_kernel,
             (u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, h0), "sel_step")):
        text = str(jax.make_jaxpr(fn)(*args))
        assert text.count("pallas_call") == 1, name
        assert f"name={name}" in text.replace(" ", ""), name


# ------------------------------------------------------------- the model
def test_full_forward_matches_the_reference(toy):
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(_ref_forward(params, tokens))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_the_tied_head_shares_the_embedding_leaf(toy):
    """No `lm_head` leaf: the logits are the final norm's output against
    the embedding itself, so scaling the embedding's row v scales logit v
    (through the head) whatever token came in."""
    model, params = toy
    assert "lm_head" not in params and "embedding" in params["tok_embed"]
    assert model.tie_embeddings and model.pattern == "SD*DSDSD"
    tokens = jnp.asarray([[3, 5, 7, 9]], jnp.int32)
    base = model.apply({"params": params}, tokens)
    emb = params["tok_embed"]["embedding"]
    scaled = dict(params, tok_embed={
        "embedding": emb.at[50].multiply(2.0)})   # a token nobody sent
    np.testing.assert_allclose(
        model.apply({"params": scaled}, tokens)[..., 50],
        2.0 * base[..., 50], rtol=1e-5)


def test_published_widths_hold_3_029_337_472_parameters():
    """Abstract init at the published widths (no memory): what
    perf/configs/jamba2_3b.json `deployment` and PERF.md state; 26 Mamba
    layers of 104,161,472, 2 attention layers of 76,682,240, the tied
    embedding and the final norm."""
    model = create_model(PUBLISHED["program_model"],
                         policy=PrecisionPolicy.bf16(),
                         **family.model_options(PUBLISHED))
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    sizes = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in abstract.items()}
    layer = lambda i: sizes[f"norm{2 * i}"] + sizes[f"norm{2 * i + 1}"] \
        + sizes.get(f"mamba{2 * i}", 0) + sizes.get(f"attn{2 * i}", 0) \
        + sizes[f"mlp{2 * i + 1}"]
    assert layer(0) == 104_161_472 and layer(7) == 76_682_240
    assert [i for i in range(28) if f"attn{2 * i}" in sizes] == [7, 21]
    assert sum(sizes.values()) == 3_029_337_472
    assert family.counts(PUBLISHED) == {"S": 26, "*": 2, "D": 28}


def test_a_slots_state_is_9_318_400_bytes_at_published_widths():
    """From shapes alone: the leaves the state pool holds a slot (`ssm_state`
    float32, `conv_state` bf16) in the 26 Mamba layers, under the names
    `serve/kv_pages.py` pools a slot, so `ssm_state_bytes` covers them."""
    from ddp_practice_tpu.serve.kv_pages import leaf_kind

    model = create_model(PUBLISHED["program_model"],
                         policy=PrecisionPolicy.bf16(),
                         **family.model_options(PUBLISHED))
    cache = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           decode=True)["cache"])
    state = [a for path, a in jax.tree_util.tree_flatten_with_path(cache)[0]
             if leaf_kind(path) == "state"]
    assert len(state) == 2 * 26
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in state) == 9_318_400
    assert 26 * (family.ssm_state_bytes(PUBLISHED)
                 + family.conv_state_bytes(PUBLISHED)) == 9_318_400
    assert family.decode_bytes(PUBLISHED) == (1024, 2 * 20 * 128 * 2 * 2)


@pytest.mark.parametrize("prompt_len", [5, 8, 13, 30])
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


def test_two_slots_of_different_lengths_and_a_slot_reused(toy, engine):
    """Two requests decode side by side from prompts of different buckets;
    the first is released and its slot taken by a third, whose logits owe
    nothing to the state the first one left there."""
    _, params = toy
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 96, n).tolist() for n in (6, 19, 11)]
    a = engine.admit(prompts[0], max_positions=24)
    b = engine.admit(prompts[1], max_positions=24)
    logits = {a: [np.asarray(engine._last_logits[a])],
              b: [np.asarray(engine._last_logits[b])]}
    toks = {a: [], b: []}
    for _ in range(6):
        out = engine.step_burst()
        for s in (a, b):
            toks[s].append(int(out[0, s]))
            logits[s].append(np.asarray(engine._last_logits[s]))
    for s, prompt in ((a, prompts[0]), (b, prompts[1])):
        want = ref_logits(params, prompt + toks[s])[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(logits[s]), want[:7],
                                   atol=TOL, rtol=TOL)
    d = engine.admit(prompts[0], max_positions=24)   # the third slot: full
    engine.release(a)
    c = engine.admit(prompts[2], max_positions=24)
    assert c == a
    got, toks_c = decode(engine, c, 6)
    for s in (b, c, d):
        engine.release(s)
    want = ref_logits(params, prompts[2] + toks_c)[10:17]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_a_state_outlives_the_toy_weights_prompt(toy):
    """The weights' point: with the dt bias near -4 the first prompt token
    still moves the logits 30 tokens on (a state dropped or reset at
    admission would pass the tests above only if it did not)."""
    _, params = toy
    rng = np.random.default_rng(0)
    seq = rng.integers(1, 96, 40).tolist()
    other = [(seq[0] + 1) % 96] + seq[1:]
    a, b = ref_logits(params, seq)[-1], ref_logits(params, other)[-1]
    assert np.abs(a - b).max() > 10 * TOL


def test_bf16_meets_a_tolerance_the_e4m3_control_fails(toy):
    """What `correct` rests on, at toy size, under the benchmark's own
    weights rule (perf/lib/weights_by_leaf.py; the toy's unit-scale weights
    amplify any rounding to 0.2-0.5): the program in bfloat16, as served,
    against the float32 reference, relative rms of the logits over a
    48-token sequence, beside the same reference with every matmul operand
    rounded to e4m3. Read over 3 seeds: bf16 0.0081-0.0083 (8 bits of
    mantissa), e4m3 0.060-0.079 (3 bits); the limit 0.025 is 3x the one
    and under half the other."""
    from perf.lib import weights_by_leaf

    # shapes, not arrays: handed arrays, the draw deletes each as it goes
    params = weights_by_leaf.make_params(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), toy[1]),
        3_000_000_019)
    model = create_model(CFG["program_model"], policy=PrecisionPolicy.bf16(),
                         **family.model_options(CFG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 48), 0, 96)
    with jax.default_matmul_precision("highest"):
        want, control = (np.asarray(x) for x in jax.jit(lambda p, t: tuple(
            reference.forward(p, t, CFG, q) for q in (None, "fp8")))(
                params, tokens))
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = np.asarray(jax.jit(model.apply)({"params": bf16}, tokens),
                     np.float32)
    rel = lambda x: float(np.sqrt(np.mean((x - want) ** 2)
                                  / np.mean(want ** 2)))
    assert rel(got) < 0.025 < rel(control), (rel(got), rel(control))


def test_scheduler_serves_it_and_the_spans_and_counters_say_what_was_scanned(
        toy):
    """Through `Scheduler` on the normal path, with the recorder and the
    metrics plane attached: every `prefill` span carries the prompt's real
    positions (`prompt_len`) in its `bucket` (the rest is the padding the
    scans run over besides), the counters add them up, and the gauge reads
    the state pool."""
    model, params = toy
    tracer = TraceRecorder(max_events=1 << 14)
    engine = make_engine(model, params, decode_burst=2)
    engine.set_tracer(tracer)
    metrics = ServeMetrics()
    sched = Scheduler(engine, max_queue=16, tracer=tracer, metrics=metrics)
    rng = np.random.default_rng(2)
    lens = [5, 8, 13, 30, 9]
    for rid, n in enumerate(lens):
        sched.submit(Request(rid=rid, prompt=rng.integers(1, 96, n).tolist(),
                             max_new_tokens=6, seed=rid))
    done = []
    while not sched.idle:
        done += sched.step()
    assert sorted(c.rid for c in done) == list(range(5))
    assert all(c.status == "length" and len(c.tokens) == 6 for c in done)
    spans = [e for e in tracer.to_chrome_trace()["traceEvents"]
             if e.get("name") == "prefill" and e.get("ph") in ("X", "B")]
    assert sorted(e["args"]["prompt_len"] for e in spans) == sorted(lens)
    for e in spans:
        a = e["args"]
        assert a["bucket"] >= a["prompt_len"] and a["prefix_hit"] == 0
    snap = metrics.registry.snapshot()
    assert snap["ssm_scan_tokens_total"] == sum(lens)
    assert snap["ssm_scan_padded_tokens_total"] == sum(
        e["args"]["bucket"] - e["args"]["prompt_len"] for e in spans)
    # 3 slots x 3 Mamba layers x (16 x 256 x 4 B state + 3 x 256 x 4 B tail)
    assert snap["ssm_state_bytes"] == engine.ssm_state_bytes \
        == 3 * 3 * (16 * 256 * 4 + 3 * 256 * 4)
