"""The Qwen3-Next layout of `HybridLM` (Gated DeltaNet mixers, gated attention
with head norms and rotary on part of a head, softmax-routed experts with a
gated shared expert) against the plain reference (perf/reference/
qwen3_next.py), at a small size on the CPU: the chunked scan and both kernels
in interpret mode against the sequential recurrence, the hand-off from a
prompt's scan to decode steps, prefill then decode through `PagedEngine` and
`Scheduler`, the four shares of an expert layer, the published parameter
count from shapes alone, the kernels by name, and the tolerance a bf16 run
meets and an e4m3 control fails."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import perf_toy  # noqa: E402
import qwen3_next_toy  # noqa: E402
from ddp_practice_tpu.config import PrecisionPolicy  # noqa: E402
from ddp_practice_tpu.inference import decode_apply  # noqa: E402
from ddp_practice_tpu.models import create_model  # noqa: E402
from ddp_practice_tpu.ops import gdn  # noqa: E402
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine  # noqa: E402
from ddp_practice_tpu.serve.kv_pages import leaf_kind, make_paged_cache  # noqa: E402
from ddp_practice_tpu.serve.metrics import ServeMetrics  # noqa: E402
from ddp_practice_tpu.serve.scheduler import Request, Scheduler  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402
from perf.families import qwen3_next as family  # noqa: E402
from perf.reference import qwen3_next as reference  # noqa: E402

CFG = qwen3_next_toy.config()
PUBLISHED = perf_toy.load("perf/configs/qwen3next_80b_ep4.json")
# float32 program against a float32 reference at the highest precision: the
# chunked scan sums a chunk's 16-64 positions in another order than the
# reference's position-by-position recurrence, the experts' rows are summed
# a tile at a time, attention a block of queries at a time; the unit-scale
# toy weights amplify that through four layers (2e-5 at the worst logit of
# a full forward here; logits up to 4). A dropped or stale state, a missing
# gate or an unrotated lane reads 0.05 and more.
TOL = 5e-4
# the scan and the kernels against the plain recurrence: the same float32
# products in another order of sums, through a 16-64-row triangular inverse
KERNEL_TOL = 2e-5


@pytest.fixture(scope="module")
def toy():
    return qwen3_next_toy.model_and_params(CFG)


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


def scan_inputs(b, l, seed, *, hk=2, hv=4, dk=32, dv=32, h0_zero=False,
                pad=0):
    """q and k unit a head (q scaled), decays of 0.93-1 a step, `pad`
    leading positions as a left-padded prefill has them: beta = 0, g = 0,
    zero q, k, v."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, l, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, l, hk, dk)))
    v = jax.random.normal(ks[2], (b, l, hv, dv))
    g = -0.05 * jax.nn.softplus(jax.random.normal(ks[3], (b, l, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, hv)))
    h0 = jnp.zeros((b, hv, dk, dv)) if h0_zero \
        else jax.random.normal(ks[5], (b, hv, dk, dv))
    if pad:
        real = (jnp.arange(l) >= pad)[None, :, None]
        q, k, v = (x * real[..., None] for x in (q, k, v))
        g, beta = g * real, beta * real
    return q, k, v, g, beta, h0


# -------------------------------------------------- the scan and the kernels
SCANS = {
    # (length, chunk, left padding): whole chunks; a partial last chunk
    # behind left padding; a prompt shorter than one chunk; several chunks
    # of the published 64 with padding that ends inside a chunk
    "whole_chunks": (64, 16, 0),
    "partial_chunk_left_padded": (70, 16, 5),
    "shorter_than_a_chunk": (21, 64, 0),
    "several_chunks_of_64": (200, 64, 13),
}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("case", sorted(SCANS))
def test_chunked_scan_is_the_sequential_recurrence(case, kernel):
    """From a NON-ZERO state: outputs and the final state of the chunked
    form (the terms in XLA and the carry as a `lax.scan`; both as their
    kernels, `gdn_terms` and `gdn_scan`, in interpret mode) against one
    position at a time; left padding moves nothing."""
    length, chunk, pad = SCANS[case]
    args = scan_inputs(2, length, 7, pad=pad)
    want_o, want_h = gdn.gdn_scan_reference(*args)
    o, h = gdn.gdn_scan(*args, chunk=chunk, kernel=kernel)
    assert float(jnp.abs(want_o).max()) > 0.3
    np.testing.assert_allclose(o, want_o, atol=KERNEL_TOL, rtol=KERNEL_TOL)
    np.testing.assert_allclose(h, want_h, atol=KERNEL_TOL, rtol=KERNEL_TOL)
    if pad:   # the padded stretch alone leaves the state as it came
        _, same = gdn.gdn_scan(*(x[:, :pad] for x in args[:5]), args[5],
                               chunk=chunk, kernel=kernel)
        np.testing.assert_array_equal(same, args[5])


REPEATED = [(0.7, -0.34), (0.95, -0.05), (0.99, -0.01)]   # (beta, g)


def repeated_token(inputs, beta, g):
    """`inputs` with one token all along the prompt under one (beta, g)."""
    q, k, v = (jnp.broadcast_to(x[:, :1], x.shape) for x in inputs[:3])
    return (q, k, v, jnp.full(v.shape[:3], g), jnp.full(v.shape[:3], beta))


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("beta, g", REPEATED)
def test_a_prompt_of_one_repeated_token_stays_the_recurrence(beta, g, kernel):
    """Every key of a chunk the same unit vector, a gate near 1 and a slow
    decay: what a prompt of one repeated token gives a layer (the engines'
    warm-up prompt is one). The chunk's triangular system is then dense and
    far from the identity; inverted by blocks it stays the recurrence to
    float32's last digits, where the closed product of its powers lost
    every digit (an error of 1e25 at beta 0.9, NaN past it: on the chip the
    warm-up left a state of 6e17 and then NaN in every slot, PR 38); so in
    XLA and in the kernel `gdn_terms`, which inverts by the same blocks."""
    ins = scan_inputs(1, 256, 9, h0_zero=True)
    args = repeated_token(ins, beta, g) + ins[5:]
    want_o, want_h = gdn.gdn_scan_reference(*args)
    o, h = gdn.gdn_scan(*args, kernel=kernel)
    np.testing.assert_allclose(o, want_o, atol=KERNEL_TOL, rtol=KERNEL_TOL)
    np.testing.assert_allclose(h, want_h, atol=KERNEL_TOL, rtol=KERNEL_TOL)


TERMS = {
    # (inputs, chunk): toy widths (2 key heads serving 4 value heads of
    # 32 x 32) and the published 128 x 128 with two value heads a key head
    "whole_chunks": lambda: (scan_inputs(1, 64, 3)[:5], 16),
    "partial_last_chunk": lambda: (tuple(
        jnp.pad(x, ((0, 0), (0, 10)) + ((0, 0),) * (x.ndim - 2))
        for x in scan_inputs(1, 38, 4)[:5]), 16),
    "left_padding": lambda: (scan_inputs(1, 64, 5, pad=21)[:5], 16),
    "batch_of_two": lambda: (scan_inputs(2, 48, 6)[:5], 16),
    **{f"repeated_token_{beta}": lambda beta=beta, g=g: (repeated_token(
        scan_inputs(1, 128, 9), beta, g), 64) for beta, g in REPEATED},
    "published_head_128x128": lambda: (scan_inputs(
        1, 128, 8, hk=1, hv=2, dk=128, dv=128)[:5], 64),
    "two_head_blocks": lambda: (scan_inputs(
        1, 32, 10, hk=16, hv=32, dk=8, dv=8)[:5], 16),
}


@pytest.mark.parametrize("case", sorted(TERMS))
def test_the_terms_kernel_is_the_chunk_terms(case):
    """`gdn_terms` (interpret mode) against its oracle `_chunk_terms`, term
    by term, to float32 rounding: the same products at the same precision,
    a (c, c) product summed inside an (n, n) tile whose other blocks are
    zeros, the running sum of g as a matmul against the lower
    triangle. The partial last chunk is padded as `gdn_scan` pads it (beta
    0, g 0, zero q, k, v). The repeated token makes the triangular system
    dense and its inverse a sum of terms that cancel (a closed product lost
    every digit there): another order of the same sums moves T's products
    by ten roundings where a random chunk's move by one."""
    ins, chunk = TERMS[case]()
    want = gdn._chunk_terms(*ins, chunk)
    got = gdn.gdn_terms_kernel(*ins, chunk)
    assert sorted(got) == sorted(want) == sorted(gdn._TERMS)
    tol = 4e-6 if case.startswith("repeated") else 4e-7
    for name in gdn._TERMS:
        assert got[name].shape == want[name].shape, name
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0.01 or (name == "dend" and scale > 0), name
        np.testing.assert_allclose(got[name], want[name], rtol=0,
                                   atol=tol * max(scale, 1.0), err_msg=name)


def test_the_block_inverse_is_the_inverse():
    """(I + a)^-1 by blocks against numpy's solve, at sizes that are and
    are not a power of two, for a dense strictly lower matrix."""
    for c in (8, 16, 21, 64):
        a = np.tril(np.random.default_rng(c).uniform(-1, 1, (3, c, c)), -1)
        got = np.asarray(gdn._unit_lower_inverse(jnp.asarray(a, jnp.float32)))
        want = np.linalg.inv(np.eye(c) + a)
        np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_step_kernel_is_one_reference_step():
    q, k, v, g, beta, h0 = scan_inputs(3, 1, 3)
    one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], h0)
    want_o, want_h = gdn.gdn_step_reference(*one)
    o, h = gdn.gdn_step_kernel(*one)
    assert float(jnp.abs(want_h - h0).max()) > 0.1
    np.testing.assert_allclose(o, want_o, atol=KERNEL_TOL, rtol=KERNEL_TOL)
    np.testing.assert_allclose(h, want_h, atol=KERNEL_TOL, rtol=KERNEL_TOL)


@pytest.mark.parametrize("kernel", [False, True])
def test_scan_then_steps_is_one_long_scan(kernel):
    """The hand-off of a prefill to decode: 21 positions scanned, 9 stepped,
    against 30 scanned at once."""
    step = gdn.gdn_step_kernel if kernel else gdn.gdn_step_reference
    q, k, v, g, beta, h0 = scan_inputs(2, 30, 5)
    want_o, want_h = gdn.gdn_scan_reference(q, k, v, g, beta, h0)
    cut = lambda x, t: x[:, t]
    o, h = gdn.gdn_scan(*(x[:, :21] for x in (q, k, v, g, beta)), h0,
                        chunk=16, kernel=kernel)
    os_ = [o]
    for t in range(21, 30):
        o_t, h = step(*(cut(x, t) for x in (q, k, v, g, beta)), h)
        os_.append(o_t[:, None])
    np.testing.assert_allclose(jnp.concatenate(os_, 1), want_o,
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)
    np.testing.assert_allclose(h, want_h, atol=KERNEL_TOL, rtol=KERNEL_TOL)


def test_the_kernels_are_one_named_op_each():
    """`gdn_terms`, `gdn_scan` and `gdn_step` are the `name=` of ONE
    `pallas_call` each: the names perf/layer_metrics/flood_gdn_* sum device
    time by (`flood_gdn_dev_pct` by the prefix `gdn_`, the two rooflines by
    `gdn_scan` and `gdn_step` whole, so the terms' kernel is in the first
    and in neither of the others), and none starts with `ssm_` or `sel_`,
    which the older readers sum. A prompt's call holds one `gdn_terms`
    beside its one `gdn_scan`; off the kernel path it holds neither."""
    q, k, v, g, beta, h0 = scan_inputs(2, 32, 3)
    scan = lambda kernel: _pallas_names(jax.make_jaxpr(
        lambda *a: gdn.gdn_scan(*a, chunk=16, kernel=kernel))(
            q, k, v, g, beta, h0).jaxpr)
    assert scan(True) == ["gdn_terms", "gdn_scan"]
    assert scan(False) == []
    assert _pallas_names(jax.make_jaxpr(gdn.gdn_step_kernel)(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], h0).jaxpr) \
        == ["gdn_step"]


# ------------------------------------------------------------- the model
def test_full_forward_matches_the_reference(toy):
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(_ref_forward(params, tokens))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_the_layout_is_the_registrys_and_the_options_are_the_models(toy):
    """`qwen3_next` in the registry: mixers named `mamba{i}` / `attn{i}`
    and expert layers `moe{i}` (the names perf/lib/scopes.py classifies),
    zero-centred norms (`weight`), a 64-wide head on a 128-wide stream with
    [query | gate] projected together, no selection bias, a gate on the
    shared expert."""
    model, params = toy
    assert model.pattern == "GQGQGQAQ" and model.pos_emb == "rope" \
        and model.recurrent and model.norm_plus_one
    assert set(params) == {
        "tok_embed", "lm_head", "norm_f", *(f"norm{i}" for i in range(8)),
        "mamba0", "mamba2", "mamba4", "attn6",
        "moe1", "moe3", "moe5", "moe7"}
    assert params["attn6"]["q"]["kernel"].shape == (128, 4, 128)
    assert params["attn6"]["kv"]["kernel"].shape == (128, 2, 2, 64)
    assert params["attn6"]["out"]["kernel"].shape == (4, 64, 128)
    assert set(params["attn6"]["q_norm"]) == {"weight"} == set(
        params["norm0"])
    assert set(params["mamba0"]) == {"in_proj", "ba_proj", "conv_kernel",
                                     "A_log", "dt_bias", "norm", "out_proj"}
    assert params["mamba0"]["in_proj"]["kernel"].shape == (128, 2 * 64 + 256)
    assert params["mamba0"]["norm"]["scale"].shape == (32,)
    assert "e_score_correction_bias" not in params["moe1"]
    assert params["moe1"]["shared_expert_gate"]["kernel"].shape == (128, 1)
    with pytest.raises(ValueError, match="pos_emb='rope'"):
        create_model("qwen3_next", pos_emb="none").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="want a string of"):
        create_model("qwen3_next", pattern="GX").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_published_widths_hold_3_667_251_328_parameters():
    """Abstract init at the published widths (no memory): what
    perf/configs/qwen3next_80b_ep4.json `deployment` and PERF.md state, and
    what the family reckons from the keys."""
    model = create_model(PUBLISHED["program_model"],
                         policy=PrecisionPolicy.bf16(),
                         **family.model_options(PUBLISHED))
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    sizes = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in abstract.items()}
    assert sizes["mamba0"] == 33_718_464 and sizes["attn6"] == 27_263_488
    moe = abstract["moe1"]
    assert sizes["moe1"] == 128 * 3_145_728 + 1_048_576 + 3_147_776
    assert moe["router"]["kernel"].shape == (2048, 512)
    assert moe["expert_gate"].shape == (128, 2048, 512)
    assert [i for i in range(8) if f"attn{2 * i}" in sizes] == [3, 7]
    assert sizes["tok_embed"] == sizes["lm_head"] == 37_984 * 2048
    assert sum(sizes.values()) == 3_667_251_328 \
        == family.param_count(PUBLISHED)
    assert family.counts(PUBLISHED) == {"G": 6, "A": 2, "Q": 8}


def test_a_slots_state_is_12_877_824_bytes_at_published_widths():
    """From shapes alone: the leaves the state pool holds a slot (`ssm_state`
    (32, 128, 128) float32, `conv_state` (3, 8192) bf16) in the 6 Gated
    DeltaNet layers, under the names `serve/kv_pages.py` pools a slot; a
    page row is 2 KV heads of 256."""
    model = create_model(PUBLISHED["program_model"],
                         policy=PrecisionPolicy.bf16(),
                         **family.model_options(PUBLISHED))
    cache = jax.eval_shape(lambda: make_paged_cache(model, 3, 64))
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    state = [a for path, a in leaves if leaf_kind(path) == "state"]
    assert sorted({a.shape for a in state}) == [(1, 3, 8192),
                                                (1, 32, 128, 128)]
    assert len(state) == 2 * 6
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in state) == 12_877_824 \
        == 6 * (family.ssm_state_bytes(PUBLISHED)
                + family.conv_state_bytes(PUBLISHED))
    pages = {a.shape for path, a in leaves
             if leaf_kind(path) == "pages" and a.ndim == 3}
    assert pages == {(3, 64, 512)}
    assert family.decode_bytes(PUBLISHED) == (4096, 2 * 16 * 256 * 2 * 2)


@pytest.mark.parametrize("prompt_len", [5, 8, 13, 30])
def test_prefill_then_decode_matches_the_reference(toy, engine, prompt_len):
    """A left-padded prompt of every bucket (full and partial), then 20
    tokens through the pages and the state pool, rotary at slot-local
    positions (the padding's included: only offsets survive): LOGITS against
    one full forward of the reference over prompt + tokens."""
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


def test_a_repeated_token_prompt_then_decode_matches_the_reference(
        toy, engine):
    """The warm-up's kind of prompt through the engine: one token thirty
    times, then 8 decoded."""
    _, params = toy
    prompt = [7] * 30
    slot = engine.admit(prompt, max_positions=16)
    got, toks = decode(engine, slot, 8)
    engine.release(slot)
    want = ref_logits(params, prompt + toks)[29:38]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_a_state_outlives_the_toy_weights_prompt(toy):
    """The weights' point: with `dt_bias` near -4 the first prompt token
    still moves the logits 30 tokens on (a state dropped or reset at
    admission would pass the tests above only if it did not)."""
    _, params = toy
    rng = np.random.default_rng(0)
    seq = rng.integers(1, 96, 40).tolist()
    other = [(seq[0] + 1) % 96] + seq[1:]
    a, b = ref_logits(params, seq)[-1], ref_logits(params, other)[-1]
    assert np.abs(a - b).max() > 10 * TOL


@pytest.mark.parametrize("option, value", [
    ("prefix_cache", True), ("spec_decode", True)])
def test_engine_refuses_what_needs_a_state_snapshot(toy, option, value):
    with pytest.raises(ValueError, match="refused for a model with recurrent"):
        make_engine(*toy, **{option: value})


def test_fork_stays_refused_and_chunks_continue_the_state(toy, engine):
    """ROADMAP M6: a fork needs the state at a position that is not the
    sequence's end; a prompt's chunks run in order need only the end."""
    model, params = toy
    slot = engine.admit([3, 4, 5], max_positions=8)
    with pytest.raises(ValueError, match="fork is refused"):
        engine.fork(slot)
    engine.release(slot)
    seq = np.random.default_rng(3).integers(1, 96, 21).tolist()
    chunked = make_engine(model, params, prefill_chunk=8)
    slot = chunked.admit(seq, max_positions=8)
    while chunked.is_prefilling(slot):
        chunked.prefill_step(slot)
    got = np.asarray(chunked._last_logits[slot])
    want = ref_logits(params, seq)[-1]
    assert np.abs(got - want).max() < TOL


# ------------------------------------------------------------ the experts
def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that the four held ranges
    give (offsets 0, 4, 8, 12 of 16; the ten-of-512 router's weights are
    normalised over ALL of a token's picks, held or not), with the gated
    shared expert counted once, add up to the uncut reference's layer."""
    from ddp_practice_tpu.ops.moe import GatedMoE

    whole_cfg = qwen3_next_toy.config(num_experts_held=16)
    _, whole = qwen3_next_toy.model_and_params(whole_cfg, seed=3)
    p = whole["moe1"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 19, 128))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.experts(x, p, whole_cfg))
        xf = x.reshape(-1, 128)
        shared = np.asarray(
            reference.swiglu(xf, p["shared"]) * jax.nn.sigmoid(
                xf @ p["shared_expert_gate"]["kernel"])).reshape(2, 19, 128)
        total = np.zeros_like(want)
        for off in (0, 4, 8, 12):
            layer = GatedMoE(16, 4, 48, 48, experts_held=4,
                             expert_offset=off, router="softmax",
                             shared_gate=True)
            share = dict(p, **{name: p[name][off:off + 4] for name in (
                "expert_gate", "expert_up", "expert_down")})
            out = np.asarray(jax.jit(layer.apply)({"params": share}, x))
            # the program's share against the reference's own share
            cut = qwen3_next_toy.config(expert_offset=off)
            np.testing.assert_allclose(
                out, np.asarray(reference.experts(x, share, cut)),
                atol=TOL, rtol=TOL)
            total += out - shared
    assert np.abs(want - shared).max() > 0.1     # the experts do something
    np.testing.assert_allclose(total + shared, want, atol=TOL, rtol=TOL)


def test_the_softmax_router_picks_and_weighs_as_written():
    """softmax over ALL experts in float32, the k largest, renormalised to
    sum 1: against numpy on a row with a clear order."""
    from ddp_practice_tpu.ops.moe import route_softmax_topk

    logits = jnp.asarray([[0.0, 3.0, 1.0, 2.0, -1.0, 0.5]], jnp.float32)
    picks, w = route_softmax_topk(logits, k=3, scaling=1.0)
    assert picks.tolist() == [[1, 3, 2]]
    e = np.exp([3.0, 2.0, 1.0])
    np.testing.assert_allclose(w[0], e / e.sum(), rtol=1e-6)


def test_kananas_paged_decode_program_is_what_it_was():
    """`GatedMoE`'s new fields default to the layer `deepseek_v3` runs: its
    paged decode step traces to the same equations whether the fields are
    left out or spelled out as their defaults, selection bias included, and
    a softmax layer is another program."""
    from ddp_practice_tpu.ops.moe import GatedMoE

    x = jnp.zeros((4, 1, 64))

    def jaxpr(**kw):
        layer = GatedMoE(8, 2, 32, 32, experts_held=8, **kw)
        params = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), x))["params"]
        cache = {"moe_stats": jnp.zeros((3,), jnp.int32)}
        return params, str(jax.make_jaxpr(
            lambda p: layer.apply({"params": p, "cache": cache}, x,
                                  decode=True, mutable=["cache"]))(params))

    p0, plain = jaxpr()
    p1, spelled = jaxpr(router="sigmoid", shared_gate=False)
    assert plain == spelled and "logistic" in plain
    assert set(p0) == set(p1) == {"router", "e_score_correction_bias",
                                  "expert_gate", "expert_up", "expert_down",
                                  "shared"}
    p2, soft = jaxpr(router="softmax", shared_gate=True)
    assert soft != plain and "e_score_correction_bias" not in p2


# --------------------------------------------- the programs, by kernel name
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


def test_a_decode_step_and_a_prefill_hold_their_kernels_by_name(monkeypatch):
    """At the published depth and layout (toy widths): a decode step is 6
    `gdn_step`, 2 `paged_decode` and 8 `moe_gmm_glu`, and no `gdn_terms`;
    an admission prefill 6 `gdn_scan`, one `gdn_terms` beside each, and 8
    `moe_gmm_glu` (its attention is plain XLA); and beside each
    `moe_gmm_glu` the two kernels that move its rows, `moe_rows_fill` in
    and `moe_rows_sum` out."""
    from ddp_practice_tpu.inference import make_cache
    from ddp_practice_tpu.utils import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = qwen3_next_toy.config(layers_run=8, linear_key_head_dim=128,
                                linear_value_head_dim=128, head_dim=128)
    model = create_model("qwen3_next", **family.model_options(cfg))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 16, 4))

    def step(params, pool, toks, table, lengths):
        return decode_apply(model, params, pool, toks, page_table=table,
                            kv_lengths=lengths)

    names = _pallas_names(jax.make_jaxpr(step)(
        params, pool, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros((4, 2), jnp.int32), jnp.zeros((4,), jnp.int32)).jaxpr)
    experts = ["moe_gmm_glu"] * 8 + ["moe_rows_fill"] * 8 \
        + ["moe_rows_sum"] * 8
    assert sorted(names) == ["gdn_step"] * 6 + experts + ["paged_decode"] * 2

    def prefill(params, tokens, start):
        return decode_apply(model, params, make_cache(model, 1, 128), tokens,
                            attn_start=start)

    names = _pallas_names(jax.make_jaxpr(prefill)(
        params, jnp.zeros((1, 128), jnp.int32),
        jnp.zeros((1,), jnp.int32)).jaxpr)
    assert sorted(names) == ["gdn_scan"] * 6 + ["gdn_terms"] * 6 + experts


def test_the_scopes_gdn_scan_and_gdn_step_are_in_the_op_paths(toy):
    """The program's side of perf/lib/scopes.py: a Gated DeltaNet layer's
    ops carry `.../mamba{i}/gdn_scan/...` (prefill) and `.../mamba{i}/
    gdn_step/...` (decode), so the enclosing `mamba{i}` reads `mixer`."""
    from ddp_practice_tpu.inference import make_cache

    import re

    model, params = toy
    paths = lambda lowered: set(re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text()))
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 2))
    step = jax.jit(lambda p, c: decode_apply(
        model, p, c, jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 4), jnp.int32),
        kv_lengths=jnp.zeros((2,), jnp.int32)))
    seen = paths(step.lower(params, pool))
    assert any("/mamba0/gdn_step/" in p for p in seen)
    assert not any("gdn_scan" in p for p in seen)
    fill = jax.jit(lambda p: decode_apply(
        model, p, make_cache(model, 1, 16), jnp.zeros((1, 16), jnp.int32),
        attn_start=jnp.zeros((1,), jnp.int32)))
    seen = paths(fill.lower(params))
    assert any("/mamba4/gdn_scan/" in p for p in seen)
    assert not any("gdn_step" in p for p in seen)


# ------------------------------------------------- precision, on the CPU
def test_bf16_meets_a_tolerance_the_e4m3_control_fails(toy):
    """What `correct` rests on, at toy size, under the benchmark's own
    weights rule (perf/lib/weights_by_leaf.py; the toy's unit-scale weights
    amplify any rounding): the program in bfloat16, as served, against the
    float32 reference, relative rms of the logits over a 48-token sequence,
    beside the same reference with every matmul operand rounded to e4m3.
    Read over 3 seeds: bf16 0.0088-0.0125 (8 bits of mantissa), e4m3
    0.061-0.065 (3 bits); the limit 0.025 is 2-3x the one and 0.4x the
    other."""
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
    bf16 = jax.jit(lambda p: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), p))(params)
    got = np.asarray(jax.jit(model.apply)({"params": bf16}, tokens),
                     np.float32)
    rel = lambda x: float(np.sqrt(np.mean((x - want) ** 2)
                                  / np.mean(want ** 2)))
    assert rel(got) < 0.025 < rel(control), (rel(got), rel(control))


# ------------------------------------------------- spans and counters
def test_scheduler_serves_it_and_the_spans_and_counters_say_what_ran(toy):
    """Through `Scheduler` on the normal path, with the recorder and the
    metrics plane attached: every `prefill` span carries the prompt's real
    positions (`prompt_len`) in its `bucket` (the rest is padding),
    every `decode_burst` what the softmax router's picks landed on, the
    counters add both up, and the gauge reads the state pool."""
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
    events = tracer.to_chrome_trace()["traceEvents"]
    spans = [e for e in events
             if e.get("name") == "prefill" and e.get("ph") in ("X", "B")]
    assert sorted(e["args"]["prompt_len"] for e in spans) == sorted(lens)
    for e in spans:
        a = e["args"]
        assert a["bucket"] >= a["prompt_len"] and a["prefix_hit"] == 0
    bursts = [e["args"] for e in events
              if e.get("name") == "decode_burst" and "args" in e]
    picks = 3 * 4 * 4 * 2        # slots x top-k x expert layers x steps
    for a in bursts:
        assert 0 < a["expert_rows"] <= picks
        assert 0 < a["experts_touched"] <= 4 * 4 * 2
        assert 0 < a["expert_rows_max"] <= 3
    snap = metrics.registry.snapshot()
    assert snap["ssm_scan_tokens_total"] == sum(lens)
    assert snap["ssm_scan_padded_tokens_total"] == sum(
        e["args"]["bucket"] - e["args"]["prompt_len"] for e in spans)
    assert snap["moe_rows_routed_total"] == picks * len(bursts)
    assert snap["moe_rows_held_total"] == sum(
        a["expert_rows"] for a in bursts)
    # 3 slots x 3 Gated DeltaNet layers x (4 x 32 x 32 x 4 B of state
    # + 3 x 256 x 4 B of conv tail)
    assert snap["ssm_state_bytes"] == engine.ssm_state_bytes \
        == 3 * 3 * (4 * 32 * 32 * 4 + 3 * 256 * 4)
