"""The Ling-3.0-flash layout of `HybridLM` (Kimi Delta Attention 'K' beside
latent attention 'T', a gate a head on both, experts 'U' under a sigmoid
router that keeps groups first) against the plain reference
(perf/reference/ling3.py), at a small size on the CPU: the three `kda_*`
kernels in interpret mode against the position-by-position recurrence, the
group-limited router against a literal loop, the shares of the experts
against the uncut layer, the full forward, chunks then decode through
`PagedEngine`'s state pool AND latent pages, the parameter recount, the
gauges, spans and refusals, and the two controls."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import ling3_toy  # noqa: E402
import perf_toy  # noqa: E402
from ddp_practice_tpu.config import PrecisionPolicy  # noqa: E402
from ddp_practice_tpu.inference import decode_apply  # noqa: E402
from ddp_practice_tpu.models import create_model, hybrid_lm, mla_lm  # noqa: E402
from ddp_practice_tpu.ops import gdn, kda  # noqa: E402
from ddp_practice_tpu.ops.moe import GatedMoE, route_sigmoid_topk  # noqa: E402
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine  # noqa: E402
from ddp_practice_tpu.serve.kv_pages import make_paged_cache  # noqa: E402
from ddp_practice_tpu.serve.metrics import ServeMetrics  # noqa: E402
from ddp_practice_tpu.serve.scheduler import Request, Scheduler  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402
from perf.families import ling3 as family  # noqa: E402
from perf.reference import ling3 as reference  # noqa: E402
from test_flash_attention import _eqns  # noqa: E402

CFG = ling3_toy.config()
PUBLISHED = perf_toy.load("perf/configs/ling3_flash_ep4.json")
# float32 program against a float32 reference at the highest precision: the
# chunked scan sums a chunk's positions in another order than the reference's
# position-by-position recurrence, and scales its rows about a reference row
# (2.5e-5 at the worst logit of a full forward here; logits up to 4). A
# dropped or stale state, a missing gate, a wrong group or an unrotated head
# reads 0.01 and more.
TOL = 2e-4
KERNEL_TOL = 2e-5


@pytest.fixture(scope="module")
def toy():
    return ling3_toy.model_and_params(CFG)


@jax.jit
def _ref_forward(params, tokens):
    with jax.default_matmul_precision("highest"):
        return reference.forward(params, tokens, CFG)


def ref_logits(params, seq):
    """The reference's logits over `seq`, through ONE compiled width (right
    padding is invisible to a causal model)."""
    tokens = np.zeros((1, 96), np.int32)
    tokens[0, :len(seq)] = seq
    return np.asarray(_ref_forward(params, jnp.asarray(tokens)))[0, :len(seq)]


def make_engine(model, params, **kw):
    opts = dict(max_slots=3, prompt_buckets=(8, 16), block_size=8,
                decode_burst=1, max_blocks_per_slot=12, temperature=0.0,
                prefill_chunk=16)
    opts.update(kw)
    return PagedEngine(model, params, EngineConfig(**opts))


@pytest.fixture(scope="module")
def engine(toy):
    return make_engine(*toy)


def admit(engine, seq, **kw):
    slot = engine.admit(seq, **kw)
    while engine.is_prefilling(slot):
        engine.prefill_step(slot)
    return slot


# --------------------------------------------------- the recurrence's kernels
def _kda_inputs(b=2, l=128, h=4, dk=16, dv=16, seed=0, g_min=-5.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, l, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, l, h, dk)))
    v = jax.random.normal(ks[2], (b, l, h, dv))
    g = g_min * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (b, l, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, l, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


@pytest.mark.parametrize("case", [
    "whole_chunks", "partial_chunk", "wide_heads", "gate_at_its_bound",
    "left_padding", "right_padding"])
def test_the_chunked_scan_is_the_sequential_recurrence(case):
    """`kda_terms` + `kda_scan` (interpret mode) and the XLA form against
    the recurrence a position at a time, FROM a state: whole chunks of 64, a
    call that ends inside a chunk (40 positions: 48 run, three sub-chunks),
    heads of 128 lanes in pairs down one tile, the safe gate AT its bound
    (g = -5 for 64 positions running: exp(G) spans float32's range, the
    sub-chunk references hold every exponent under 88), and padding on
    either side (beta = 0, g = 0, zero q, k, v: nothing moves)."""
    args = {"whole_chunks": {}, "partial_chunk": dict(b=1, l=40, h=3, dv=32),
            "wide_heads": dict(b=1, l=96, h=2, dk=128, dv=128),
            "gate_at_its_bound": dict(b=1, l=64, h=2)}.get(
                case, dict(b=1, l=64, h=2))
    q, k, v, g, beta, h0 = _kda_inputs(**args)
    if case == "gate_at_its_bound":
        g = jnp.full_like(g, -5.0)
    if case.endswith("padding"):
        at = jnp.arange(64)[None, :, None]
        real = at >= 21 if case == "left_padding" else at < 43
        q, k, v, g = (jnp.where(real[..., None], x, 0) for x in (q, k, v, g))
        beta = jnp.where(real, beta, 0)
    want_o, want_s = kda.kda_scan_reference(q, k, v, g, beta, h0)
    if case.endswith("padding"):   # the real rows alone, from the same state
        rows = slice(21, 64) if case == "left_padding" else slice(0, 43)
        alone = kda.kda_scan_reference(
            *(x[:, rows] for x in (q, k, v, g, beta)), h0)
        assert np.abs(alone[1] - want_s).max() < 1e-6
        assert np.abs(alone[0] - want_o[:, rows]).max() < 1e-6
    # at the bound a pair's exponent is the difference of two sums near 80
    tol = 1e-3 if case == "gate_at_its_bound" else KERNEL_TOL
    for use_kernel in (False, True):
        o, s = kda.kda_scan(q, k, v, g, beta, h0, kernel=use_kernel)
        assert bool(jnp.isfinite(o).all())
        assert np.abs(o - want_o).max() < tol, (case, use_kernel)
        assert np.abs(s - want_s).max() < KERNEL_TOL, (case, use_kernel)


def test_the_terms_kernel_is_its_oracle_term_by_term():
    q, k, v, g, beta, _ = _kda_inputs(b=1, l=128, h=4)
    want = kda._chunk_terms(q, k, v, g, beta, 64)
    got = kda.kda_terms_kernel(q, k, v, g, beta, 64)
    assert set(got) == set(want) == set(gdn._TERMS)
    for name in gdn._TERMS:
        assert got[name].shape == want[name].shape, name
        assert np.abs(got[name] - want[name]).max() < KERNEL_TOL, name
    assert want["dend"].shape == (1, 4, 2, 1, 16)    # a decay a KEY lane


@pytest.mark.parametrize("shape", [
    dict(b=3, h=4, dv=32), dict(b=3, h=16, dk=128, dv=128),
    dict(b=2, h=32, dk=128, dv=128)], ids=["4x32", "16x128", "32x128"])
def test_the_step_kernel_is_one_position_of_the_recurrence(shape):
    """Every head in one grid cell (4 heads: `_head_block`'s fallback), one
    whole cell of 16 at the served widths, and two cells a slot."""
    q, k, v, g, beta, h0 = _kda_inputs(l=1, **shape)
    one = tuple(x[:, 0] for x in (q, k, v, g, beta))
    want_o, want_s = kda.kda_step_reference(*one, h0)
    got_o, got_s = kda.kda_step_kernel(*one, h0)
    assert np.abs(got_o - want_o).max() < 1e-6
    assert np.abs(got_s - want_s).max() < 1e-6
    # the decay is a vector: a lane with g = 0 keeps its row of the state
    g0 = one[3].at[:, :, 5].set(0.0)
    kept = kda.kda_step_reference(one[0], jnp.zeros_like(one[1]), one[2], g0,
                                  one[4], h0)[1]
    assert np.abs(kept[:, :, 5] - h0[:, :, 5]).max() == 0
    assert np.abs(kept[:, :, 4] - h0[:, :, 4]).max() > 0


@pytest.mark.parametrize("heads", [4, 32])
def test_the_step_kernel_makes_no_column_by_a_matmul(heads):
    """A head's three column tiles are lanes of ONE transpose a grid cell,
    broadcast: the body holds no `dot_general` (through PR 47 one float32
    "highest" matmul a head made them, and the VPU waited for it: PERF.md
    section 6, PR 48), whether a cell holds 16 heads or all of them."""
    q, k, v, g, beta, h0 = _kda_inputs(b=2, l=1, h=heads, dk=128, dv=128)
    one = tuple(x[:, 0] for x in (q, k, v, g, beta))
    names = [e.primitive.name for e in _eqns(jax.make_jaxpr(
        kda.kda_step_kernel)(*one, h0).jaxpr)]
    assert names.count("pallas_call") == 1, names
    assert "dot_general" not in names
    cell = min(heads, 16)
    assert names.count("transpose") == 1
    assert names.count("swap") == 2 * cell      # a head's state and output


def test_gdn_carries_a_scalar_decay_through_the_shared_carry():
    """ops/gdn.py's own scan still equals its recurrence through the carry
    `kda_scan` now shares (`_carry_call`, `key_decay`)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, 128, 2, 16))) / 4
    k = unit(jax.random.normal(ks[1], (1, 128, 2, 16)))
    v = jax.random.normal(ks[2], (1, 128, 4, 16))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, 128, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 4)))
    h0 = jax.random.normal(ks[5], (1, 4, 16, 16))
    want = gdn.gdn_scan_reference(q, k, v, g, beta, h0)
    got = gdn.gdn_scan(q, k, v, g, beta, h0, kernel=True)
    assert np.abs(got[0] - want[0]).max() < KERNEL_TOL
    assert np.abs(got[1] - want[1]).max() < KERNEL_TOL


# ------------------------------------------------------------- the router
def _loop_route(logits, bias, k, scaling, n_group, topk_group):
    """The group-limited pick, a token at a time in Python."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    b = s + np.asarray(bias, np.float64)
    picks, weights = [], []
    per = b.shape[1] // n_group
    for t in range(b.shape[0]):
        score = [np.sort(b[t, j * per:(j + 1) * per])[-2:].sum()
                 for j in range(n_group)]
        kept = np.argsort(score)[::-1][:topk_group]
        allowed = [e for j in kept for e in range(j * per, (j + 1) * per)]
        best = sorted(allowed, key=lambda e: -b[t, e])[:k]
        w = s[t, best]
        picks.append(best)
        weights.append(w / w.sum() * scaling)
    return np.asarray(picks), np.asarray(weights)


def test_group_limited_routing_is_the_literal_loop_and_1_1_is_todays():
    key = jax.random.PRNGKey(4)
    logits = 2 * jax.random.normal(key, (40, 32))
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (32,))
    picks, w = route_sigmoid_topk(logits, bias, k=4, scaling=2.5, n_group=8,
                                  topk_group=3)
    want_picks, want_w = _loop_route(logits, bias, 4, 2.5, 8, 3)
    assert (np.sort(picks, -1) == np.sort(want_picks, -1)).all()
    assert np.abs(np.sort(w, -1) - np.sort(want_w, -1)).max() < 1e-6
    # every pick lies in one of 3 groups of 4 consecutive experts
    assert all(len(set(row // 4)) <= 3 for row in np.asarray(picks))
    # the ungrouped pick would have left those groups for some token
    free, _ = route_sigmoid_topk(logits, bias, k=4, scaling=2.5)
    assert (np.sort(free, -1) != np.sort(picks, -1)).any()
    # n_group = topk_group = 1: the router as it was, to the bit
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, old = jax.lax.top_k(s + bias.astype(jnp.float32), 4)
    old_w = jnp.take_along_axis(s, old, axis=-1)
    old_w = old_w / jnp.maximum(old_w.sum(-1, keepdims=True), 1e-20) * 2.5
    one, one_w = route_sigmoid_topk(logits, bias, k=4, scaling=2.5,
                                    n_group=1, topk_group=1)
    assert (one == old).all() and (one_w == old_w).all()
    assert (free == old).all()
    with pytest.raises(ValueError, match="groups"):
        route_sigmoid_topk(logits, bias, k=4, scaling=1.0, n_group=5,
                           topk_group=2)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """8 groups of 4 experts, 4 groups kept, top-4; four chips hold two
    groups each. The partial outputs of the four shares, the shared expert
    (which every chip computes alike) counted once, add up to the layer with
    all 32 experts held; a token whose 4 kept groups miss groups 0 and 1
    gets the shared expert alone from chip 0."""
    d, e, f = 32, 32, 12
    x = jax.random.normal(jax.random.PRNGKey(7), (48, d))
    layer = lambda held, off: GatedMoE(
        e, 4, f, f, held, off, 2.5, n_group=8, topk_group=4)
    whole = layer(e, 0)
    params = whole.init(jax.random.PRNGKey(8), x)["params"]
    params["router"]["kernel"] = 3 * params["router"]["kernel"]
    params["e_score_correction_bias"] = 0.2 * jax.random.normal(
        jax.random.PRNGKey(9), (e,))
    want = whole.apply({"params": params}, x)

    def share(i):
        mine = dict(params, **{k: params[k][8 * i:8 * (i + 1)] for k in (
            "expert_gate", "expert_up", "expert_down")})
        return layer(8, 8 * i).apply({"params": mine}, x)

    from ddp_practice_tpu.ops.moe import GatedMLP

    shared = GatedMLP(f).apply({"params": params["shared"]}, x)
    parts = [share(i) for i in range(4)]
    assert np.abs(sum(parts) - 3 * shared - want).max() < 1e-5
    logits = x @ params["router"]["kernel"]
    picks, _ = route_sigmoid_topk(
        logits, params["e_score_correction_bias"], k=4, scaling=2.5,
        n_group=8, topk_group=4)
    missed = np.asarray((picks >= 8).all(-1))
    assert missed.any() and not missed.all()
    assert np.abs(parts[0] - shared)[missed].max() < 1e-6
    assert np.abs(parts[0] - shared)[~missed].max() > 1e-3


# ------------------------------------------------------ the model's layers
def test_full_forward_matches_the_reference(toy):
    model, params = toy
    assert model.pattern == "KDKUTUKU" and model.recurrent
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 96), 0, 96)
    got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(_ref_forward(params, tokens))
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


def test_mla_lm_and_hybrid_lm_run_one_latent_attention(toy):
    assert mla_lm.LatentAttention is hybrid_lm.LatentAttention
    model, params = toy
    assert set(params["attn4"]) == {"q", "kv_a", "kv_norm", "kv_b", "gate",
                                    "out"}
    assert params["attn4"]["gate"]["kernel"].shape == (64, 4)   # a head
    assert set(params["mamba0"]) == {
        "in_proj", "f_proj", "b_proj", "z_proj", "dt_bias", "A_log",
        "conv_kernel", "norm", "out_proj"}
    assert params["mamba0"]["z_proj"]["kernel"].shape == (64, 4)
    assert params["mamba0"]["A_log"].shape == (4,)
    assert params["mamba0"]["dt_bias"].shape == (64,)           # a lane
    with pytest.raises(ValueError, match="out_gate"):
        hybrid_lm.LatentAttention(2, 8, 4, 8, 16, out_gate="lane").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))
    # MLALM's layers have no gate: the class's default
    plain = create_model("deepseek_v3", vocab_size=32)
    shapes = jax.eval_shape(lambda: plain.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert "gate" not in shapes["attn0"]


def test_the_registry_builds_the_pattern_from_the_configs_keys():
    opts = family.model_options(PUBLISHED)
    model = create_model("ling3", **opts)
    assert model.pattern == "KDKDKUKUKUTUKU"
    assert (model.n_group, model.topk_group, model.top_k,
            model.num_experts, model.experts_held) == (8, 4, 8, 512, 128)
    assert (model.gdn_value_heads, model.gdn_key_dim, model.gdn_value_dim,
            model.kda_lower_bound) == (32, 128, 128, -5.0)
    assert (model.num_heads, model.nope_dim, model.rope_dim, model.v_dim,
            model.attn_latent_dim, model.rope_theta) \
        == (32, 128, 64, 128, 512, 6e6)
    assert create_model("ling3", layers=12, layer_group_size=6,
                        first_dense=2).pattern[::2] == "KKKKKTKKKKKT"
    with pytest.raises(ValueError, match="rotate q and k"):
        create_model("ling3", pos_emb="none").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_published_widths_hold_4_454_368_704_parameters():
    """The deployment's count from the PROGRAM's shapes, the family's count
    from the keys, and the whole model's from the same function."""
    model = create_model("ling3", **family.model_options(PUBLISHED))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    assert count(shapes) == family.param_count(PUBLISHED) == 4_454_368_704
    assert count(shapes["mamba0"]) == 52_646_048
    assert count(shapes["attn10"]) == 31_965_696
    assert count(shapes["mlp1"]) == 47_185_920
    assert count(shapes["moe5"]) == 762_184_192
    whole = dict(PUBLISHED, layers_run=42, num_experts_held=512,
                 vocab_size=PUBLISHED["published"]["vocab_size"])
    assert family.param_count(whole) == 124_050_077_152
    assert family.counts(whole) == {"K": 35, "T": 7, "D": 2, "U": 40}


def test_a_slots_caches_at_published_widths():
    """A per-slot state pool AND a latent page pool in one cache: 13.03 MB
    of state a slot over 6 layers, 1,280 B a cached token in one."""
    model = create_model("ling3", policy=PrecisionPolicy.bf16(),
                         **family.model_options(PUBLISHED))
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 64, 2))
    flat = {jax.tree_util.keystr(p): a for p, a
            in jax.tree_util.tree_flatten_with_path(pool)[0]}
    state = sum(a.size * a.dtype.itemsize for k, a in flat.items()
                if "ssm_state" in k or "conv_state" in k)
    assert state == 2 * 13_025_280 \
        == 2 * 6 * (family.ssm_state_bytes(PUBLISHED)
                    + family.conv_state_bytes(PUBLISHED))
    latent = [a for k, a in flat.items() if "cached_latent" in k]
    assert [a.shape for a in latent] == [(9, 64, 640)]
    assert latent[0].dtype == jnp.bfloat16
    assert family.decode_bytes(PUBLISHED)[0] == 1152


# -------------------------------------------------------------- the engine
@pytest.mark.parametrize("prompt_len", [5, 16, 37, 70])
def test_chunks_then_decode_match_the_reference(toy, engine, prompt_len):
    """A prompt in 16-token chunks that carry the slot's state and write its
    latent pages, then 10 decode steps through `kda_step` and the absorbed
    latent walk: the reference's full forward at every served position."""
    model, params = toy
    seq = np.random.default_rng(prompt_len).integers(1, 96, prompt_len)
    slot = admit(engine, seq.tolist(), max_positions=12)
    assert engine.context_len(slot) == prompt_len
    logits, toks = [np.asarray(engine._last_logits[slot])], []
    for _ in range(10):
        toks.append(int(engine.step_burst()[0, slot]))
        logits.append(np.asarray(engine._last_logits[slot]))
    want = ref_logits(params, seq.tolist() + toks)[prompt_len - 1:]
    assert np.abs(np.stack(logits) - want).max() < TOL
    engine.release(slot)


def test_a_slot_waits_between_its_chunks_while_others_decode(toy, engine):
    """A 61-token prompt's four chunks, a decode burst of another slot
    between every two: the waiting slot's state row and latent pages do not
    move under the others' steps (`real_lengths` 0 for it)."""
    model, params = toy
    rng = np.random.default_rng(8)
    short, long_ = (rng.integers(1, 96, n).tolist() for n in (12, 61))
    a = admit(engine, short, max_positions=12)
    logits = {a: [np.asarray(engine._last_logits[a])]}
    toks = {a: []}
    b = engine.admit(long_, max_positions=8)
    chunks = 1
    while not engine.prefill_step(b):
        chunks += 1
        toks[a].append(int(engine.step_burst()[0, a]))
        logits[a].append(np.asarray(engine._last_logits[a]))
    assert chunks == 4 and not engine.is_prefilling(b)
    logits[b], toks[b] = [np.asarray(engine._last_logits[b])], []
    for _ in range(4):
        out = engine.step_burst()
        for s in (a, b):
            toks[s].append(int(out[0, s]))
            logits[s].append(np.asarray(engine._last_logits[s]))
    for s, seq in ((a, short), (b, long_)):
        want = ref_logits(params, seq + toks[s])[len(seq) - 1:]
        assert np.abs(np.stack(logits[s]) - want).max() < TOL
        engine.release(s)


def test_what_needs_a_snapshot_stays_refused_with_its_reason(toy, engine):
    model, params = toy
    for option, why in (
            ("prefix_cache", "without the state at the prefix's end"),
            ("spec_decode", "cannot be rolled back out of the state")):
        with pytest.raises(ValueError, match="refused for a model with "
                                             "recurrent state") as e:
            make_engine(model, params, **{option: True})
        assert why in str(e.value)
    slot = admit(engine, [3, 4, 5], max_positions=4)
    with pytest.raises(ValueError, match="fork is refused"):
        engine.fork(slot)
    engine.release(slot)


def test_scheduler_serves_it_and_the_spans_and_gauges_say_what_ran(toy):
    """Through `Scheduler` on the normal path, with the recorder and the
    metrics plane attached: every `prefill_chunk` span carries its real
    positions, every `decode_burst` the latent pages walked and what the
    expert layers touched, and BOTH pool gauges read non-zero for the one
    engine."""
    model, params = toy
    tracer = TraceRecorder(max_events=1 << 14)
    engine = make_engine(model, params, decode_burst=2)
    engine.set_tracer(tracer)
    metrics = ServeMetrics()
    sched = Scheduler(engine, max_queue=16, tracer=tracer, metrics=metrics)
    rng = np.random.default_rng(2)
    lens = [5, 40, 13, 70]
    for rid, n in enumerate(lens):
        sched.submit(Request(rid=rid, prompt=rng.integers(1, 96, n).tolist(),
                             max_new_tokens=6, seed=rid))
    done = []
    while not sched.idle:
        done += sched.step()
    assert sorted(c.rid for c in done) == list(range(4))
    assert all(c.status == "length" and len(c.tokens) == 6 for c in done)
    events = tracer.to_chrome_trace()["traceEvents"]
    spans = [e["args"] for e in events if e.get("name") == "prefill_chunk"
             and e.get("ph") in ("X", "B")]
    assert sum(a["take"] for a in spans) == sum(lens)
    assert len(spans) == sum(-(-n // 16) for n in lens)
    bursts = [e["args"] for e in events
              if e.get("name") == "decode_burst" and "args" in e]
    assert bursts and all(
        a["pages_walked"] > 0 and a["expert_rows"] > 0
        and 0 < a["experts_touched"] for a in bursts)
    snap = metrics.registry.snapshot()
    assert snap["ssm_scan_tokens_total"] == sum(lens)
    # 3 slots x 3 KDA layers x (4 x 16 x 16 x 4 B + 3 x 192 x 4 B)
    assert snap["ssm_state_bytes"] == engine.ssm_state_bytes \
        == 3 * 3 * (4096 + 2304)
    # 37 blocks x 8 positions x 128 lanes x 4 B, one latent layer
    assert snap["latent_cache_bytes"] == engine.latent_cache_bytes \
        == 37 * 8 * 128 * 4


def test_the_scopes_are_in_the_op_paths(toy):
    """The program's side of perf/lib/scopes.py: `mamba{i}/kda_step` and
    `attn{i}` in a decode step, `mamba{i}/kda_terms` and `mamba{i}/kda_scan`
    in a chunk."""
    model, params = toy
    paths = lambda lowered: set(re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text()))
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 2))
    step = jax.jit(lambda p, c: decode_apply(
        model, p, c, jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 4), jnp.int32),
        kv_lengths=jnp.zeros((2,), jnp.int32)))
    seen = paths(step.lower(params, pool))
    for want in ("/mamba0/kda_step/", "/attn4/", "/moe3/moe_route/"):
        assert any(re.search(want, p) for p in seen), want
    one = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 1))
    fill = jax.jit(lambda p, c: decode_apply(
        model, p, c, jnp.zeros((1, 16), jnp.int32),
        page_table=jnp.zeros((1, 4), jnp.int32),
        kv_lengths=jnp.zeros((1,), jnp.int32),
        real_lengths=jnp.full((1,), 16, jnp.int32)))
    seen = paths(fill.lower(params, one))
    for want in ("/mamba0/kda_terms/", "/mamba0/kda_scan/"):
        assert any(re.search(want, p) for p in seen), want
    assert not any("kda_step" in p for p in seen)


# ------------------------------------------------------------ the controls
def test_bf16_meets_a_tolerance_both_controls_fail(toy):
    """What `correct` rests on, at toy size, under the benchmark's own
    weights rule (perf/lib/weights_by_leaf.py with `dt_bias` shifted by the
    configuration's -4, as the cell's driver draws them; the toy's unit-scale
    weights amplify any rounding): the program in bfloat16, as served,
    against the float32 reference, relative rms of the logits over a
    72-token sequence, beside the same reference with every matmul operand
    rounded to e4m3 and beside the one whose carried state is zeroed every
    16 positions (a program that lost a slot's state between two chunks of
    its prompt: the state's memory is long enough to be missed)."""
    from perf.drivers import serve_state_latent_by_leaf as driver
    from perf.lib import weights_by_leaf

    # shapes, not arrays: handed arrays, the draw deletes each as it goes
    params = driver.shifted(
        weights_by_leaf.make_params, {"dt_bias": CFG["dt_bias_shift"]})(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         toy[1]), 3_000_000_019)
    assert abs(float(params["mamba0"]["dt_bias"].mean()) + 4.0) < 0.02
    model = create_model(CFG["program_model"], policy=PrecisionPolicy.bf16(),
                         **family.model_options(CFG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 72), 0, 96)
    with jax.default_matmul_precision("highest"):
        want, e4m3, lost, prompt = (np.asarray(x) for x in jax.jit(
            lambda p, t: (
                reference.forward(p, t, CFG),
                reference.forward(p, t, CFG, "fp8"),
                reference.forward(p, t, CFG, state_reset=16),
                reference.forward(p, t, CFG, state_reset=16,
                                  reset_until=48)))(params, tokens))
    bf16 = jax.jit(lambda p: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), p))(params)
    got = np.asarray(jax.jit(model.apply)({"params": bf16}, tokens),
                     np.float32)
    rel = lambda x: float(np.sqrt(np.mean((x - want) ** 2)
                                  / np.mean(want ** 2)))
    assert rel(got) < 0.025 < min(rel(e4m3), rel(lost)), \
        (rel(got), rel(e4m3), rel(lost))
    # before the first reset the two references are one
    assert np.abs(lost[:, :16] - want[:, :16]).max() == 0
    # a 48-token prompt's boundaries are 16 and 32: no reset at 48 or after
    assert np.abs(prompt[:, :48] - lost[:, :48]).max() < 1e-5
    assert np.abs(prompt[:, 48:] - lost[:, 48:]).max() > 1e-2
