"""The MiniCPM-SALA layout of `HybridLM` (lightning attention on the Mamba-2
state kernels, block-sparse attention over compressed keys, muP scalars)
against the plain reference (perf/reference/minicpm_sala.py), at a small size
on the CPU: toy blocks of 8 tokens, top-4, dense up to 32 visible tokens, so
that a sequence of some seventy tokens meets the sparse branch, the forced
blocks and the switch inside a chunk. `LightningMixer` against the sequential
recurrence, the selection against the reference's picks, the three kernels in
interpret mode against their plain forms, prefill in one call, in chunks, and
chunks then decode through `PagedEngine` and `Scheduler`, the parameter
recount, the kernels by name, and the tolerance a bf16 run meets and an e4m3
control fails."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import minicpm_sala_toy  # noqa: E402
import perf_toy  # noqa: E402
from ddp_practice_tpu.config import PrecisionPolicy  # noqa: E402
from ddp_practice_tpu.inference import decode_apply, make_cache  # noqa: E402
from ddp_practice_tpu.models import create_model  # noqa: E402
from ddp_practice_tpu.ops import sparse_attention as sparse, ssm  # noqa: E402
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine  # noqa: E402
from ddp_practice_tpu.serve.kv_pages import leaf_kind, make_paged_cache  # noqa: E402
from ddp_practice_tpu.serve.metrics import ServeMetrics  # noqa: E402
from ddp_practice_tpu.serve.scheduler import Request, Scheduler  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402
from perf.families import minicpm_sala as family  # noqa: E402
from perf.reference import minicpm_sala as reference  # noqa: E402

CFG = minicpm_sala_toy.config()
PUBLISHED = perf_toy.load("perf/configs/minicpm_sala_9b_pp4.json")
SPEC = sparse.SparseSpec(**CFG["sparse"])
# float32 program against a float32 reference at the highest precision: the
# chunked scan sums a chunk's positions in another order than the reference's
# position-by-position recurrence, attention a block of queries at a time
# (3e-6 at the worst logit of a full forward here; logits up to 1). A dropped
# or stale state, a missing gate, a wrong pick or an unrotated head reads 0.01
# and more.
TOL = 5e-5
KERNEL_TOL = 2e-5


@pytest.fixture(scope="module")
def toy():
    return minicpm_sala_toy.model_and_params(CFG)


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


def decode(engine, slot, steps):
    logits, toks = [np.asarray(engine._last_logits[slot])], []
    for _ in range(steps):
        toks.append(int(engine.step_burst()[0, slot]))
        logits.append(np.asarray(engine._last_logits[slot]))
    return np.stack(logits), toks


# ------------------------------------------------------ lightning attention
def _lightning_inputs(b=2, s=40, h=4, p=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    slope = 2.0 ** (-8.0 * (np.arange(h) + 1.0) / h)
    return (f(b, s, h, p), jnp.ones((b, s, h)), -jnp.asarray(slope, jnp.float32),
            f(b, s, h, p), f(b, s, h, p), jnp.zeros((h,)),
            0.1 * f(b, h, p, p))


def test_the_scan_at_a_group_a_head_is_the_sequential_recurrence():
    """`ssm_scan` as 'L' calls it (dt 1, a constant decay, groups = heads, no
    skip) from a state that is not zero, a chunk of 16 against position by
    position."""
    args = _lightning_inputs()
    want, state = ssm.ssm_scan_sequential(*args)
    got, final = ssm.ssm_scan(*args, chunk=16)
    assert np.abs(np.asarray(got - want)).max() < KERNEL_TOL * 10
    assert np.abs(np.asarray(final - state)).max() < KERNEL_TOL * 10


def _step_args(h, g, p=128, n=128, slots=2, seed=1):
    """`ssm_step`'s arguments for `h` heads of (p, n) in `g` groups."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(slots, h, p), jnp.abs(f(slots, h)), -jnp.abs(f(h)),
            f(slots, g, n), f(slots, g, n), f(h), f(slots, h, p, n))


def _step_call(*args):
    """What `ssm_step_kernel` traces to: (grid, a cell's block of the state
    past the squeezed slot, the kernel body's jaxpr as text)."""
    fresh = lambda *a: ssm.ssm_step_kernel(*a)   # no trace of another budget
    call, = (e for e in jax.make_jaxpr(fresh)(*args).eqns
             if e.primitive.name == "pallas_call")
    mapping = call.params["grid_mapping"]
    block = tuple(getattr(d, "block_size", d)
                  for d in mapping.block_mappings[4].block_shape[1:])
    return tuple(mapping.grid), block, str(call.params["jaxpr"])


def test_the_step_kernel_takes_several_groups_a_cell():
    """32 groups of one head (lightning attention at the published widths'
    count) are 2 grid cells of 16 a sequence, 1 MiB of state each way: the
    program the long-document cell has run since PR 40, an unrolled body
    with no loop in it. Mamba-2's 8 groups of 16 heads of (64, 128), 512 KB
    each, go two a cell (PR 51); a group that is 1 MiB alone stays a cell.
    Both equal the plain step."""
    for h, g in ((32, 32), (16, 2)):
        args = _step_args(h, g)
        want, state = ssm.ssm_step_reference(*args)
        got, new = ssm.ssm_step_kernel(*args)
        assert np.abs(np.asarray(got - want)).max() < 1e-4
        assert np.abs(np.asarray(new - state)).max() < KERNEL_TOL
    grid = lambda *shape: _step_call(*_step_args(*shape))[0]
    assert grid(128, 8) == (2, 8) and grid(128, 8, 64) == (2, 4)
    lightning, block, body = _step_call(*_step_args(32, 32))
    assert lightning == (2, 2) and block == (16, 1, 128, 128)
    assert not re.search(r"\b(while|scan)\b", body)


# (heads, groups, head size, state size), MiB a cell -> groups a grid cell
STEP_CELLS = {
    "two_mamba2_groups_a_cell": ((32, 2, 64, 128), 1, 2),
    "four_mamba2_groups_in_2_mib": ((64, 4, 64, 128), 2, 4),
    "four_half_groups_a_cell": ((32, 4, 64, 128), 1, 4),
    "three_groups_none_divides": ((48, 3, 64, 128), 1, 1),
    "one_group": ((16, 1, 64, 128), 1, 1),
    "a_group_over_the_budget": ((32, 1, 128, 128), 1, 1),
    "small_groups_all_in_one_cell": ((8, 4, 16, 32), 1, 4),
}


@pytest.mark.parametrize("case", sorted(STEP_CELLS))
def test_the_step_kernel_fills_its_cell_by_bytes(case, monkeypatch):
    """`_STEP_BYTES` of state a cell (1 MiB in the tree; 2 MiB is the form
    PR 51 measured beside it): whole groups, a divisor of their count, one
    where nothing else fits; the interpreted kernel equals the plain step
    whatever the cell holds."""
    (h, g, p, n), mib, groups = STEP_CELLS[case]
    assert ssm._STEP_BYTES == 2**20
    monkeypatch.setattr(ssm, "_STEP_BYTES", mib * 2**20)
    args = _step_args(h, g, p, n, seed=len(case))
    grid, block, _ = _step_call(*args)
    assert grid == (2, g // groups) and block == (groups, h // g, p, n)
    cell = groups * (h // g) * 4 * p * n
    assert cell <= ssm._STEP_BYTES or groups == 1
    want, state = ssm.ssm_step_reference(*args)
    got, new = jax.jit(lambda *a: ssm.ssm_step_kernel(*a))(*args)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert np.abs(np.asarray(new - state)).max() < KERNEL_TOL


def test_the_step_kernel_rewrites_a_donated_state_in_place():
    """Two calls, each on the state the one before wrote and donated (the
    engine's decode burst): the aliased output is the second step of the
    plain recurrence, with two groups a cell."""
    *vectors, state = _step_args(32, 2, 64, 128, seed=7)
    step = jax.jit(ssm.ssm_step_kernel, donate_argnums=(6,))
    want = state
    for _ in range(2):
        want_y, want = ssm.ssm_step_reference(*vectors, want)
    got = state + 0.0       # the donated copy
    for _ in range(2):
        got_y, got = step(*vectors, got)
    assert np.abs(np.asarray(got_y - want_y)).max() < 1e-4
    assert np.abs(np.asarray(got - want)).max() < KERNEL_TOL


def test_lightning_mixer_is_the_references_layer(toy):
    """One 'L' layer of the model against `reference.lightning` on the same
    weights: norms, rotary, decay of the PUBLISHED layer, output norm and
    gate; then the same in two calls through a flat cache."""
    model, params = toy
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 64))
    from ddp_practice_tpu.models.hybrid_lm import LightningMixer

    layer = LightningMixer(4, 16, layer=4, layers=8, norm_eps=1e-6)
    p = params["mamba2"]      # sub-layer 2 is published layer 4
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": p}, x, positions=jnp.arange(40))
        want = reference.lightning(x, p, CFG, 4)
        wrong = reference.lightning(x, p, CFG, 5)
    assert np.abs(np.asarray(got - want)).max() < KERNEL_TOL
    assert np.abs(np.asarray(got - wrong)).max() > 100 * KERNEL_TOL


# ---------------------------------------------------------------- selection
def _selection_case(s=72, seed=5):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = f(1, s, 4, 16), f(1, s, 2, 16)
    blocks = -(-s // 8)
    at = 2 * np.arange(4 * blocks)[:, None] + np.arange(4)
    padded = jnp.pad(k, ((0, 0), (0, int(at.max()) + 1 - s), (0, 0), (0, 0)))
    return q, k, f(1, s, 2, 16), jnp.mean(padded[:, at], axis=2)


def test_the_programs_picks_are_the_references():
    """`block_scores` + `select_blocks` against `reference.block_picks`, row
    by row over 72 positions: the same sets wherever no two scores tie."""
    q, k, _, index = _selection_case()
    pos = jnp.arange(72)[None]
    zero = jnp.zeros((1,), jnp.int32)
    picks = sparse.select_blocks(
        sparse.block_scores(q.reshape(1, 72, 2, 2, 16), index, pos + 1, zero,
                            SPEC), pos, zero, SPEC)
    mine = np.asarray(sparse.picked_mask(picks, 9))[0]
    theirs = np.asarray(reference.block_picks(
        q[0].reshape(72, 2, 2, 16), index[0], pos[0], CFG["sparse"]))
    sparse_rows = np.arange(72) >= 32
    assert (mine[sparse_rows] == theirs[sparse_rows]).all()
    # block 0 and the query's own and the one before are always in
    for t in (40, 55, 71):
        for m in (0, t // 8, (t - 7) // 8):
            assert mine[t, :, m].all()
    assert (mine[sparse_rows].sum(-1) == 4).all()


def test_select_hands_the_walk_one_list_a_slot_and_kv_head():
    """`sparse_select` for three slots of a 9-page table: a dense one (20
    tokens) gets its own pages in order and its length as it is; a sparse one
    (position 61) its 4 picks ascending, the query's page last, and a length
    of 3 whole pages and the page's head; lists, lengths and starts are
    (slots, KV heads): the kernel reads them by grid cell."""
    rng = np.random.default_rng(6)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, 40))[:27].reshape(3, 9),
                        jnp.int32)
    lengths = jnp.asarray([20, 61, 0], jnp.int32)
    pages, tokens, held = sparse.sparse_select(
        f(3, 4, 16), f(40, 4, 32), table, lengths, jnp.zeros((3,), jnp.int32),
        spec=SPEC, kv_heads=2)
    assert pages.shape == (3, 2, 4) and tokens.shape == (3, 2)
    assert np.asarray(held).tolist() == [3, 8, 1]
    assert np.asarray(tokens).tolist() == [[20, 20], [29, 29], [0, 0]]
    assert (np.asarray(pages[0]) == np.asarray(
        [*table[0, :3].tolist(), 0])).all()
    for head in range(2):
        cols = [int(np.flatnonzero(np.asarray(table[1]) == p)[0])
                for p in np.asarray(pages[1, head])]
        assert cols == sorted(cols) and cols[0] == 0 and cols[-1] == 7
        assert 6 in cols       # the last `window` 8 tokens reach into page 6
    with pytest.raises(ValueError, match="a length and a start a list"):
        sparse.sparse_walk(f(3, 4, 16), f(40, 8, 32), f(40, 8, 32), pages,
                           tokens[:, :1], tokens)


def test_topk_over_every_block_is_dense_attention():
    """A sparse layer whose top-k covers all blocks equals plain causal
    attention, and with the toy's top-4 it does not."""
    q, k, v, index = _selection_case()
    pos, zero = jnp.arange(72)[None], jnp.zeros((1,), jnp.int32)
    all_blocks = SPEC._replace(topk=9, dense_len=72)
    out, _ = sparse.sparse_attention_reference(
        q, k, v, index, pos, zero, SPEC._replace(topk=9, dense_len=32,
                                                 window=8))
    dense, _ = sparse.sparse_attention_reference(
        q, k, v, index, pos, zero, all_blocks)
    few, _ = sparse.sparse_attention_reference(q, k, v, index, pos, zero, SPEC)
    assert np.abs(np.asarray(out - dense)).max() < 1e-6
    assert np.abs(np.asarray(few - dense))[:, :32].max() < 1e-6
    assert np.abs(np.asarray(few - dense))[:, 40:].max() > 1e-2


def test_the_spec_refuses_sizes_that_do_not_fit():
    with pytest.raises(ValueError, match="4 strides"):
        SPEC._replace(block=16).check()
    with pytest.raises(ValueError, match="dense_len"):
        SPEC._replace(dense_len=24).check()
    with pytest.raises(ValueError, match="forced blocks"):
        SPEC._replace(window=32).check()
    assert sparse.SparseSpec().check().list_pages == 128


# ------------------------------------------------ the kernels, interpreted
def test_the_list_walk_kernel_is_its_plain_form():
    """`sparse_walk` (the paged walk kernel fed lists, one KV head a cell)
    in interpret mode: lists of unlike lengths, a page's tail cut by the
    length, a first page's head by `start`."""
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, kp, vp = f(3, 8, 128), f(40, 8, 256), f(40, 8, 256)
    pages = jnp.asarray(rng.integers(1, 40, (3, 2, 6)), jnp.int32)
    tokens = jnp.asarray([[5, 47], [20, 20], [0, 33]], jnp.int32)
    start = jnp.asarray([[0, 3], [2, 2], [0, 0]], jnp.int32)
    want = sparse.sparse_walk_reference(q, kp, vp, pages, tokens, start)
    got = sparse.sparse_walk(q, kp, vp, pages, tokens, start, impl="kernel")
    assert np.abs(np.asarray(got - want)).max() < KERNEL_TOL


def _hand_picks(s, blocks, rows):
    """(s, 2, blocks) bool: every row its own `rows[i]` blocks, both KV
    heads alike."""
    picked = np.zeros((s, 2, blocks), bool)
    for i, own in enumerate(rows):
        picked[i, :, list(own)] = True
    return jnp.asarray(picked)


def _prefill_case(name):
    """(s, pages, pos0, picks, check): a chunk of `s` queries at `pos0` of a
    `pages`-page table; `picks` None for the real selection or (s, 2, pages)
    bool; `check(picked, counts)` says the case is the one its name tells of
    (counts (tiles, 2): the entries of each tile's list)."""
    fold = sparse.PREFILL_FOLD
    if name == "many_picks":        # rows attend their OWN picks though a
        return 32, 12, 40, None, lambda p, c: (      # tile runs the union
            (p.sum(-1) == 4).all()
            and len({tuple(r) for r in p[:, 0].tolist()}) > 4)
    if name == "odd_count":         # the last live step's tail entry is dead
        rows = [{0, 2, 5, 6, 9}] * 16 + [{0, 2, 3, 6, 8, 9}] * 16
        return 32, 12, 72, _hand_picks(32, 12, rows), \
            lambda p, c: (c == 7).all() and 7 % fold
    if name == "count_of_one":      # a chunk at position 0 of the first page
        return 8, 12, 0, None, lambda p, c: (c == 1).all()
    if name == "first_of_a_pair":   # entries 2 and 3 are blocks 4 and 7:
        rows = [{0, 1, 4, 9}] * 16 + [{0, 1, 4, 7, 9}] * 16   # no row has 7
        return 32, 12, 72, _hand_picks(32, 12, rows), \
            lambda p, c: p[:16, :, 4].all() and not p[:16, :, 7].any()
    if name == "second_of_a_pair":
        rows = [{0, 1, 7, 9}] * 16 + [{0, 1, 4, 7, 9}] * 16   # nor has 4
        return 32, 12, 72, _hand_picks(32, 12, rows), \
            lambda p, c: p[:16, :, 7].all() and not p[:16, :, 4].any()
    if name == "across_dense_len":  # rows 16-31 are dense, 32-47 pick 4
        return 32, 12, 16, None, lambda p, c: (
            (p[:16].sum(-1) == np.arange(16, 32)[:, None] // 8 + 1).all()
            and (p[16:].sum(-1) == 4).all())
    if name == "second_lane_tile":  # 160 blocks: picks past block 127, whose
        return 32, 160, 1048, None, lambda p, c: (  # bias is the 2nd lane tile
            (p.sum(-1) == 4).all() and p[:, :, 128:].any(-1).all())
    if name == "two_tiles":         # tile 0 ends at block 15, tile 1 at 31
        return 256, 40, 0, None, lambda p, c: (
            c.shape[0] == 2 and (c[0] != c[1]).all())
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "many_picks", "odd_count", "count_of_one", "first_of_a_pair",
    "second_of_a_pair", "across_dense_len", "second_lane_tile", "two_tiles"])
def test_the_prefill_kernel_is_the_per_row_selection(name):
    """`sparse_prefill` in interpret mode against its plain form: a tile
    runs the union of its rows' picks, `PREFILL_FOLD` list entries a grid
    step, and every row attends its OWN picks under the causal mask."""
    s, pages, pos0, picks, check = _prefill_case(name)
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool = pages + 28
    kp, vp = f(pool, 8, 256), f(pool, 8, 256)
    table = jnp.asarray(rng.permutation(np.arange(1, pool))[:pages],
                        jnp.int32)
    q, index = f(s, 2, 4, 128), f(pages * SPEC.rows, 2, 128)
    picked = picks if picks is not None else sparse.prefill_selection(
        q, index, pos0 + jnp.arange(s), jnp.int32(0), SPEC)
    tile = min(sparse.PREFILL_TILE, s)
    counts = np.asarray(picked).reshape(s // tile, tile, 2, pages).any(
        1).sum(-1)
    assert check(np.asarray(picked), counts), counts
    want = sparse.sparse_prefill(q, kp, vp, picked, table, pos0, block=8,
                                 impl="reference")
    got = sparse.sparse_prefill(q, kp, vp, picked, table, pos0, block=8,
                                impl="kernel")
    assert np.abs(np.asarray(got - want)).max() < KERNEL_TOL
    assert np.abs(np.asarray(want)).max() > 0.1


# ------------------------------------------------------------------ the model
def test_full_forward_matches_the_reference(toy):
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 96), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(_ref_forward(params, tokens))
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.5


def test_the_layout_is_the_registrys_and_the_options_are_the_models(toy):
    model, params = toy
    assert model.pattern == "LDLDBDLD" and model.recurrent
    assert model.lightning_layers == (3, 4, 6) and model.decay_layers == 8
    assert (model.embed_scale, model.head_scale) == (12.0, 0.25)
    assert model.residual_scale == pytest.approx(1.4 / 8 ** 0.5)
    assert set(params["attn4"]) == {"q", "kv", "q_norm", "k_norm", "out"}
    assert set(params["mamba0"]) == {"in_proj", "q_norm", "k_norm", "norm",
                                     "out_proj"}
    assert "lm_head" in params
    with pytest.raises(ValueError, match="one each"):
        create_model("minicpm_sala", pattern="LDBD",
                     lightning_layers=(1, 2)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="unknown model"):
        create_model("minicpm_sala_2")


def test_published_widths_hold_2_820_545_280_parameters():
    model = create_model(PUBLISHED["program_model"],
                         **family.model_options(PUBLISHED))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(shapes) == family.param_count(PUBLISHED) == 2_820_545_280
    # a sparse layer ~52.5 M + its MLP 201.3 M; a lightning layer ~83.9 M
    assert count(shapes["attn0"]) == 52_429_056
    assert count(shapes["mamba2"]) == 83_886_464
    assert count(shapes["mlp1"]) == 201_326_592


def test_a_slots_caches_at_published_widths():
    """12.58 MB of lightning state a slot; 2,048 B of K and V and 64 B of
    compressed keys a cached token."""
    model = create_model(PUBLISHED["program_model"],
                         **family.model_options(PUBLISHED))
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 64, 2))
    flat = jax.tree_util.tree_flatten_with_path(pool)[0]
    size = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize
    state = sum(size(a) for p, a in flat if leaf_kind(p) == "state")
    assert state // 2 == 6 * family.ssm_state_bytes(PUBLISHED) == 12_582_912
    shapes = {"/".join(str(k.key) for k in p): a.shape for p, a in flat}
    assert shapes["attn0/cached_key"] == (9, 64, 256)
    assert shapes["attn0/cached_index"] == (9, 4, 256)
    assert shapes["attn0/sparse_stats"] == (2, 2)
    assert "mamba2/conv_state" not in shapes
    served = sum(2 * int(np.prod(a.shape)) for p, a in flat   # bf16
                 if leaf_kind(p) == "pages" and a.ndim == 3)
    assert served / (9 * 64) == 2048 + 64


# -------------------------------------------------------------- the engine
@pytest.mark.parametrize("prompt_len", [5, 16, 37, 70])
def test_chunks_then_decode_match_the_reference(toy, engine, prompt_len):
    """A prompt in 16-token chunks through the slot's own state and table,
    then 10 decode steps through `sparse_select` + `sparse_walk`: at 70
    tokens the switch to the sparse branch lies inside the third chunk."""
    model, params = toy
    rng = np.random.default_rng(prompt_len)
    seq = rng.integers(1, 96, prompt_len).tolist()
    slot = admit(engine, seq, max_positions=12)
    assert engine.context_len(slot) == prompt_len
    got, toks = decode(engine, slot, 10)
    want = ref_logits(params, seq + toks)[prompt_len - 1:]
    assert np.abs(got - want).max() < TOL
    engine.release(slot)


def test_two_slots_dense_and_sparse_share_a_step(toy, engine):
    model, params = toy
    rng = np.random.default_rng(8)
    seqs = [rng.integers(1, 96, n).tolist() for n in (12, 61)]
    slots = [admit(engine, s, max_positions=8) for s in seqs]
    logits = {s: [np.asarray(engine._last_logits[s])] for s in slots}
    toks = {s: [] for s in slots}
    for _ in range(6):
        out = engine.step_burst()
        assert engine.last_burst_sparse[2] == 1      # one slot past 32
        walked, held, _ = engine.last_burst_sparse
        assert walked < held
        for s in slots:
            toks[s].append(int(out[0, s]))
            logits[s].append(np.asarray(engine._last_logits[s]))
    for s, seq in zip(slots, seqs):
        want = ref_logits(params, seq + toks[s])[len(seq) - 1:]
        assert np.abs(np.stack(logits[s]) - want).max() < TOL
        engine.release(s)


def test_prefill_in_one_call_and_in_chunks_agree(toy):
    """The same 53 tokens through the flat cache in one call (the plain
    jax.numpy form) and through pages in chunks of 16."""
    model, params = toy
    seq = np.random.default_rng(3).integers(1, 96, 53).tolist()
    cache = make_cache(model, 1, 56)
    _, whole = decode_apply(model, params, cache,
                            jnp.asarray([seq + [0, 0, 0]], jnp.int32))
    chunked = make_engine(model, params)
    slot = admit(chunked, seq, max_positions=8)
    got = np.asarray(chunked._last_logits[slot])
    assert np.abs(got - np.asarray(whole)[0, 52]).max() < TOL
    assert np.abs(got - ref_logits(params, seq)[-1]).max() < TOL


def test_what_needs_a_snapshot_stays_refused_with_its_reason(toy, engine):
    model, params = toy
    for option, value, why in (
            ("prefix_cache", True, "without the state at the prefix's end"),
            ("spec_decode", True, "cannot be rolled back out of the state")):
        with pytest.raises(ValueError, match="refused for a model with "
                                             "recurrent state") as e:
            make_engine(model, params, **{option: value})
        assert why in str(e.value)
    slot = admit(engine, [3, 4, 5], max_positions=4)
    with pytest.raises(ValueError, match="fork is refused"):
        engine.fork(slot)
    engine.release(slot)
    with pytest.raises(ValueError, match="exceeds the largest prompt"):
        make_engine(model, params, prefill_chunk=32)


def test_a_preempted_long_prompt_is_readmitted_through_chunks(toy):
    """A pool too small for two long requests: the younger is preempted
    when the older grows, and comes back as prompt + tokens so far through
    the same chunks, to the same tokens."""
    model, params = toy
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 96, n).tolist() for n in (60, 58)]

    def serve(num_blocks):
        eng = make_engine(model, params, decode_burst=4,
                          max_blocks_per_slot=12, num_blocks=num_blocks)
        sched = Scheduler(eng, max_queue=8)
        for rid, p in enumerate(prompts):
            sched.submit(Request(rid=rid, prompt=p, max_new_tokens=24,
                                 seed=rid))
        done = []
        while not sched.idle:
            done += sched.step()
        return eng, {c.rid: c for c in done}

    roomy, want = serve(40)
    tight, got = serve(1 + 19)
    assert roomy.preemptions == 0 and tight.preemptions > 0
    for rid in (0, 1):
        assert got[rid].status == "length"
        assert got[rid].tokens == want[rid].tokens
    chunks = tight.ssm_scan_tokens
    assert chunks > sum(len(p) for p in prompts)    # the re-prefill's too


def test_a_cap_on_chunks_a_tick_runs_prompts_in_order(toy):
    """`prefill_chunks_per_tick` 2: three prompts of 3-5 chunks admitted in
    one tick take 2 chunk forwards a tick, the oldest first to its end,
    where the default runs one for every mid-prefill slot (3 a tick); the
    tokens are the same."""
    model, params = toy
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 96, n).tolist() for n in (70, 40, 55)]

    def serve(**kw):
        tracer = TraceRecorder(max_events=1 << 14)
        eng = make_engine(model, params, decode_burst=2, **kw)
        eng.set_tracer(tracer)
        sched = Scheduler(eng, max_queue=8, tracer=tracer)
        for rid, p in enumerate(prompts):
            sched.submit(Request(rid=rid, prompt=p, max_new_tokens=4,
                                 seed=rid))
        done = []
        while not sched.idle:
            done += sched.step()
        from perf.run import program_spans

        events = tracer.to_chrome_trace()["traceEvents"]
        ticks = [(a, b) for name, a, b in program_spans(tracer)
                 if name == "tick"]
        chunks = [(e["ts"] * 1e-6, e["tid"]) for e in events
                  if e.get("name") == "prefill_chunk"
                  and e.get("ph") in ("X", "B")]
        a_tick = [sum(1 for t, _ in chunks if a <= t < b) for a, b in ticks]
        return {c.rid: c.tokens for c in done}, a_tick, \
            [slot for _, slot in sorted(chunks)]

    plain, each, _ = serve()
    capped, two, order = serve(prefill_chunks_per_tick=2)
    assert capped == plain
    assert max(each) == 3 and max(two) == 2 and sum(two) == sum(each) == 12
    # oldest first, each to its end: the slots' chunks do not interleave
    assert [s for i, s in enumerate(order) if i == 0 or order[i - 1] != s] \
        == sorted(set(order), key=order.index) and len(set(order)) == 3


# ------------------------------------------- the programs, by kernel name
def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        if eqn.primitive.name in ("pjit", "jit") \
                and eqn.params.get("name") == "sparse_select":
            out.append("sparse_select")
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                out.extend(_pallas_names(inner))
    return out


def test_a_decode_step_and_a_chunk_hold_their_kernels_by_name(monkeypatch):
    """At the published depth and layout (toy widths, heads of 128): a
    decode step is 6 `ssm_step`, 2 `sparse_select` and 2 `sparse_walk` and
    nothing named `paged_decode`; a chunk 2 `sparse_prefill` (the scans are
    XLA under `ssm_scan`)."""
    from ddp_practice_tpu.utils import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = minicpm_sala_toy.config(
        head_dim=128, lightning_head_dim=128, layers_run=8,
        layers_published=[0, 1, 2, 3, 4, 5, 6, 7],
        mixer_types=["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])
    model = create_model("minicpm_sala", **family.model_options(cfg))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 4))

    def step(params, pool, toks, table, lengths):
        return decode_apply(model, params, pool, toks, page_table=table,
                            kv_lengths=lengths)

    names = _pallas_names(jax.make_jaxpr(step)(
        params, pool, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros((4, 6), jnp.int32), jnp.zeros((4,), jnp.int32)).jaxpr)
    assert sorted(names) == ["sparse_select"] * 2 + ["sparse_walk"] * 2 \
        + ["ssm_step"] * 6
    assert not any("paged_decode" in n for n in names)

    def chunk(params, pool, toks, table, pos0, real):
        one = jax.tree_util.tree_map_with_path(
            lambda p, a: a[:1] if leaf_kind(p) in ("state", "slots") else a,
            pool)
        return decode_apply(model, params, one, toks, page_table=table,
                            kv_lengths=pos0, real_lengths=real)

    names = _pallas_names(jax.make_jaxpr(chunk)(
        params, pool, jnp.zeros((1, 16), jnp.int32),
        jnp.zeros((1, 6), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 16, jnp.int32)).jaxpr)
    assert sorted(names) == ["sparse_prefill"] * 2


def test_the_scopes_are_in_the_op_paths(toy):
    """The program's side of perf/lib/scopes.py: `mamba{i}/ssm_step` and
    `attn{i}/sparse_select`, `attn{i}/sparse_walk` in a decode step,
    `mamba{i}/ssm_scan` and `attn{i}/sparse_prefill` in a chunk."""
    model, params = toy
    paths = lambda lowered: set(re.findall(
        r'op_name="([^"]*)"', lowered.compile().as_text()))
    pool = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 2))
    step = jax.jit(lambda p, c: decode_apply(
        model, p, c, jnp.zeros((2, 1), jnp.int32),
        page_table=jnp.zeros((2, 4), jnp.int32),
        kv_lengths=jnp.zeros((2,), jnp.int32)))
    seen = paths(step.lower(params, pool))
    for want in ("/mamba0/ssm_step/", "/attn4/.*sparse_select/",
                 "/attn4/.*sparse_walk/"):
        assert any(re.search(want, p) for p in seen), want
    one = jax.eval_shape(lambda: make_paged_cache(model, 9, 8, 1))
    fill = jax.jit(lambda p, c: decode_apply(
        model, p, c, jnp.zeros((1, 16), jnp.int32),
        page_table=jnp.zeros((1, 4), jnp.int32),
        kv_lengths=jnp.zeros((1,), jnp.int32),
        real_lengths=jnp.full((1,), 16, jnp.int32)))
    seen = paths(fill.lower(params, one))
    for want in ("/mamba0/ssm_scan/", "/attn4/.*sparse_prefill/"):
        assert any(re.search(want, p) for p in seen), want
    assert not any("ssm_step" in p or "sparse_walk" in p for p in seen)


def test_bf16_meets_a_tolerance_the_e4m3_control_fails(toy):
    """The served type against the float32 reference, at logit level: bf16
    weights and activations read under 0.1 on logits up to 1; the reference
    computed in e4m3 reads over it, and float32 under 1e-4 of it."""
    cfg = CFG
    model, params = toy
    served = create_model("minicpm_sala", policy=PrecisionPolicy.bf16(),
                          **family.model_options(cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 72), 0, 96)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = np.asarray(jax.jit(served.apply)({"params": half}, tokens),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.forward(half, tokens, cfg))
        control = np.asarray(reference.forward(half, tokens, cfg, "fp8"))
    rel = lambda a: np.sqrt(np.mean((a - want) ** 2) / np.mean(want ** 2))
    assert rel(got) < 0.03 < rel(control)


def test_scheduler_serves_it_and_the_spans_and_counters_say_what_ran(toy):
    """Through `Scheduler` on the normal path, with the recorder and the
    metrics plane attached: every `prefill_chunk` span carries the chunk's
    real positions (`take`) in its `bucket` (the rest is padding), every
    `decode_burst` what the sparse layers read, the counters add both up
    and the gauges read the pools."""
    model, params = toy
    tracer = TraceRecorder(max_events=1 << 14)
    engine = make_engine(model, params, decode_burst=2)
    engine.set_tracer(tracer)
    metrics = ServeMetrics()
    sched = Scheduler(engine, max_queue=16, tracer=tracer, metrics=metrics)
    rng = np.random.default_rng(2)
    lens = [5, 40, 13, 70, 33]
    for rid, n in enumerate(lens):
        sched.submit(Request(rid=rid, prompt=rng.integers(1, 96, n).tolist(),
                             max_new_tokens=6, seed=rid))
    done = []
    while not sched.idle:
        done += sched.step()
    assert sorted(c.rid for c in done) == list(range(5))
    assert all(c.status == "length" and len(c.tokens) == 6 for c in done)
    events = tracer.to_chrome_trace()["traceEvents"]
    spans = [e["args"] for e in events if e.get("name") == "prefill_chunk"
             and e.get("ph") in ("X", "B")]
    assert sum(a["take"] for a in spans) == sum(lens)
    assert len(spans) == sum(-(-n // 16) for n in lens)
    for a in spans:
        assert 0 < a["take"] <= 16 and a["bucket"] >= a["take"]
    bursts = [e["args"] for e in events
              if e.get("name") == "decode_burst" and "args" in e]
    assert bursts and all(
        0 < a["sparse_pages_walked"] <= a["sparse_pages_held"]
        and 0 <= a["sparse_slots"] <= 3 for a in bursts)
    assert any(a["sparse_pages_walked"] < a["sparse_pages_held"]
               for a in bursts)
    snap = metrics.registry.snapshot()
    assert snap["ssm_scan_tokens_total"] == sum(lens)
    assert snap["sparse_pages_walked_total"] == sum(
        a["sparse_pages_walked"] for a in bursts)
    assert snap["sparse_pages_held_total"] == sum(
        a["sparse_pages_held"] for a in bursts)
    # 37 blocks x 4 rows x 2 KV heads x 16 x 4 B, one sparse layer
    assert snap["index_cache_bytes"] == engine.index_cache_bytes \
        == 37 * 4 * 32 * 4
    assert snap["ssm_state_bytes"] == 3 * 3 * 4 * 16 * 16 * 4
