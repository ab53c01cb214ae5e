"""The SmallThinker layout of `HybridLM` (window attention with rotary beside
global attention with no positional embedding, ReGLU experts routed on the
attention's normed input) against the plain reference
(perf/reference/smallthinker.py), at a small size on the CPU: a window of 8,
pages of 4, chunks of 8, two periods G W W W, 8 experts of 3. The cache spec
(a window group beside the global one: its own pool, allocator and table, the
pages behind a slot's window given back), `window_prefill` in interpret mode
and `window_walk` against plain `jax.numpy`, the experts' activation, the
refusals with their reasons, and the tolerance a bf16 run meets and the
controls fail."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import smallthinker_toy  # noqa: E402
from ddp_practice_tpu.config import PrecisionPolicy  # noqa: E402
from ddp_practice_tpu.models import create_model  # noqa: E402
from ddp_practice_tpu.ops import moe, window_attention as wa  # noqa: E402
from ddp_practice_tpu.ops.decode_attention import (  # noqa: E402
    paged_attention_reference,
    paged_decode_attention,
)
from ddp_practice_tpu.serve import kv_pages  # noqa: E402
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine  # noqa: E402
from ddp_practice_tpu.serve.metrics import ServeMetrics  # noqa: E402
from ddp_practice_tpu.serve.scheduler import Request, Scheduler  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402
from perf.families import smallthinker as family  # noqa: E402
from perf.reference import smallthinker as reference  # noqa: E402

CFG = smallthinker_toy.config()
WINDOW, PAGE, CHUNK = CFG["sliding_window_size"], 4, 8
# a slot's most pages in the window group: ceil((8 + 8) / 4) + 1
BOUND = 5
# float32 program against a float32 reference at the highest precision, in
# another order of sums (5e-6 at the worst logit here; logits up to 3). A
# dropped window, a stale page, a router on the wrong input or an unrotated
# head reads 0.05 and more.
TOL = 1e-4


@pytest.fixture(scope="module")
def toy():
    return smallthinker_toy.model_and_params(CFG)


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
    opts = dict(max_slots=3, prompt_buckets=(4, 8), block_size=PAGE,
                decode_burst=1, max_blocks_per_slot=16, temperature=0.0,
                prefill_chunk=CHUNK)
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


# ------------------------------------------------------------ the kernels
def _pools(kvh=2, d=128, bs=8, blocks=40, mb=24, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, blocks))[:mb], jnp.int32)
    return f(blocks, bs, kvh * d), f(blocks, bs, kvh * d), table


@pytest.mark.parametrize("name, s, pos0, start, window, real", [
    ("global_from_zero", 16, 0, 0, wa.NO_WINDOW, None),
    ("global_deep_padded", 16, 40, 0, wa.NO_WINDOW, 9),
    ("window_begins_mid_page", 16, 44, 3, 20, None),
    ("window_two_tiles", 256, 8, 0, 50, 200),
    ("window_of_one_page_behind", 32, 100, 0, 9, 30),
    # PR 46: a step folds `pages_per_step` pages (8 of 8 tokens at tables
    # of 12 to 15 columns), so a walk of 11 or 14 pages is one whole step
    # and a part of one; 16 pages of 64 tokens at the 240 columns, two whole
    # steps and two parts for 37 pages
    ("walk_of_a_step_and_three_pages", 16, 72, 0, wa.NO_WINDOW, None),
    ("window_edge_mid_page_in_a_whole_step", 16, 100, 0, 90, None),
    ("start_inside_a_page_of_a_whole_step", 16, 100, 13, wa.NO_WINDOW, None),
    ("a_tile_wholly_past_the_real_rows", 384, 64, 0, 90, 130),
    ("global_at_a_table_of_240_columns", 16, 12300, 10005, wa.NO_WINDOW, 12),
])
def test_the_prefill_kernel_is_the_mask_over_the_whole_span(
        name, s, pos0, start, window, real):
    """`window_prefill` in interpret mode against the gathered span under
    the mask: a global layer (first key `start`), a window whose first key
    lies inside a page (44 - 20 + 1 = 25 = page 3, row 1), two tiles of 128
    rows each with its own first column, and padding past `real`; walks
    that are no whole number of steps, edges inside whole steps, a tile that
    walks nothing, and the cell's 240 table columns."""
    if "240" in name:    # the cell's table: pages of 64 tokens, 16 a step
        k, v, table = _pools(bs=64, blocks=248, mb=240)
    else:
        mb = (pos0 + s) // 8 + 1
        k, v, table = _pools(blocks=max(40, mb + 8), mb=mb)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(s, 2, 3, 128)),
                    jnp.float32)
    kw = dict(start=start, window=window, real=real)
    want = wa.window_prefill(q, k, v, table, pos0, impl="reference", **kw)
    got = wa.window_prefill(q, k, v, table, pos0, impl="kernel", **kw)
    rows = s if real is None else real
    assert np.abs(np.asarray(got - want))[:rows].max() < 2e-5
    assert np.isfinite(np.asarray(got)).all()


def test_a_tile_walks_its_window_and_no_page_behind_it():
    """The kernel's grid by the rule: a tile of 128 rows under a window of
    4,096 on pages of 64 walks 67 columns whatever the context, a global
    layer's every column up to its own, and a tile past the real tokens
    none."""
    lo, cnt = wa.tile_walks(
        jnp.int32(12300), jnp.int32(0), jnp.int32(4096), jnp.int32(300),
        tiles=16, tile=128, block=64, columns=240)
    assert cnt[:3].tolist() == [67, 67, 67] and not cnt[3:].any()
    assert lo[:3].tolist() == [(12300 + 128 * t - 4095) // 64
                               for t in range(3)]
    lo, cnt = wa.tile_walks(
        jnp.int32(12288), jnp.int32(0), jnp.int32(wa.NO_WINDOW),
        jnp.int32(2048), tiles=16, tile=128, block=64, columns=240)
    assert not lo.any() and cnt.tolist() == [
        (12288 + 128 * t + 127) // 64 + 1 for t in range(16)]


    # the host's count (serve/engine.py _chunk_pages) by the same rule
    args = (12300, 0, 4096, 300)
    for a, b in zip(wa.tile_walks(*args, tiles=16, tile=128, block=64,
                                  columns=240, xp=np),
                    wa.tile_walks(*map(jnp.int32, args), tiles=16, tile=128,
                                  block=64, columns=240)):
        assert a.tolist() == b.tolist()


def test_a_step_folds_what_the_shapes_allow():
    """`pages_per_step`, the one place the key tile comes from: 8 pages =
    512 keys at the window cell's shapes (896 rows, pages of 64 tokens of
    4 x 128 lanes, 240 columns), a power of two whatever the shapes, never
    more than the table holds, fewer where a tile has more rows, a page
    more tokens or a row more lanes; the walk's last step computes a
    quarter of a step at a time, of at least a lane tile of keys."""
    assert wa.pages_per_step(64, 512, 896, 240) == 8
    assert wa.pages_per_step(64, 512, 896, 5) == 4
    assert wa.pages_per_step(64, 512, 2048, 240) == 4
    assert wa.pages_per_step(16, 512, 896, 240) == 32
    assert wa.pages_per_step(64, 512, 256, 76) == 16
    assert wa.pages_per_step(64, 4096, 256, 76) == 2
    assert wa.pages_per_step(8, 256, 48, 7, 4) == 4
    assert wa.pages_per_step(8, 256, 48, 1, 4) == 1
    for shape in [(64, 512, 896, 240), (8, 256, 384, 33, 4),
                  (32, 512, 4096, 100), (128, 128, 128, 1000)]:
        p = wa.pages_per_step(*shape)
        assert p & (p - 1) == 0 and 1 <= p <= shape[3], (shape, p)
    assert wa._tail_pages(16, 64) == 4 and wa._tail_pages(8, 64) == 2
    assert wa._tail_pages(2, 64) == 2 and wa._tail_pages(16, 8) == 16


def _call(pages, s, pos0, start, window, real, mb, **kw):
    k, v, table = _pools(blocks=mb + 8, mb=mb)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(s, 2, 3, 128)),
                    jnp.float32)
    i32 = jnp.int32
    got = wa._prefill_call(q, k, v, table, i32(pos0), i32(start), i32(window),
                           i32(real), block=8, pages=pages, **kw)
    return got, (q, k, v, table)


@pytest.mark.parametrize("pages", [1, 2, 4, 8, 16, 32])
def test_the_prefill_kernel_at_every_count_of_pages_a_step(pages):
    """Every power of two the rule can return, on pages of 8 tokens (score
    lane blocks of 8 to 128 keys; from 16 pages up the last step computes
    its pages a part at a time): a window whose edge and whose diagonal lie
    inside steps, a walk of 29 pages that no count but 1 divides."""
    s, pos0, start, window, real, mb = 128, 200, 3, 100, 117, 48
    got, (q, k, v, table) = _call(pages, s, pos0, start, window, real, mb)
    want = wa._prefill_reference(q, k, v, table, pos0, start, window)
    assert np.abs(np.asarray(got - want))[:real].max() < 2e-5
    assert np.isfinite(np.asarray(got)).all()
    counts = wa.walk_counts(pos0, start, window, real, s=s, block=8,
                            columns=mb, pages=pages)
    part = wa._tail_pages(pages, 8)
    assert counts["walked"] == 29 and counts["steps"] == -(-29 // pages)
    assert counts["executed"] == 29 // pages * pages \
        + -(-(29 % pages) // part) * part
    assert counts == _brute_counts(pos0, start, window, real, s=s, block=8,
                                   columns=mb, pages=pages)


@pytest.mark.parametrize("name, pages, s, pos0, start, window, real, clear", [
    ("window_deep", 4, 128, 900, 0, 403, 128, 7),
    ("window_two_tiles_ragged", 2, 256, 520, 0, 300, 200, 20),
    ("global_from_a_start", 8, 128, 1000, 70, wa.NO_WINDOW, 128, 13),
    ("global_short", 16, 128, 128, 0, wa.NO_WINDOW, 128, 1),
])
def test_a_step_without_an_edge_adds_no_mask(name, pages, s, pos0, start,
                                             window, real, clear):
    """The maskless body is taken exactly where no row's mask can cut a key
    of the step: the same call with the mask added in EVERY step gives the
    same output to the bit (a maskless step over an edge would keep a cut
    score, a masked step adds zeros), and the host's count of such steps is
    the brute-force one, over the rows and keys of every step."""
    mb = (pos0 + s) // 8 + 1
    got, _ = _call(pages, s, pos0, start, window, real, mb)
    all_masked, _ = _call(pages, s, pos0, start, window, real, mb,
                          mask_all=True)
    assert np.array_equal(np.asarray(got), np.asarray(all_masked))
    counts = wa.walk_counts(pos0, start, window, real, s=s, block=8,
                            columns=mb, pages=pages)
    assert counts == _brute_counts(pos0, start, window, real, s=s, block=8,
                                   columns=mb, pages=pages)
    assert counts["clear"] == clear


@pytest.mark.parametrize("pages, s, pos0, window, real", [
    (4, 256, 520, 300, 200),        # whole steps, a part, a tile past real
    (16, 128, 200, wa.NO_WINDOW, 128),     # one step a tile, split in parts
    (2, 384, 64, 90, 130)])         # two live tiles, the third walks none
def test_the_prefill_kernel_waits_for_what_it_reads(pages, s, pos0, window,
                                                    real, monkeypatch):
    """The kernel under the TPU interpreter, which lands a copy's bytes only
    when it is WAITED for and watches for races: a step's pages read before
    their wait, a buffer refilled while a head still reads it, or a tile
    that takes the copies its predecessor did not start, shows here and
    nowhere else on the CPU (plain interpret mode copies at `start`)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pallas_call = pl.pallas_call

    def on_wait(*args, interpret, **kw):
        assert interpret is True
        return pallas_call(*args, **kw, interpret=pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))

    monkeypatch.setattr(pl, "pallas_call", on_wait)
    jax.clear_caches()
    mb = (pos0 + s) // 8 + 1
    got, (q, k, v, table) = _call(pages, s, pos0, 0, window, real, mb)
    want = wa._prefill_reference(q, k, v, table, pos0, 0, window)
    assert np.abs(np.asarray(got - want))[:real].max() < 2e-5
    assert not interpret_pallas_call.races.races_found
    jax.clear_caches()


def _brute_counts(pos0, start, window, real, *, s, block, columns, pages):
    """`walk_counts` from the mask itself: a tile walks the pages that hold
    a key some row of it attends, `pages` a step; a whole step is clear
    where every row attends every key of it; the last step computes what is
    left in parts."""
    tile, part = min(wa.WINDOW_TILE, s), wa._tail_pages(pages, block)
    keys = np.arange(columns * block)
    out = dict(steps=0, clear=0, walked=0, executed=0)
    for first in range(pos0, pos0 + min(s, real), tile):
        rows = first + np.arange(tile)[:, None]
        seen = (keys <= rows) & (keys >= start) & (keys > rows - window)
        held = np.unique(keys[seen.any(0)] // block)
        assert held.tolist() == list(range(held[0], held[-1] + 1))
        out["walked"] += len(held)
        for col in range(held[0], held[-1] + 1, pages):
            left = held[-1] + 1 - col
            out["steps"] += 1
            if left >= pages:
                out["executed"] += pages
                out["clear"] += bool(
                    seen[:, col * block:(col + pages) * block].all())
            else:
                out["executed"] += -(-left // part) * part
    return out


@pytest.mark.parametrize("window", [5, 16, 1000])
def test_the_window_walk_is_the_paged_walk_from_a_later_start(window):
    """A decode step of a window layer: `_paged_walk_kernel` (interpret
    mode) from `window_start`, against the gathered span under the mask; a
    window that begins mid-page, at a page's head, and before the sequence
    does."""
    k, v, _ = _pools(kvh=2, blocks=60)
    rng = np.random.default_rng(2)
    tables = jnp.asarray(rng.integers(1, 60, (3, 12)), jnp.int32)
    lengths = jnp.asarray([7, 38, 80], jnp.int32)
    begin = jnp.asarray([0, 2, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, 6 * 128)), jnp.float32)
    first = wa.window_start(lengths, begin, window)
    assert first.tolist() == [max(int(n) + 1 - window, int(b))
                              for n, b in zip(lengths, begin)]
    want = paged_attention_reference(q, k, v, tables, lengths, first,
                                     n_heads=6, n_kv_heads=2)
    got = paged_decode_attention(q, k, v, tables, lengths, first, n_heads=6,
                                 n_kv_heads=2, impl="kernel",
                                 name="window_walk")
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    # the plain form itself: position s counts iff first <= s <= length
    span = np.asarray(jnp.take(k, tables[2], axis=0)).reshape(96, 2, 128)
    vals = np.asarray(jnp.take(v, tables[2], axis=0)).reshape(96, 2, 128)
    lo = int(first[2])
    qh = np.asarray(q)[2, 0].reshape(2, 3, 128)
    scores = np.einsum("ngd,snd->ngs", qh, span[lo:81]) / np.sqrt(128.0)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    plain = np.einsum("ngs,snd->ngd", probs, vals[lo:81]).reshape(-1)
    assert np.abs(np.asarray(want)[2, 0] - plain).max() < 2e-5


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_the_expert_kernel_takes_its_activation(activation):
    """`moe_gmm_glu` in interpret mode and its plain form against a dense
    loop over the tiles: SwiGLU as it was, ReGLU beside it."""
    rng = np.random.default_rng(3)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, wg, wu, wd = f(64, 128), f(4, 128, 128), f(4, 128, 128), f(4, 128, 128)
    tile_expert = jnp.asarray([0, 2, 2, 3], jnp.int32)
    used = jnp.asarray([3], jnp.int32)
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[activation]
    want = np.zeros((64, 128), np.float32)
    for t in range(3):
        rows, e = np.asarray(x[16 * t:16 * t + 16]), int(tile_expert[t])
        want[16 * t:16 * t + 16] = (
            np.asarray(act(rows @ wg[e])) * (rows @ np.asarray(wu[e]))
        ) @ np.asarray(wd[e])
    for fn in (moe.expert_glu_tiles_reference, moe.expert_glu_tiles_kernel):
        got = fn(x, wg, wu, wd, tile_expert, used, tile=16,
                 activation=activation)
        assert np.abs(np.asarray(got) - want).max() < 2e-3, fn.__name__
    with pytest.raises(ValueError, match="want 'silu' or 'relu'"):
        moe.expert_glu_tiles_reference(x, wg, wu, wd, tile_expert, used,
                                       tile=16, activation="gelu")


# --------------------------------------------------------------- the model
def test_full_forward_matches_the_reference(toy):
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
        want = np.asarray(reference.forward(params, tokens, CFG))
    assert np.abs(got - want).max() < TOL


def test_the_router_reads_the_attentions_input_and_not_its_own(toy):
    """What `assumed.router_input` states: with the router handed the
    experts' own normed input the logits move by far more than rounding."""
    model, params = toy
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 24), 0, 96)
    got = np.asarray(model.apply({"params": params}, tokens))
    real = moe.GatedMoE.__call__

    def own_input(self, x, *, decode=False, router_input=None):
        return real(self, x, decode=decode)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe.GatedMoE, "__call__", own_input)
        other = np.asarray(model.apply({"params": params}, tokens))
    assert np.abs(got - other).max() > 0.05


def test_the_layout_is_the_registrys_and_the_options_are_the_models(toy):
    model, _ = toy
    assert model.pattern == "*RWRWRWR" * 2 and not model.recurrent
    assert model.pos_emb == "rope" and model.attn_prefill == "kernel"
    spec = kv_pages.cache_spec(model)
    assert spec == kv_pages.CacheSpec(8, tuple(
        f"attn{i}" for i in (2, 4, 6, 10, 12, 14)))
    assert spec.window_pages(PAGE, CHUNK) == BOUND
    assert kv_pages.CacheSpec(4096, ("attn2",)).window_pages(64, 2048) == 97
    for bad, why in (({"pattern": "RW"}, "routes on the input of the mixer"),
                     ({"pattern": "WR", "window": 0}, "attends a window"),
                     ({"pattern": "WR", "pos_emb": "none"}, "rotate q and k"),
                     ({"pattern": "XR"}, "'R', 'D', '\\*', 'A', 'W', 'B'")):
        with pytest.raises(ValueError, match=why):
            create_model("smallthinker", **bad).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------------------ the cache spec
def test_a_window_leaf_has_its_groups_lead_dimension(toy):
    model, _ = toy
    cache = kv_pages.make_paged_cache(model, 21, PAGE, max_slots=3,
                                      window_blocks=9)
    leads = {}
    for path, a in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = kv_pages.leaf_name(path)
        if name in ("cached_key", "cached_value"):
            leads.setdefault(kv_pages.cache_spec(model).group(path),
                             set()).add(
                a.shape)
        if name == kv_pages.WINDOW_STATS_LEAF:
            assert a.shape == (3, 2) and kv_pages.per_slot(path)
    assert leads == {"global": {(21, PAGE, 32)}, "window": {(9, PAGE, 32)}}
    # a model without the method has the global group alone
    assert kv_pages.cache_spec(object()) == kv_pages.CacheSpec()


def test_a_page_group_gives_back_what_lies_behind():
    g = kv_pages.PageGroup(num_blocks=8, max_slots=2, columns=10)
    g.extend(0, g.blocks.alloc(4))
    assert g.held(0) == 4 and g.table[0, :4].all()
    assert g.trim(0, 0) == 0 and g.trim(0, 2) == 2 and g.freed == 2
    assert g.table[0, :2].tolist() == [0, 0] and g.held(0) == 2
    assert g.trim(0, 1) == 0            # nothing behind what went already
    g.extend(0, g.blocks.alloc(3))
    assert (g.first[0], g.end[0], g.blocks.num_free) == (2, 7, 2)
    assert g.trim(0, 99) == 5 and g.held(0) == 0      # never past its end
    g.extend(1, g.blocks.alloc(2))
    g.clear(1)
    assert g.blocks.num_used == 0 and not g.table.any()


@pytest.mark.parametrize("prompt_len", [5, 13, 16, 17, 41])
def test_chunks_then_decode_match_the_reference(toy, engine, prompt_len):
    """A prompt in 8-token chunks through both groups' tables, then 10
    decode steps: contexts under the window, under and at window + chunk
    = 16, past it, and far past it (pages go back inside the prefill)."""
    model, params = toy
    rng = np.random.default_rng(prompt_len)
    seq = rng.integers(1, 96, prompt_len).tolist()
    freed = engine.window_pages_freed
    slot = admit(engine, seq, max_positions=12)
    assert engine.context_len(slot) == prompt_len
    got, toks = decode(engine, slot, 10)
    want = ref_logits(params, seq + toks)[prompt_len - 1:]
    assert np.abs(got - want).max() < TOL
    # pages went back iff the context passed the window by a page
    gone = engine.window_pages_freed - freed
    assert gone == max(0, prompt_len + 9 - WINDOW + 1) // PAGE
    assert engine.wgroup.held(slot) <= BOUND
    assert engine.pages_held() == {
        "global": -(-(prompt_len + 10) // PAGE),
        "window": engine.wgroup.held(slot)}
    engine.release(slot)
    assert engine.pages_held() == {"global": 0, "window": 0}


def test_the_same_logits_with_nothing_given_back(toy, monkeypatch):
    """The model with a window group whose pages all stay (`trim` does
    nothing, the pool backs every column) reads what the engine reads when
    the pages behind the window go back: nothing attends them."""
    model, params = toy
    seq = np.random.default_rng(5).integers(1, 96, 37).tolist()
    giving = make_engine(model, params)
    got, toks = decode(giving, admit(giving, seq, max_positions=14), 12)
    assert giving.window_pages_freed > 0
    monkeypatch.setattr(kv_pages.PageGroup, "trim", lambda *a: 0)
    monkeypatch.setattr(kv_pages.CacheSpec, "window_pages",
                        lambda *a: 16)
    keeping = make_engine(model, params)
    kept, same = decode(keeping, admit(keeping, seq, max_positions=14), 12)
    assert keeping.window_pages_freed == 0 and keeping.wgroup.held(0) == 13
    assert same == toks and np.abs(got - kept).max() < 1e-5


def test_a_long_decode_never_holds_more_than_the_bound(toy):
    """80 decode steps in bursts of 4 over two slots: the window group's
    pages a slot stay at or under ceil((w + chunk) / page) + 1 while the
    global group's grow with the context, the counters and the walks say
    what went back, and the logits stay the reference's."""
    model, params = toy
    eng = make_engine(model, params, decode_burst=4, max_blocks_per_slot=32)
    rng = np.random.default_rng(6)
    seqs = [rng.integers(1, 96, n).tolist() for n in (6, 30)]
    slots = [admit(eng, s, max_positions=84) for s in seqs]
    toks = {s: [] for s in slots}
    for step in range(20):
        out = eng.step_burst()
        for s in slots:
            toks[s] += [int(t) for t in out[:, s]]
            assert eng.wgroup.held(s) <= BOUND
        near, whole = eng.last_burst_window
        assert 0 < near <= whole
    assert near < whole            # both contexts are past the window by now
    held = eng.pages_held()
    assert held["global"] == sum(-(-(len(q) + 80) // PAGE) for q in seqs)
    assert held["window"] <= 2 * BOUND
    assert eng.pages_held(a_slot=True) == {
        "global": -(-(30 + 80) // PAGE),
        "window": max(eng.wgroup.held(s) for s in slots)}
    # every page behind a window went back, and no other
    assert eng.window_pages_freed == sum(
        (len(q) + 80 - 4 - WINDOW + 1) // PAGE for q in seqs)
    assert eng.window_pages_walked < eng.window_pages_whole
    for s, seq in zip(slots, seqs):
        want = ref_logits(params, (seq + toks[s])[:64])
        assert toks[s][:64 - len(seq)] == [
            int(t) for t in want[len(seq) - 1:-1].argmax(-1)]


def test_release_and_preempt_return_both_groups_pages(toy):
    model, params = toy
    eng = make_engine(model, params)
    seq = np.random.default_rng(7).integers(1, 96, 21).tolist()
    a, b = admit(eng, seq, max_positions=8), admit(eng, seq[:9],
                                                   max_positions=8)
    eng.step_burst()
    used = (eng.blocks.num_used, eng.wgroup.blocks.num_used)
    assert used == (6 + 3, eng.wgroup.held(a) + eng.wgroup.held(b))
    # 22 positions: the first three columns lie behind the window of 8
    assert eng.wgroup.held(a) == 3 and eng.wgroup.table[a, :3].sum() == 0
    eng.preempt(b)
    assert eng.take_preempted() == [b]
    assert (eng.blocks.num_used, eng.wgroup.blocks.num_used) == (6, 3)
    eng.release(a)
    assert (eng.blocks.num_used, eng.wgroup.blocks.num_used) == (0, 0)
    assert not eng.wgroup.table.any() and not eng._pt.any()


def test_admission_counts_pages_a_group(toy):
    """A window pool of two slots' bound: the third admission waits for
    that group's pages though the global group has room, and growth under
    pressure preempts the youngest, whose pages of BOTH groups return."""
    model, params = toy
    eng = make_engine(model, params, window_blocks=1 + 2 * BOUND,
                      decode_burst=4)
    seq = np.random.default_rng(8).integers(1, 96, 30).tolist()
    slots = [admit(eng, seq, max_positions=12) for _ in range(2)]
    assert eng.wgroup.blocks.num_free == 2 * BOUND - 8
    assert eng.admit_gate(30, 12) == "later"          # 2 + 1 pages wanted
    assert eng.blocks.num_free > 3
    assert not eng.preempt_headroom([], 30)
    assert eng.preempt_headroom(slots[1:], 30)
    eng.release(slots[1])
    assert eng.admit_gate(30, 12) == "ok"
    with pytest.raises(ValueError, match="cannot hold one slot's 5 pages"):
        make_engine(model, params, window_blocks=BOUND)


def test_what_needs_a_page_behind_the_window_is_refused(toy, engine):
    model, params = toy
    for option, value, why in (
            ("prefix_cache", True, "a published prefix has none to share"),
            ("spec_decode", True, "cannot be rewound over pages the window")):
        with pytest.raises(ValueError, match="refused for a model with a "
                                             "window page group") as e:
            make_engine(model, params, **{option: value})
        assert why in str(e.value)
    with pytest.raises(ValueError, match="is admitted in chunks"):
        make_engine(model, params, prefill_chunk=0)
    slot = admit(engine, [3, 4, 5], max_positions=4)
    with pytest.raises(ValueError, match="fork is refused for a model with "
                                         "a window page group") as e:
        engine.fork(slot)
    assert "gives back as it decodes on" in str(e.value)
    engine.release(slot)


# -------------------------------------------------------------- controls
def _rel(a, want):
    return np.sqrt(np.mean((a - want) ** 2) / np.mean(want ** 2))


def test_bf16_meets_a_tolerance_the_e4m3_control_fails(toy):
    """The served type against the float32 reference, at logit level; the
    reference computed in e4m3 reads over it."""
    _, params = toy
    served, _ = smallthinker_toy.model_and_params(
        CFG, policy=PrecisionPolicy.bf16())
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 40), 0, 96)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = np.asarray(jax.jit(served.apply)({"params": half}, tokens),
                     np.float32)
    # the same values in float32: `ref_logits` keeps its one compile
    rounded = jax.tree.map(lambda a: a.astype(jnp.float32), half)
    want = ref_logits(rounded, tokens[0].tolist())
    with jax.default_matmul_precision("highest"):
        control = np.asarray(
            reference.forward(rounded, tokens[:1], CFG, "fp8"))
    assert _rel(got[0], want) < 0.05 < _rel(control[0], want)


def test_the_program_without_its_window_mask_fails_too(toy):
    """The no-window control: every layer attending every key (rotary kept)
    leaves the reference by far more than the served type does."""
    _, params = toy
    unmasked, _ = smallthinker_toy.model_and_params(CFG, window=1 << 20)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 40), 0, 96)
    got = np.asarray(unmasked.apply({"params": params}, tokens))
    assert _rel(got[0], ref_logits(params, tokens[0].tolist())) > 0.05


def test_a_page_read_after_it_went_back_is_seen(toy, monkeypatch):
    """The stale-page control: a decode walk that starts at the sequence's
    first position in a window layer too reads the garbage block where the
    table's columns went back, and the logits leave the reference's."""
    model, params = toy
    seq = np.random.default_rng(9).integers(1, 96, 37).tolist()
    monkeypatch.setattr(
        wa, "window_start", lambda lengths, attn_start, window:
        jnp.zeros_like(lengths) if attn_start is None else attn_start)
    eng = make_engine(model, params)
    got, toks = decode(eng, admit(eng, seq, max_positions=8), 6)
    want = ref_logits(params, seq + toks)[len(seq) - 1:]
    assert np.abs(got[0] - want[0]).max() < TOL      # the chunks were sound
    assert np.abs(got[1:] - want[1:]).max() > 0.05


# ------------------------------------------------------------ the scheduler
def test_scheduler_serves_it_and_the_spans_and_counters_say_what_ran(toy):
    """Through `Scheduler` on the normal path, with the recorder and the
    metrics plane attached: every `prefill_chunk` and `decode_burst` span
    carries the pages walked by group and the window pages given back, the
    counters add them up and the gauges read the groups."""
    model, params = toy
    tracer = TraceRecorder(max_events=1 << 14)
    engine = make_engine(model, params, decode_burst=2)
    engine.set_tracer(tracer)
    metrics = ServeMetrics()
    sched = Scheduler(engine, max_queue=16, tracer=tracer, metrics=metrics)
    rng = np.random.default_rng(2)
    lens = [5, 40, 13, 27, 33]
    for rid, n in enumerate(lens):
        sched.submit(Request(rid=rid, prompt=rng.integers(1, 96, n).tolist(),
                             max_new_tokens=6, seed=rid))
    done, most = [], {"global": 0, "window": 0}
    while not sched.idle:
        done += sched.step()
        snap = metrics.registry.snapshot()
        for group in most:
            most[group] = max(most[group],
                              snap[f"kv_pages_held{{group={group}}}"])
    assert sorted(c.rid for c in done) == list(range(5))
    assert all(c.status == "length" and len(c.tokens) == 6 for c in done)
    assert most["window"] <= 3 * BOUND < most["global"]
    events = tracer.to_chrome_trace()["traceEvents"]
    chunks = [e["args"] for e in events if e.get("name") == "prefill_chunk"
              and e.get("ph") in ("X", "B")]
    assert len(chunks) == sum(-(-n // CHUNK) for n in lens)
    for a in chunks:
        assert 0 < a["window_pages"] <= 3 * a["global_pages"]
        # what the steps execute is the kernel's tests' to count, no span's
        assert "pages_executed" not in a and "steps_unmasked" not in a
        assert a["window_pages_freed"] == max(
            0, a["pos0"] - WINDOW + 1) // PAGE - max(
            0, a["pos0"] - CHUNK - WINDOW + 1) // PAGE
    bursts = [e["args"] for e in events
              if e.get("name") == "decode_burst" and "args" in e]
    assert bursts and all(
        0 < a["window_pages"] <= 3 * a["global_pages"] for a in bursts)
    snap = metrics.registry.snapshot()
    assert snap["kv_window_pages_freed_total"] == engine.window_pages_freed \
        == sum(a["window_pages_freed"] for a in chunks + bursts) > 0
    assert snap["window_pages_walked_total"] == sum(
        a["window_pages"] for a in bursts)
    assert snap["window_pages_walked_total"] \
        < snap["window_pages_whole_total"]
    assert snap["kv_pages_held{group=global}"] == 0 \
        == snap["kv_pages_held{group=window}"]
    assert snap["moe_rows_held_total"] == snap["moe_rows_routed_total"] > 0


@pytest.mark.parametrize("pos0, take, width", [
    (0, 8, 8), (8, 8, 8), (16, 3, 4), (24, 5, 8), (40, 8, 8)])
def test_a_chunks_span_counts_what_the_kernels_grid_runs(engine, pos0, take,
                                                         width):
    """`_chunk_pages` (the `prefill_chunk` span's attrs) against the count
    made from the mask itself: the pages a chunk's `window_prefill` calls
    walk, by group and over the layers of each (what their grid steps
    execute at the step the rule gives this engine's shapes is
    `walk_counts`', held to the same count here)."""
    m = engine.model
    columns = engine._pt.shape[1]
    pages = wa.pages_per_step(
        PAGE, m.kv_heads * m.head_dim,
        m.num_heads // m.kv_heads * min(width, 128), columns,
        4)                              # the toy's pools are float32
    assert pages == 16 == columns
    far, near = (_brute_counts(pos0, 0, w, take, s=width, block=PAGE,
                               columns=columns, pages=pages)
                 for w in (wa.NO_WINDOW, WINDOW))
    g, n = engine._global_layers, engine._window_layers
    assert (g, n) == (2, 6)
    assert engine._chunk_pages(pos0, take, width) == {
        "global_pages": g * far["walked"], "window_pages": n * near["walked"]}
    for brute, w in ((far, wa.NO_WINDOW), (near, WINDOW)):
        assert wa.walk_counts(pos0, 0, w, take, s=width, block=PAGE,
                              columns=columns, pages=pages) == brute


def test_the_cells_chunk_executes_what_the_longer_step_costs():
    """The counter at the cell's shapes (a 2,048-token chunk at position
    4,096, 8 pages a step, pages of 64, a window of 4,096, 240 columns), one
    KV head and layer: a window tile walks 66 pages in eight whole steps and
    a part of one, seven of them without a mask; a global tile all its
    pages; executed over walked says what the longer tile costs at the
    edges (nothing here: every walk is a whole number of 2-page parts)."""
    kw = dict(s=2048, block=64, columns=240, pages=8)
    near = wa.walk_counts(4096, 0, 4096, 2048, **kw)
    far = wa.walk_counts(4096, 0, wa.NO_WINDOW, 2048, **kw)
    assert near == _brute_counts(4096, 0, 4096, 2048, **kw)
    assert far == _brute_counts(4096, 0, wa.NO_WINDOW, 2048, **kw)
    assert near == {"steps": 144, "clear": 112, "walked": 1056,
                    "executed": 1056}
    assert far["walked"] == sum(66 + 2 * t for t in range(16)) == 1296
    assert far["steps"] == sum(-(-(66 + 2 * t) // 8) for t in range(16))
    assert far["clear"] == 168 - 16 and far["executed"] == 1296
    wide = wa.walk_counts(4096, 0, 4096, 2048, **dict(kw, pages=32))
    assert wide["executed"] == 1152 and wide["clear"] == 16


def test_published_widths_hold_3_966_937_600_parameters():
    published = smallthinker_toy.perf_config()
    model = create_model("smallthinker", **family.model_options(published))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == family.param_count(published) == 3_966_937_600
    assert model.pattern == "*RWRWRWR" * 2
    assert kv_pages.cache_spec(model).window_pages(64, 2048) == 97
