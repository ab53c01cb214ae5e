"""Pallas flash-attention kernel: numerics pinned to the dense reference.

Runs in interpret mode under the CPU test backend (same code path as the
compiled TPU kernel modulo Mosaic lowering). Forward and backward must
match dense attention, causal and non-causal, including bf16 inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.ops.attention import _attention, dot_product_attention
from ddp_practice_tpu.ops.flash_attention import flash_attention


def _qkv(b=2, s=256, h=2, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.fast
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    want = _attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_multiple_k_blocks():
    """seq > block size: the online-softmax accumulation crosses blocks."""
    q, k, v = _qkv(s=512, seed=1)
    want = _attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_dense(causal):
    q, k, v = _qkv(s=128, seed=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_bf16():
    q, k, v = _qkv(s=128, seed=3, dtype=jnp.bfloat16)
    want = _attention(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_causal_cross_lengths():
    """seq_q != seq_k causal uses bottom-right alignment, like _attention."""
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    want = _attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_dispatch_via_impl_flag():
    q, k, v = _qkv(s=128, seed=4)
    got = dot_product_attention(q, k, v, impl="flash")
    want = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_causal_rejects_longer_queries():
    """seq_q > seq_k causal has no sound bottom-right alignment: reject."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    with pytest.raises(ValueError, match="seq_q <= seq_k"):
        flash_attention(q, k, k, causal=True)


def test_block_sizes_fit_down_to_divisors():
    """Requested blocks are upper bounds: a seq that the default block
    doesn't divide fits down to the largest dividing power-of-two split
    instead of erroring (seq 1536 with default block_k 1024 -> 512)."""
    from ddp_practice_tpu.ops.flash_attention import _fit_block

    assert _fit_block(1536, 1024) == 512
    assert _fit_block(65, 512) == 65      # seq <= block: clamp to seq
    assert _fit_block(96, 64) == 32
    assert _fit_block(2048, 1024) == 1024


def test_flash_indivisible_seq_still_works():
    """seq=96 with requested block 64 (not a divisor): blocks fit down and
    numerics still match dense — the pre-fit behavior was a ValueError."""
    q, k, v = _qkv(s=96, seed=5)
    want = _attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_packed_matches_folded(causal):
    """The packed-layout kernels (round 4: attention directly on the flat
    (b, s, h*d) activations, head pairs in 128-lane column blocks) must
    agree with the folded (b*h, s, d) path — forward AND all three grads.
    On TPU the two are bit-identical; interpret mode gets a float
    tolerance."""
    from ddp_practice_tpu.ops.flash_attention import (
        _flash_lse, _heads_per_pack)

    b, s, h, d = 2, 256, 4, 64
    assert _heads_per_pack(h, d) == 2  # shapes take the packed path
    q, k, v = _qkv(b=b, s=s, h=h, d=d, seed=11)

    def folded(q, k, v):
        fold = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * h, x.shape[1], d)
        out, _ = _flash_lse(fold(q), fold(k), fold(v), causal, 512, 1024)
        return jnp.transpose(out.reshape(b, h, s, d), (0, 2, 1, 3))

    got = flash_attention(q, k, v, causal=causal)  # dispatches packed
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(folded(q, k, v)), rtol=2e-5, atol=2e-5
    )

    loss_p = lambda q, k, v: (
        flash_attention(q, k, v, causal=causal) ** 2).sum()
    loss_f = lambda q, k, v: (folded(q, k, v) ** 2).sum()
    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gp, gf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4
        )


def test_unpackable_heads_fall_back_to_folded():
    """h=3 with d=64 cannot pack into whole 128-lane pairs: the dispatch
    must fall back to the folded path and still match dense."""
    from ddp_practice_tpu.ops.flash_attention import _heads_per_pack

    assert _heads_per_pack(3, 64) is None
    q, k, v = _qkv(h=3, seed=13)
    want = _attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("d", [128, 256])
def test_packed_single_head_per_pack(d):
    """hpc=1 packing (d a multiple of 128: whole heads own >=128-lane
    column blocks) and the _widen lane-tile path (w > 128 for d=256) must
    match dense — the hpc=2 test never reaches either branch."""
    from ddp_practice_tpu.ops.flash_attention import _heads_per_pack

    assert _heads_per_pack(2, d) == 1
    q, k, v = _qkv(b=1, s=256, h=2, d=d, seed=17)
    want = _attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    gp = jax.grad(lambda q: (flash_attention(q, k, v, causal=True) ** 2
                             ).sum())(q)
    gd = jax.grad(lambda q: (_attention(q, k, v, causal=True) ** 2
                             ).sum())(q)
    np.testing.assert_allclose(
        np.asarray(gp), np.asarray(gd), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("causal", [False, True])
def test_fused_qkv_matches_sliced(causal):
    """flash_attention_qkv (round 4: the kernels window the raw (b, s,
    3*h*d) QKV-projection output at column offsets — q/k/v never
    materialize as slices) must match slicing q/k/v out and calling
    flash_attention: forward and the full dqkv gradient."""
    from ddp_practice_tpu.ops.flash_attention import flash_attention_qkv

    b, s, h, d = 2, 256, 4, 64
    rng = np.random.default_rng(23)
    qkv = jnp.asarray(rng.standard_normal((b, s, 3 * h * d)), jnp.float32)

    def sliced(qkv):
        hd = h * d
        rs = lambda x: x.reshape(b, s, h, d)
        return flash_attention(
            rs(qkv[..., :hd]), rs(qkv[..., hd:2 * hd]),
            rs(qkv[..., 2 * hd:]), causal=causal,
        )

    got = flash_attention_qkv(qkv, h, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got.reshape(b, s, h * d)),
        np.asarray(sliced(qkv).reshape(b, s, h * d)),
        rtol=2e-5, atol=2e-5,
    )

    # weighted-sum loss so dq/dk/dv all flow through one qkv cotangent
    w = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    g_fused = jax.grad(
        lambda t: (flash_attention_qkv(t, h, causal=causal) * w).sum()
    )(qkv)
    g_sliced = jax.grad(lambda t: (sliced(t) * w).sum())(qkv)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_sliced), rtol=2e-4, atol=2e-4
    )


def test_fused_qkv_unpackable_falls_back():
    """h*d shapes that cannot pack must still work through the fallback
    slice path inside flash_attention_qkv."""
    from ddp_practice_tpu.ops.flash_attention import (
        _heads_per_pack, flash_attention_qkv)

    b, s, h, d = 2, 128, 3, 64
    assert _heads_per_pack(h, d) is None
    rng = np.random.default_rng(29)
    qkv = jnp.asarray(rng.standard_normal((b, s, 3 * h * d)), jnp.float32)
    hd = h * d
    rs = lambda x: x.reshape(b, s, h, d)
    want = _attention(
        rs(qkv[..., :hd]), rs(qkv[..., hd:2 * hd]), rs(qkv[..., 2 * hd:]),
        causal=True,
    )
    got = flash_attention_qkv(qkv, h, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("rope", [False, True])
def test_flash_island_matches_unsharded(devices, rope):
    """Under a registered mesh the flash call runs in a shard_map island
    (parallel/ring.py kernel_island) — batch over 'data', heads over
    'tensor' — because the TPU partitioner refuses a bare Mosaic kernel
    (tests/test_tpu_compile.py). Here, on virtual devices: the island's
    split is the same function. rope=False is the packed-QKV call site
    in models/vit.py, whose split must be taken on the (3, h, hd) dims
    (a split of the flat 3*h*hd dim would part q heads from their k, v);
    rope=True is ops.attention's."""
    from ddp_practice_tpu.config import MeshConfig
    from ddp_practice_tpu.models.vit import SelfAttention
    from ddp_practice_tpu.parallel.mesh import build_mesh
    from ddp_practice_tpu.parallel.ring import set_current_mesh

    attn = SelfAttention(num_heads=4, attn_impl="flash", causal=True,
                         rope=rope)
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(4, 128, 256)), jnp.float32
    )
    variables = attn.init(jax.random.PRNGKey(0), x)

    def loss(variables, x):
        return jnp.sum(jnp.square(attn.apply(variables, x)))

    want_y = attn.apply(variables, x)
    want_g = jax.grad(loss)(variables, x)
    set_current_mesh(
        build_mesh(MeshConfig(data=2, tensor=2), devices=devices[:4])
    )
    got_y = jax.jit(attn.apply)(variables, x)
    got_g = jax.jit(jax.grad(loss))(variables, x)
    np.testing.assert_allclose(
        np.asarray(got_y), np.asarray(want_y), rtol=2e-5, atol=2e-5
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        ),
        got_g, want_g,
    )


# ---------------------------------------------------------------------
# The causal sub-tile schedule (PR 27): inside a visible grid cell only
# the score sub-tiles holding an unmasked element are computed.
# ---------------------------------------------------------------------

def _tile_classes(seq_q, seq_k, block_q, block_k):
    """What _live_subtiles says of every sub-tile of a causal head, strip
    by strip and cell by cell as the kernels ask it, beside what the mask
    itself says: two int maps over the (seq_q // sub_q, seq_k // sub_k)
    sub-tiles, 0 = all mask (skipped), 1 = crossed by the diagonal
    (computed with the penalty), 2 = no masked element (computed bare)."""
    from ddp_practice_tpu.ops.flash_attention import (
        _check_blocks, _live_subtiles, _sub_tiles)

    block_q, block_k = _check_blocks(seq_q, seq_k, block_q, block_k, True)
    sub_q, sub_k = _sub_tiles(block_q, block_k)
    offset = seq_k - seq_q
    nq, nk = seq_q // sub_q, seq_k // sub_k
    by_schedule = np.zeros((nq, nk), int)
    for qi in range(seq_q // block_q):
        for kj in range(seq_k // block_k):
            shift = qi * block_q + offset - kj * block_k
            c0 = kj * block_k // sub_k
            for r in range(block_q // sub_q):
                n_full, n_live = _live_subtiles(
                    shift + r * sub_q, sub_q, sub_k, block_k, xp=np)
                row = qi * block_q // sub_q + r
                by_schedule[row, c0:c0 + n_live] = 1
                by_schedule[row, c0:c0 + n_full] = 2
    unmasked = (np.arange(seq_k)[None, :]
                <= np.arange(seq_q)[:, None] + offset)
    tiles = unmasked.reshape(nq, sub_q, nk, sub_k)
    by_mask = tiles.any(axis=(1, 3)).astype(int) + tiles.all(axis=(1, 3))
    return by_schedule, by_mask, (sub_q, sub_k)


@pytest.mark.parametrize("seq_q,seq_k,block_q,block_k", [
    (2048, 2048, 512, 1024),   # the LM training cells
    (2048, 2048, 1024, 512),
    (2048, 2048, 256, 256),
    (4096, 4096, 512, 1024),
    (1024, 2048, 512, 1024),   # seq_q < seq_k: bottom-right alignment
    (512, 640, 512, 1024),     # offset 128: no multiple of the 256 rows
    (384, 1536, 512, 1024),    # blocks 384 and 512
    (256, 2048, 512, 1024),
    (768, 768, 512, 512),
    (192, 192, 512, 1024),     # no lane-aligned sub-tile: the cell whole
    (320, 640, 512, 1024),
    (128, 128, 512, 1024),
])
def test_causal_schedule_covers_the_mask_and_nothing_else(
        seq_q, seq_k, block_q, block_k):
    """Every unmasked (i, j) lies in a computed sub-tile, no computed
    sub-tile is wholly masked, the penalty goes exactly where the
    diagonal crosses, and causal_tile_counts counts that same schedule."""
    from ddp_practice_tpu.ops.flash_attention import causal_tile_counts

    by_schedule, by_mask, (sub_q, sub_k) = _tile_classes(
        seq_q, seq_k, block_q, block_k)
    np.testing.assert_array_equal(by_schedule, by_mask)
    executed, useful = causal_tile_counts(seq_q, seq_k, block_q, block_k)
    assert executed == (by_mask > 0).sum()
    offset = seq_k - seq_q
    unmasked = np.clip(np.arange(seq_q) + offset + 1, 0, seq_k).sum()
    assert useful == pytest.approx(unmasked / (sub_q * sub_k))
    assert useful <= executed


def test_causal_tile_counts_at_the_training_cells_shape():
    """lm_2k_b8: the whole-cell schedule ran 12 squares of 512x512 a head
    where 8 hold work (1.50x); sub-tiles must leave at most 1.13x."""
    from ddp_practice_tpu.ops.flash_attention import (
        _cell_strips, _sub_tiles, causal_tile_counts)

    executed, useful = causal_tile_counts(2048, 2048, 512, 1024)
    assert executed <= 1.13 * useful
    sub_q, sub_k = _sub_tiles(512, 1024)
    squares = executed * sub_q * sub_k / 512 ** 2
    assert 8.0 < squares <= 9.0
    # a non-causal cell is one strip: every row against every key, bare
    seen = []
    _cell_strips(seen.append, 0, 0, block_q=512, block_k=1024, causal=False,
                 seq_q=2048, seq_k=2048)
    assert seen == [[(slice(None), 1024, None)]]


_CAUSAL_SHAPES = {
    # block_k > block_q at two q blocks: wholly masked sub-tiles, diagonal
    # sub-tiles, bare ones, and masked trailing columns of the K/V block
    "trailing": (1024, 1024, 512, 1024),
    # cells of one sub-tile each, every loop at most one step long
    "single": (768, 768, 512, 512),
    # seq_q < seq_k: bottom-right alignment, offset a whole K/V sub-tile
    "cross": (512, 1024, 256, 512),
    # offset 128 under 256-row strips: two crossed sub-tiles a strip
    "cross_odd": (512, 640, 512, 1024),
    # blocks of 384 fit 128-wide sub-tiles
    "fit_128": (384, 384, 512, 1024),
    # 192 and 320 hold no lane-aligned sub-tile: the cell stays whole
    "whole_192": (192, 192, 512, 1024),
    "whole_320": (320, 320, 512, 1024),
}


@pytest.mark.parametrize("path,shape", [
    (path, shape) for shape, (sq, sk, _, _) in _CAUSAL_SHAPES.items()
    for path in ("packed", "fused_qkv", "folded")
    if path != "fused_qkv" or sq == sk       # fused IS self-attention
] + [("packed_d128", "trailing")])           # one head a 128-lane pack
def test_causal_subtiles_match_dense(path, shape):
    """Causal output and all three gradients against dense attention, on
    every kernel family, over shapes whose cells hold skipped, crossed
    and bare sub-tiles (asserted below, so a change of _SUB that empties
    a case fails here and not silently)."""
    from ddp_practice_tpu.ops.flash_attention import flash_attention_qkv

    seq_q, seq_k, block_q, block_k = _CAUSAL_SHAPES[shape]
    _, by_mask, _ = _tile_classes(seq_q, seq_k, block_q, block_k)
    if shape == "trailing":
        assert {0, 1, 2} <= set(by_mask.ravel())
    if shape.startswith("whole"):
        assert by_mask.shape == (1, 1)
    h = 3 if path == "folded" else 2         # 3 heads of 64 do not pack
    b, d = 1, 128 if path == "packed_d128" else 64
    rng = np.random.default_rng(31)
    mk = lambda s: jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    q, k, v, w = mk(seq_q), mk(seq_k), mk(seq_k), mk(seq_q)

    if path == "fused_qkv":
        def flash(q, k, v):
            qkv = jnp.concatenate(
                [x.reshape(b, seq_q, h * d) for x in (q, k, v)], axis=-1)
            return flash_attention_qkv(qkv, h, causal=True, block_q=block_q,
                                       block_k=block_k)
    else:
        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=block_q,
                                   block_k=block_k)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)),
        np.asarray(_attention(q, k, v, causal=True)), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (_attention(*a, causal=True) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------
# The packed backward as ONE kernel (PR 31): dq, dk and dv from a single
# recomputation of p and ds, where a whole-sequence dq accumulator fits
# VMEM; the dk/dv kernel and the dq kernel past that.
# ---------------------------------------------------------------------

def _eqns(jaxpr):
    """Every equation under `jaxpr`, those of nested jaxprs (jit, cond
    branches, a pallas_call's kernel) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _pallas_calls(jaxpr) -> dict:
    """{name: kernel jaxpr} of every pallas_call under `jaxpr`."""
    return {e.params["name"]: e.params["jaxpr"] for e in _eqns(jaxpr)
            if e.primitive.name == "pallas_call"}


# (seq_q, seq_k, block_q, block_k, heads, causal, fused qkv input?)
_ONE_KERNEL_CASES = {
    # skipped, crossed and bare sub-tiles, masked trailing columns
    "causal": (1024, 1024, 512, 1024, 2, True, False),
    "noncausal": (512, 512, 256, 256, 2, False, False),
    # seq_q != seq_k: the causal offset, a whole K/V sub-tile
    "cross": (512, 1024, 256, 512, 2, True, False),
    # offset 128 under 256-row strips: the penalty on PART of a strip's
    # keys (_mask_tail's concatenate), two crossed sub-tiles a strip
    "cross_odd_tail": (512, 640, 512, 1024, 2, True, False),
    "cross_noncausal": (256, 512, 128, 256, 2, False, False),
    "qkv_causal": (768, 768, 512, 512, 2, True, True),
    "qkv_noncausal": (512, 512, 256, 512, 2, False, True),
    # the LM cells' twelve heads: six packs, the qkv windows at 0 / 6 / 12
    "12_heads": (512, 512, 256, 256, 12, True, False),
    "12_heads_qkv": (256, 256, 128, 256, 12, True, True),
    # 192 holds no lane-aligned sub-tile: the cell whole, one q block
    "whole_192": (192, 192, 512, 1024, 2, True, False),
}


@pytest.mark.parametrize("case", sorted(_ONE_KERNEL_CASES))
def test_one_backward_kernel_matches_the_two_and_dense(case):
    """dq, dk, dv of the one kernel: EQUAL to the two kernels' (the same
    tiles accumulated in the same order) and within float32 rounding of
    XLA's gradient of dense attention."""
    from ddp_practice_tpu.ops import flash_attention as fa

    seq_q, seq_k, block_q, block_k, h, causal, fused = _ONE_KERNEL_CASES[case]
    b, d = 1, 64
    rng = np.random.default_rng(41)
    mk = lambda s: jnp.asarray(rng.normal(size=(b, s, h * d)), jnp.float32)
    q, k, v, do = mk(seq_q), mk(seq_k), mk(seq_k), mk(seq_q)
    kw = dict(n_heads=h, causal=causal, block_q=block_q, block_k=block_k,
              interpret=True, fused_qkv=fused)
    flat = (jnp.concatenate([q, k, v], axis=-1),) * 3 if fused else (q, k, v)
    out, lse = fa._flash_fwd_packed(*flat, **kw)
    one = fa._packed_bwd_calls(*flat, do, out, lse, one_kernel=True, **kw)
    two = fa._packed_bwd_calls(*flat, do, out, lse, one_kernel=False, **kw)
    heads = lambda x: x.reshape(b, -1, h, d)
    _, vjp = jax.vjp(lambda *a: _attention(*a, causal=causal),
                     heads(q), heads(k), heads(v))
    for got, same, want in zip(one, two, vjp(heads(do))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
        np.testing.assert_allclose(np.asarray(heads(got)), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seq,heads,d,dtype,kernels", [
    # the LM cells' shape, and the longest bf16 sequence on the near side
    (2048, 2, 64, jnp.bfloat16, 1),
    (6144, 2, 64, jnp.bfloat16, 1),
    # past the rule: a whole-sequence dq would not leave the tiles room
    (7168, 2, 64, jnp.bfloat16, 2),
    (16384, 2, 64, jnp.bfloat16, 2),
    # wider elements and wider packs cross the line sooner
    (2048, 2, 64, jnp.float32, 1),
    (4096, 2, 64, jnp.float32, 2),
    (1024, 1, 256, jnp.bfloat16, 1),
    (2048, 1, 256, jnp.bfloat16, 2),
])
@pytest.mark.parametrize("entry", ["packed", "qkv"])
def test_the_shape_alone_picks_one_backward_kernel_or_two(
        entry, seq, heads, d, dtype, kernels):
    """No option names the backward: `_ONE_KERNEL_BWD_VMEM` against the
    shape does, the same for both packed entries (traced, not run;
    tests/test_tpu_compile.py asks the TPU compiler about these shapes)."""
    from ddp_practice_tpu.ops import flash_attention as fa

    if entry == "qkv":
        loss = lambda x: fa.flash_attention_qkv(x, heads, causal=True).astype(
            jnp.float32).sum()
        x = jnp.zeros((1, seq, 3 * heads * d), dtype)
    else:
        loss = lambda x: fa.flash_attention(x, x, x, causal=True).astype(
            jnp.float32).sum()
        x = jnp.zeros((1, seq, heads, d), dtype)
    names = sorted(_pallas_calls(jax.make_jaxpr(jax.grad(loss))(x).jaxpr))
    want = {1: ["flash_bwd_packed"],
            2: ["flash_bwd_dkv_packed", "flash_bwd_dq_packed"]}[kernels]
    assert names == want + ["flash_fwd_packed"]
    w = fa._heads_per_pack(heads, d) * d
    held = fa._one_kernel_bwd_vmem(seq, 512, 1024, w, dtype)
    assert (held <= fa._ONE_KERNEL_BWD_VMEM) == (kernels == 1), held


def test_one_backward_kernel_runs_five_dots_where_two_ran_seven():
    """A (strip, head) of the one kernel: s, dp, dv, dk, dq, and one exp;
    the dk/dv kernel runs four of them and the dq kernel three, each with
    an exp of its own. Counted in the kernels' jaxprs, non-causal (one
    strip a cell) over a pack of two heads."""
    from ddp_practice_tpu.ops import flash_attention as fa

    x = jnp.zeros((1, 256, 128), jnp.float32)
    lse = jnp.zeros((1, 1, 256, 2), jnp.float32)

    def calls(one_kernel):
        return _pallas_calls(jax.make_jaxpr(lambda *a: fa._packed_bwd_calls(
            *a, one_kernel=one_kernel, n_heads=2, causal=False, block_q=256,
            block_k=256, interpret=True, fused_qkv=False))(
                x, x, x, x, x, lse).jaxpr)

    def count(kernel):
        ops = [e.primitive.name for e in _eqns(kernel)]
        return ops.count("dot_general") // 2, ops.count("exp") // 2

    assert {n: count(j) for n, j in calls(True).items()} == {
        "flash_bwd_packed": (5, 1)}
    assert {n: count(j) for n, j in calls(False).items()} == {
        "flash_bwd_dkv_packed": (4, 1), "flash_bwd_dq_packed": (3, 1)}


# ---------------------------------------------------------------------
# The whole-sequence kernels for short sequences (PR 29): a grid cell is
# G images x one 128-lane head pack, plain softmax, ONE backward kernel.
# ---------------------------------------------------------------------

# (batch, seq, heads, head_dim, images a cell (None: from the shape),
#  fused qkv input?)
SHORT_CASES = [
    (2, 64, 2, 64, 2, True),
    (3, 196, 2, 64, 2, True),      # G does not divide the batch
    (3, 196, 12, 64, 2, False),
    (2, 197, 2, 64, 1, False),
    (2, 256, 12, 64, None, True),
    (4, 384, 2, 64, None, False),
    (3, 196, 1, 128, 2, True),     # one head a pack
]


def _short_vs_dense(b, s, h, d, g, fused, causal, dtype, seed=0):
    """((out, dq, dk, dv) of the short kernels, the same of _attention in
    float32), from one set of inputs rounded to `dtype`."""
    from ddp_practice_tpu.ops.flash_attention import (
        flash_short,
        flash_short_qkv,
    )

    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.normal(size=(b, s, 3, h, d)), dtype)
    w = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)

    def split(x):
        return x[:, :, 0], x[:, :, 1], x[:, :, 2]

    def short(x):
        if fused:
            out = flash_short_qkv(x.reshape(b, s, 3 * h * d), h,
                                  causal=causal, images_per_cell=g)
        else:
            out = flash_short(*split(x), causal=causal, images_per_cell=g)
        assert out.dtype == dtype and out.shape == (b, s, h, d)
        return out.astype(jnp.float32)

    def dense(x):
        return _attention(*split(x), causal=causal)

    def with_grads(fn, x):
        out, vjp = jax.vjp(fn, x)
        return (out,) + split(vjp(w.astype(out.dtype))[0].astype(jnp.float32))

    return (with_grads(short, qkv),
            with_grads(dense, qkv.astype(jnp.float32)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SHORT_CASES, ids=lambda c: "b{}s{}h{}d{}g{}{}".format(
    *c[:5], "fused" if c[5] else "sliced"))
def test_short_matches_dense(case, dtype):
    """Forward and all three gradients against _attention in float32."""
    got, want = _short_vs_dense(*case, causal=False, dtype=dtype)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=tol, atol=tol * scale,
            err_msg=name)


@pytest.mark.parametrize("case", [SHORT_CASES[1], SHORT_CASES[3],
                                  SHORT_CASES[4]],
                         ids=["s196fused", "s197sliced", "s256h12"])
def test_short_causal_matches_dense(case):
    """The static triangle: one penalty tile a cell."""
    got, want = _short_vs_dense(*case, causal=True, dtype=jnp.float32)
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-5,
            atol=2e-5 * float(jnp.abs(r).max()), err_msg=name)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "sliced"])
def test_short_ignores_what_it_does_not_own(monkeypatch, fused):
    """Poison: with every byte the kernels do not write themselves set to
    NaN (the TPU interpreter's uninitialised memory: the images past the
    batch in a ragged last cell, the cotangents' VMEM scratch, the rows
    past the sequence in a block), no output and no gradient changes by
    a bit. The key tail up to the lane tile (196 -> 256) is Mosaic's to
    mask: the kernels work on logical (s, s) tiles, and chip_smoke-style
    numerics on the chip (experiments/flash_time.py vit) hold that."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    case = (3, 196, 2, 64, 2, fused)
    want, _ = _short_vs_dense(*case, causal=False, dtype=jnp.float32)
    pallas_call = pl.pallas_call

    def poisoned(*args, interpret, **kw):
        assert interpret is True
        return pallas_call(*args, **kw, interpret=pltpu.InterpretParams(
            uninitialized_memory="nan", out_of_bounds_reads="uninitialized"))

    monkeypatch.setattr(pl, "pallas_call", poisoned)
    jax.clear_caches()  # the wrappers are jitted: lower them again
    got, _ = _short_vs_dense(*case, causal=False, dtype=jnp.float32)
    jax.clear_caches()
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r), name)


def test_short_refuses_what_it_cannot_hold():
    from ddp_practice_tpu.ops.flash_attention import (
        SHORT_SEQ_MAX,
        flash_short_qkv,
        short_seq_supported,
    )

    assert short_seq_supported(196, 12, 64)
    assert short_seq_supported(196, 2, 128)
    assert not short_seq_supported(196, 3, 64)      # 3 heads: no pack
    assert not short_seq_supported(196, 4, 48)
    assert not short_seq_supported(SHORT_SEQ_MAX + 1, 12, 64)
    with pytest.raises(ValueError, match="whole-sequence"):
        flash_short_qkv(jnp.zeros((2, 16, 3 * 3 * 64)), 3)


def test_short_cell_size_follows_the_shape():
    """G: a cell of at least a few microseconds of dots, inside VMEM,
    never past the batch."""
    from ddp_practice_tpu.ops.flash_attention import _images_per_cell

    at = lambda b, s: _images_per_cell(b, s, 128)
    assert at(128, 196) >= 4
    assert at(2, 196) == 2
    assert at(128, 64) >= at(128, 196) >= at(128, 384) >= at(128, 640) >= 1
