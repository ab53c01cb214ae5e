"""Packed decode-attention kernel (ops/decode_attention.py): numerics
pinned to the masked XLA reference on the CPU backend (interpret mode),
covering the single-block fast path, the multi-block online-softmax
path, prefix masking, and left-padded (attn_start) prompts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.ops.attention import attention_with_mask
from ddp_practice_tpu.ops.decode_attention import decode_attention_packed

B, H, HD = 3, 4, 64


def _setup(L, cur, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H * HD)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, L, H * HD)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, L, H * HD)), jnp.float32)
    return q, kc, vc, jnp.int32(cur)


def _reference(q, kc, vc, cur, attn_start=None):
    L = kc.shape[1]
    mask = jnp.arange(L)[None, :] <= cur[..., None]
    if attn_start is not None:
        mask = mask[None] & (
            jnp.arange(L)[None, None, :] >= attn_start[:, None, None]
        )
        mask = mask[:, None]
    q4 = q.reshape(B, 1, H, HD)
    k4 = kc.reshape(B, -1, H, HD)
    v4 = vc.reshape(B, -1, H, HD)
    return attention_with_mask(q4, k4, v4, mask).reshape(B, 1, H * HD)


@pytest.mark.parametrize("L,cur", [(256, 0), (256, 100), (256, 255)])
@pytest.mark.fast
def test_single_block_matches_reference(L, cur):
    q, kc, vc, c = _setup(L, cur)
    got = decode_attention_packed(q, kc, vc, c, n_heads=H)
    want = _reference(q, kc, vc, jnp.asarray(cur))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("cur", [3, 700, 1500])
def test_multi_block_matches_reference(cur):
    """L > single_block_max exercises the online-softmax sweep with
    blocks past `cur` skipped (their DMA pinned to block 0)."""
    L = 2048
    q, kc, vc, c = _setup(L, cur, seed=1)
    got = decode_attention_packed(q, kc, vc, c, n_heads=H,
                                  block_l=512, single_block_max=1024)
    want = _reference(q, kc, vc, jnp.asarray(cur))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("L", [256, 2048])
def test_attn_start_left_padding(L):
    """Per-sequence first-valid-key masking (left-padded prompts)."""
    cur = min(L - 1, 900)
    q, kc, vc, c = _setup(L, cur, seed=2)
    start = jnp.asarray([0, 5, min(cur, 60)], jnp.int32)
    got = decode_attention_packed(q, kc, vc, c, start, n_heads=H,
                                  single_block_max=1024)
    want = _reference(q, kc, vc, jnp.asarray(cur), attn_start=start)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_rejects_multi_row_queries():
    q, kc, vc, c = _setup(128, 4)
    q2 = jnp.concatenate([q, q], axis=1)
    with pytest.raises(ValueError, match="single-token"):
        decode_attention_packed(q2, kc, vc, c, n_heads=H)


def test_rejects_unpackable_heads():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 1, 3 * 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(1, 64, 3 * 64)), jnp.float32)
    with pytest.raises(ValueError, match="pack"):
        decode_attention_packed(q, kc, kc, jnp.int32(0), n_heads=3)


def test_q8_broadcast_matches_plain():
    """The q8 MXU-broadcast branch of attention_with_mask (live on TPU
    for unpackable head shapes) must equal the plain 1-row path — pinned
    here directly since the backend gate keeps it off the CPU suite."""
    from ddp_practice_tpu.ops.attention import _attention, _q8_attention

    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 1, 3, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 40, 3, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 40, 3, 32)), jnp.float32)
    mask = (jnp.arange(40)[None, :] <= 17)[None, None]
    want = _attention(q, k, v, causal=False, mask=mask)
    got = _q8_attention(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_int8_kv_cache_decode_close_to_full_forward(devices):
    """kv_cache_dtype='int8': cached decode through the quantized packed
    kernel tracks the full forward within quantization tolerance (~1%
    relative — per-(batch, head, position) symmetric scales), and the
    cache actually stores int8."""
    import numpy as np

    from ddp_practice_tpu.inference import make_cache
    from ddp_practice_tpu.models import create_model

    VOCAB, TOTAL = 32, 16
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    kw = dict(vocab_size=VOCAB, max_len=TOTAL, hidden_dim=64, depth=2,
              num_heads=1, mlp_dim=128)
    m_q = create_model("lm_tiny", kv_cache_dtype="int8", **kw)
    m_ref = create_model("lm_tiny", **kw)
    params = m_ref.init(jax.random.PRNGKey(0), tokens)["params"]
    full = m_ref.apply({"params": params}, tokens)

    cache = make_cache(m_q, 2, TOTAL)
    kc = cache["block0"]["attn"]["cached_key"]
    assert kc.dtype == jnp.int8
    assert cache["block0"]["attn"]["cached_key_scale"].shape == (2, 1, TOTAL)
    logits, st = m_q.apply({"params": params, "cache": cache},
                           tokens[:, :8], decode=True, mutable=["cache"])
    outs = [logits]
    for i in range(8, tokens.shape[1]):
        lg, st = m_q.apply({"params": params, **st},
                           tokens[:, i:i + 1], decode=True,
                           mutable=["cache"])
        outs.append(lg)
    got = jnp.concatenate(outs, axis=1)
    rel = float(jnp.max(jnp.abs(got - full))
                / (jnp.max(jnp.abs(full)) + 1e-9))
    assert rel < 0.05, rel


# ----------------------------------------------------------------- paged
# PagedAttention-style path (serve/kv_pages.py layout): the kernel walks
# per-slot page tables instead of a contiguous cache; pinned against the
# gather reference, which is itself pinned against attention_with_mask
# by construction (it calls it).


def _paged_setup(nb, bs, mb, seed=0):
    from ddp_practice_tpu.ops.decode_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, H * HD)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, H * HD)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, H * HD)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, nb, size=(B, mb)), jnp.int32)
    return q, kp, vp, pt, paged_attention_reference, paged_decode_attention


@pytest.mark.fast
def test_paged_kernel_matches_reference():
    """Interpret-mode paged kernel == gather reference across slots at
    different lengths (block-skip masking, per-slot cursors)."""
    q, kp, vp, pt, ref_fn, kern_fn = _paged_setup(nb=12, bs=16, mb=4)
    lengths = jnp.asarray([0, 37, 63], jnp.int32)
    ref = ref_fn(q, kp, vp, pt, lengths, None, n_heads=H)
    got = kern_fn(q, kp, vp, pt, lengths, None, n_heads=H, impl="kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_respects_attn_start():
    """Left-padded prompts in slot-local coordinates: positions before
    attn_start[b] never contribute."""
    q, kp, vp, pt, ref_fn, kern_fn = _paged_setup(nb=9, bs=16, mb=3, seed=3)
    lengths = jnp.asarray([5, 20, 47], jnp.int32)
    start = jnp.asarray([2, 0, 17], jnp.int32)
    ref = ref_fn(q, kp, vp, pt, lengths, start, n_heads=H)
    got = kern_fn(q, kp, vp, pt, lengths, start, n_heads=H, impl="kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the masked positions actually matter: corrupting a pre-start row
    # changes nothing, corrupting an in-window row changes the output
    b0_block = int(pt[0, 0])
    kp_bad = kp.at[b0_block, 0].add(100.0)   # position 0 < start[0]=2
    same = kern_fn(q, kp_bad, vp, pt, lengths, start, n_heads=H,
                   impl="kernel")
    np.testing.assert_allclose(np.asarray(same)[0], np.asarray(got)[0],
                               atol=2e-5, rtol=2e-5)
    kp_bad2 = kp.at[b0_block, 3].add(100.0)  # position 3 in [2, 5]
    diff = kern_fn(q, kp_bad2, vp, pt, lengths, start, n_heads=H,
                   impl="kernel")
    assert float(jnp.abs(diff[0] - got[0]).max()) > 1e-3


def test_paged_int8_kernel_matches_dequantized_reference():
    """INT8 block pool with per-block (num_blocks, h, block_size) scale
    pages: the quantized page-walking kernel (interpret mode) tracks
    the dequantizing gather reference — the numerics pin behind the
    kv_cache_dtype='int8' paged serving path (halved KV bytes/token)."""
    from ddp_practice_tpu.ops.decode_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    rng = np.random.default_rng(7)
    nb, bs, mb = 10, 16, 4
    q = jnp.asarray(rng.normal(size=(B, 1, H * HD)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, size=(nb, bs, H * HD)),
                     jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(nb, bs, H * HD)),
                     jnp.int8)
    ks = jnp.asarray(np.abs(rng.normal(size=(nb, H, bs))) * 0.01 + 1e-3,
                     jnp.float32)
    vs = jnp.asarray(np.abs(rng.normal(size=(nb, H, bs))) * 0.01 + 1e-3,
                     jnp.float32)
    pt = jnp.asarray(rng.integers(1, nb, size=(B, mb)), jnp.int32)
    lengths = jnp.asarray([0, 37, 63], jnp.int32)
    start = jnp.asarray([0, 5, 17], jnp.int32)
    ref = paged_attention_reference(q, kq, vq, pt, lengths, start,
                                    n_heads=H, k_scale=ks, v_scale=vs)
    got = paged_decode_attention(q, kq, vq, pt, lengths, start, n_heads=H,
                                 k_scale=ks, v_scale=vs, impl="kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # missing v_scale refuses loudly rather than serving garbage
    with pytest.raises(ValueError, match="BOTH"):
        paged_decode_attention(q, kq, vq, pt, lengths, None, n_heads=H,
                               k_scale=ks)


def test_paged_single_token_contract():
    """Multi-token queries refuse loudly (prefill is the scratch-cache
    path), and unpackable heads refuse the kernel but serve the
    reference through the auto dispatch."""
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(4, 16, H * HD)), jnp.float32)
    pt = jnp.zeros((B, 2), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    q2 = jnp.asarray(rng.normal(size=(B, 2, H * HD)), jnp.float32)
    with pytest.raises(ValueError, match="single-token"):
        paged_decode_attention(q2, kp, kp, pt, lengths, n_heads=H)
    # h=4, d=16: below the 64-lane column-slice floor -> kernel refuses
    q_small = jnp.asarray(rng.normal(size=(B, 1, 64)), jnp.float32)
    kp_small = jnp.asarray(rng.normal(size=(4, 16, 64)), jnp.float32)
    with pytest.raises(ValueError, match="packable"):
        paged_decode_attention(q_small, kp_small, kp_small, pt, lengths,
                               n_heads=4, impl="kernel")
    out = paged_decode_attention(q_small, kp_small, kp_small, pt, lengths,
                                 n_heads=4)  # auto -> reference
    assert out.shape == (B, 1, 64)


# ------------------------------------------------------- the page walk
# ISSUE 25: one grid cell a slot walks the slot's live pages `P` at a
# time by manual double-buffered DMA. Cases of ONE test; page 16 means
# P = 8 (128-token chunks), so column 8 opens a slot's second chunk.
LAST = 66 * 16 - 1   # last position of the last page of a 66-column table


def _random_table(rng, b, mb, nb):
    return rng.integers(1, nb, size=(b, mb))


def _retired_table(rng, b, mb, nb):
    """Slot 1 retired: its whole row names page 0 (the garbage page);
    its length stays pinned, once inside the table and once past it."""
    pt = _random_table(rng, b, mb, nb)
    pt[1] = 0
    pt[3] = 0
    return pt


def _shared_prefix_table(rng, b, mb, nb):
    """Slots 0 and 1 share their first 9 pages (a chunk and a page)."""
    pt = _random_table(rng, b, mb, nb)
    pt[1, :9] = pt[0, :9]
    return pt


def _descending_table(rng, b, mb, nb):
    """Every row's pages lie in the pool in the reverse of their order."""
    return np.stack([np.arange(nb - 1 - i * mb, nb - 1 - (i + 1) * mb, -1)
                     for i in range(b)])


WALK_CASES = {
    # name: (block_size, table columns, lengths, attn_start, table, dtype)
    "lengths_at_page_and_chunk_edges": (
        16, 66, [0, 1, 15, 16, 17, 127, 128, 129, LAST], None,
        _random_table, jnp.float32),
    "table_shorter_than_a_chunk": (
        16, 3, [0, 20, 47], [0, 3, 17], _random_table, jnp.float32),
    "attn_start_zero_midpage_later_chunk": (
        16, 66, [40, 200, 700, LAST, 130], [0, 21, 300, 1040, 129],
        _random_table, jnp.float32),
    "retired_slot_beside_active": (
        16, 66, [77, 5, 300, 66 * 16 + 5, 129], [0, 0, 140, 0, 16],
        _retired_table, jnp.float32),
    "shared_prefix_pages": (
        16, 66, [150, 190, 9], [0, 0, 0], _shared_prefix_table, jnp.float32),
    "page_ids_descending": (
        16, 66, [LAST, 500, 127], [3, 130, 0], _descending_table,
        jnp.float32),
    "page_8_chunks_of_16_pages": (
        8, 21, [0, 127, 128, 167], [0, 5, 126, 129], _random_table,
        jnp.float32),
    "page_32_chunks_of_4_pages": (
        32, 7, [31, 128, 223, 100], [0, 33, 129, 99], _random_table,
        jnp.float32),
    "bf16_pool": (
        16, 66, [250, 1000, 15, 129], [6, 517, 0, 0], _random_table,
        jnp.bfloat16),
}

# PR 45: a GROUPED walk's chunk follows the pool's shape, not 128 tokens.
# 8 query heads of 128 on 2 KV heads; the cases name the chunk their lengths
# are laid around (the test checks that the rule still picks it): on pages
# of 64 eight pages = 512 tokens; on float32 pages of 128 (128 KB a page)
# four; on pages of 16 eight pages = 128 tokens, as ever.
GH, GKV, GD = 8, 2, 128
GROUPED_WALK_CASES = {
    # name: (block_size, table columns, lengths, attn_start, table, dtype,
    #        pages a chunk)
    # lengths end one short of, on and one past the chunk's edges; one
    # ends on the first page of its THIRD chunk (1,024 + 5), one mid-chunk
    "grouped_lengths_at_the_new_chunks_edges": (
        64, 40, [0, 63, 511, 512, 513, 700, 1029, 40 * 64 - 1], None,
        _random_table, jnp.bfloat16, 8),
    # starts mid-page in what the table's first chunk does not hold (column
    # 9, 17 and 39); slot 1 ends on the first page of its walk's second
    # chunk (columns 9 .. 17), slot 2 inside its first, slot 3 on its
    # start's own page at the table's end
    "grouped_start_midpage_later_chunk_end_on_a_chunks_first_page": (
        64, 40, [100, 17 * 64 + 3, 1500, 40 * 64 - 1, 2000],
        [0, 9 * 64 + 21, 17 * 64 + 63, 39 * 64 + 5, 513],
        _random_table, jnp.bfloat16, 8),
    # bytes, not pages: float32 pages of 128 tokens are 128 KB, four a chunk
    "grouped_float32_chunks_of_4_pages": (
        128, 20, [511, 512, 513, 700, 20 * 128 - 1, 300],
        [0, 0, 128 + 7, 4 * 128 + 9, 2000, 299],
        _random_table, jnp.float32, 4),
    # a table of 3 columns holds no whole chunk: P is the table's width
    "grouped_table_narrower_than_a_chunk": (
        64, 3, [0, 70, 191, 130], [0, 3, 65, 129], _random_table,
        jnp.bfloat16, 3),
    # slots 1 and 3 retired (row 0; one length pinned past the table)
    # between slots that walk two and three chunks
    "grouped_retired_slot_beside_active": (
        64, 40, [600, 5, 1300, 40 * 64 + 5, 1029], [0, 0, 140, 0, 64],
        _retired_table, jnp.bfloat16, 8),
    # 16-token pages: eight a chunk, 128 tokens, what the walk always ran
    "grouped_page_16_chunks_of_8_pages": (
        16, 66, [40, 200, 700, LAST, 130, 128], [0, 21, 300, 1040, 129, 0],
        _random_table, jnp.bfloat16, 8),
}


def _walk_case(case):
    """(case tuple, query heads, KV heads, head_dim)."""
    if case in WALK_CASES:
        return WALK_CASES[case] + (None,), H, H, HD
    return GROUPED_WALK_CASES[case], GH, GKV, GD


@pytest.mark.parametrize("case",
                         sorted(WALK_CASES) + sorted(GROUPED_WALK_CASES))
def test_paged_walk_matches_reference(case):
    from ddp_practice_tpu.ops.decode_attention import (
        _pages_per_chunk,
        paged_attention_reference,
        paged_decode_attention,
    )

    (bs, mb, lengths, start, table, dtype, pages), heads, kvh, d = \
        _walk_case(case)
    if pages is not None:   # the chunk the case's lengths are laid around
        assert _pages_per_chunk(bs, kvh * d, dtype, table_pages=mb) == pages
    b = len(lengths)
    nb = 1 + b * mb
    rng = np.random.default_rng(
        sorted([*WALK_CASES, *GROUPED_WALK_CASES]).index(case))
    q = jnp.asarray(rng.normal(size=(b, 1, heads * d)), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dtype)
    pt = jnp.asarray(table(rng, b, mb, nb), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    start = None if start is None else jnp.asarray(start, jnp.int32)
    kw = dict(n_heads=heads, n_kv_heads=kvh)
    ref = paged_attention_reference(q, kp, vp, pt, lengths, start, **kw)
    got = paged_decode_attention(q, kp, vp, pt, lengths, start, **kw,
                                 impl="kernel")
    # bf16: an ulp of outputs that reach 2 (chip_smoke.py's bound)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_paged_walk_skips_what_it_may_and_nothing_else():
    """Pages under `attn_start` and past `len` are never read (NaNs
    there change nothing); every page between them is (a NaN in any
    shows)."""
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    bs, mb, b = 16, 66, 2
    nb = 1 + b * mb
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(b, 1, H * HD)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, H * HD)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, H * HD)), jnp.float32)
    pt = np.arange(1, nb).reshape(b, mb)
    lengths = jnp.asarray([700, 40], jnp.int32)     # pages 0..43, 0..2
    start = jnp.asarray([300, 17], jnp.int32)       # from page 18, 1

    @jax.jit
    def kernel(k, v):
        return paged_decode_attention(
            q, k, v, jnp.asarray(pt, jnp.int32), lengths, start, n_heads=H,
            impl="kernel")

    def run(k, v):
        return np.asarray(kernel(k, v))

    want = run(kp, vp)
    dead = np.concatenate([pt[0, :18], pt[0, 44:], pt[1, :1], pt[1, 3:], [0]])
    np.testing.assert_array_equal(
        run(kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)), want)
    for page in (pt[0, 18], pt[0, 25], pt[0, 26], pt[0, 43], pt[1, 1],
                 pt[1, 2]):
        slot = 0 if page in pt[0] else 1
        assert np.isnan(run(kp, vp.at[page].set(jnp.nan))[slot]).all(), page


@pytest.mark.parametrize("block_size,width,dtype,table,pages", [
    # the MHA walk (no table handed in) aims at 128 tokens, as ever
    (16, 768, jnp.bfloat16, None, 8),   # the flood cell: 4 x 196 KB
    (8, 768, jnp.bfloat16, None, 16),
    (32, 768, jnp.bfloat16, None, 4),
    (24, 256, jnp.float32, None, 6),    # 144 tokens: never under 128
    (128, 1024, jnp.bfloat16, None, 1),
    (256, 1024, jnp.bfloat16, None, 1),   # a page longer than a chunk
    (16, 16384, jnp.bfloat16, None, 4),   # too wide for 4 x 128 rows in 8 MiB
    # the GROUPED walk at its four cells' shapes (page, KV heads x head_dim,
    # table columns): 512 KB of a pool a chunk, at most eight pages
    pytest.param(64, 4 * 128, jnp.bfloat16, 240, 8,
                 id="smallthinker_512_tokens"),
    pytest.param(64, 2 * 256, jnp.bfloat16, 76, 8,
                 id="qwen3next_512_tokens"),
    pytest.param(64, 1 * 128, jnp.bfloat16, 48, 8,
                 id="jamba2_512_tokens_at_the_page_cap"),
    pytest.param(16, 2 * 128, jnp.bfloat16, 114, 8,
                 id="nemo3s_128_tokens_at_the_page_cap"),
    # the cap on P: 8-token pages would ask 128 for their 512 KB
    pytest.param(8, 256, jnp.bfloat16, 512, 8, id="grouped_page_cap"),
    # bytes, not tokens: a 2,048 B row (float32) fills 512 KB in four pages
    pytest.param(64, 4 * 128, jnp.float32, 240, 4, id="grouped_bytes"),
    # never more than the table holds
    pytest.param(64, 4 * 128, jnp.bfloat16, 3, 3, id="grouped_narrow_table"),
    pytest.param(256, 8 * 128, jnp.bfloat16, 60, 1,
                 id="grouped_page_of_512_KB"),
    # the VMEM halving is the token targets' (the 16384-wide case above):
    # 512 KB a pool keeps a grouped walk's four buffers at 2 MiB however
    # wide the row (16 KB rows on 16-token pages: two pages)
    pytest.param(16, 8192, jnp.bfloat16, 64, 2, id="grouped_inside_vmem"),
])
def test_pages_per_chunk_follows_the_shapes(block_size, width, dtype, table,
                                            pages):
    from ddp_practice_tpu.ops.decode_attention import (
        _CHUNK_VMEM_BYTES,
        _MLA_CHUNK_TOKENS,
        _pages_per_chunk,
    )

    got = _pages_per_chunk(block_size, width, dtype, table_pages=table)
    assert got == pages
    buffers = 4 * got * block_size * width * jnp.dtype(dtype).itemsize
    assert got == 1 or buffers <= _CHUNK_VMEM_BYTES
    # the latent walk's own target is what it was: 16 pages of 64, and
    # halved like any token target where the row is very wide
    assert _pages_per_chunk(64, 640, jnp.bfloat16, _MLA_CHUNK_TOKENS) == 16
    assert _pages_per_chunk(64, 8192, jnp.bfloat16, _MLA_CHUNK_TOKENS) == 2


@pytest.mark.parametrize("case", [
    "attn_start_zero_midpage_later_chunk",
    "retired_slot_beside_active",
    "grouped_start_midpage_later_chunk_end_on_a_chunks_first_page",
    "grouped_table_narrower_than_a_chunk",
    "grouped_retired_slot_beside_active",
])
def test_paged_walk_waits_for_what_it_reads(case, monkeypatch):
    """The same cases under the TPU interpreter, which lands a DMA's
    bytes only when it is WAITED for and watches for races: a chunk
    read before its wait, or a buffer refilled while it is read, shows
    here and nowhere else on the CPU (plain interpret mode copies at
    `start`)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pallas_call = pl.pallas_call

    def on_wait(*args, interpret, **kw):
        assert interpret is True
        return pallas_call(*args, **kw, interpret=pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))

    monkeypatch.setattr(pl, "pallas_call", on_wait)
    test_paged_walk_matches_reference(case)
    assert not interpret_pallas_call.races.races_found


# --------------------------------------------- groups of any size (PR 32)
# Grouped queries whose group is no multiple of 8 (20 query heads on ONE KV
# head) used to fall to the gather reference under "auto"; the walk pads the
# group's rows to whole sublane tiles and cuts the pad rows from the output.
ANY_GROUP = {
    # name: (query heads, KV heads, rows a KV head inside the kernel)
    "group_20_one_kv_head": (20, 1, 24),
    "group_5_two_kv_heads": (10, 2, 8),
    "group_3_four_kv_heads": (12, 4, 8),
    "group_16_unchanged": (16, 1, 16),
    "group_32_unchanged": (64, 2, 32),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(ANY_GROUP))
def test_paged_walk_takes_a_group_of_any_size(case, dtype):
    from ddp_practice_tpu.ops.decode_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    heads, kvh, rows = ANY_GROUP[case]
    lengths, start = [3, 17, 100, 191, 64], [0, 5, 33, 0, 64]
    b, d, bs, mb = len(lengths), 128, 16, 12
    nb = 1 + b * mb
    rng = np.random.default_rng(heads)
    q = jnp.asarray(rng.normal(size=(b, 1, heads * d)), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dtype)
    pt = jnp.asarray(rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1,
                     jnp.int32)
    args = (q, kp, vp, pt, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(start, jnp.int32))
    kw = dict(n_heads=heads, n_kv_heads=kvh)
    want = paged_attention_reference(*args, **kw)
    walk = lambda *a: paged_decode_attention(*a, **kw, impl="kernel")
    # bf16: an ulp of outputs that reach 2 (chip_smoke.py's bound)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(walk(*args), np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    # ONE kernel, on `rows` query rows a KV head; a group that was a
    # multiple of 8 already is handed over as it was (no pad, no slice)
    text = str(jax.make_jaxpr(walk)(*args))
    assert text.count("pallas_call") == 1
    assert "name=paged_decode" in text.replace(" ", "")
    assert f"[{b},{kvh * rows},{d}]" in text.replace(" ", "")
    assert ("pad" in text) == (rows * kvh != heads)


def test_latent_walk_of_32_rows_is_unchanged():
    """The absorbed latent walk (32 query rows on one 640-lane row a
    token) shares the kernel body and takes no padding."""
    from ddp_practice_tpu.ops.decode_attention import (
        paged_decode_mla,
        paged_mla_reference,
    )

    rng = np.random.default_rng(32)
    lengths, start = [3, 100, 191], [0, 33, 0]
    b, heads, w, v, bs, mb = 3, 32, 640, 512, 16, 12
    nb = 1 + b * mb
    q = jnp.asarray(rng.normal(size=(b, heads, w)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(nb, bs, w)), jnp.float32)
    pt = jnp.asarray(rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1,
                     jnp.int32)
    args = (q, pool, pt, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(start, jnp.int32))
    kw = dict(v_lanes=v, sm_scale=192 ** -0.5)
    walk = lambda *a: paged_decode_mla(*a, **kw, impl="kernel")
    np.testing.assert_allclose(walk(*args), paged_mla_reference(*args, **kw),
                               atol=2e-5, rtol=2e-5)
    text = str(jax.make_jaxpr(walk)(*args))
    assert text.count("pallas_call") == 1 and "pad" not in text


# ------------------------------------------- heads of 256 lanes (PR 38)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ["ragged_left_padded", "no_start"])
def test_paged_walk_at_head_dim_256_group_8_page_64(case, dtype):
    """The walk as `qwen3next_80b_ep4` runs it: 16 query heads of 256 lanes
    on 2 KV heads (a group of 8, a pool row of 512 lanes), pages of 64,
    contexts that end inside a page and past several; against the gather
    reference, ONE kernel, no padding of the group."""
    from ddp_practice_tpu.ops.decode_attention import (
        paged_attention_reference,
        paged_decode_attention,
    )

    lengths = [3, 64, 100, 700, 333]
    start = None if case == "no_start" else [0, 5, 64, 130, 0]
    b, heads, kvh, d, bs, mb = len(lengths), 16, 2, 256, 64, 12
    nb = 1 + b * mb
    rng = np.random.default_rng(256)
    q = jnp.asarray(rng.normal(size=(b, 1, heads * d)), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh * d)), dtype)
    pt = jnp.asarray(rng.permutation(nb - 1)[:b * mb].reshape(b, mb) + 1,
                     jnp.int32)
    args = (q, kp, vp, pt, jnp.asarray(lengths, jnp.int32),
            None if start is None else jnp.asarray(start, jnp.int32))
    kw = dict(n_heads=heads, n_kv_heads=kvh)
    want = paged_attention_reference(*args, **kw)
    walk = lambda *a: paged_decode_attention(*a, **kw, impl="kernel")
    # bf16: an ulp of outputs that reach 2 (chip_smoke.py's bound)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(walk(*args), np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    text = str(jax.make_jaxpr(walk)(*args))
    assert text.count("pallas_call") == 1 and "pad" not in text
    assert "name=paged_decode" in text.replace(" ", "")
    assert f"[{b},{heads},{d}]" in text.replace(" ", "")
