"""The main path's programs, compiled for a described TPU v5e — no chip.

Interpret mode on CPU turns a Pallas kernel into ordinary XLA ops, so it
never meets the TPU compiler: tilings it refuses, VMEM it does not have,
or the partitioner's "Mosaic kernels cannot be automatically
partitioned" all pass every CPU test and die on the first chip call.
The TPU compiler is installed here and compiles for a chip that is
DESCRIBED (`topologies.get_topology_desc`) and not attached, so each
case lowers one program at real widths, steers the `backend.on_tpu()`
gates to their compiled branch (in the test, not through an option of
the program), and asserts the kernel is in the executable
(`tpu_custom_call`). Nothing runs: a pass says the first chip call
should not die in the compiler, never that results or times are right
(chip_smoke.py says that, on the chip).

Kernel cases are tier-1 (about a second each). Whole-step programs take
10-20 s each and are `slow`.
"""

import contextlib
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# libtpu guards the chip with /tmp/libtpu_lockfile, one process at a time.
# No chip is touched here, and test processes run side by side.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ddp_practice_tpu.config import MeshConfig, PrecisionPolicy, TrainConfig
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.parallel.mesh import build_mesh, shard_state
from ddp_practice_tpu.parallel.ring import set_current_mesh
from ddp_practice_tpu.utils import backend

# lm_base widths, the shape of the smoke and of perf/'s gpt2s_train_2k cells
B, S, H, D = 8, 2048, 12, 64
HD = H * D
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def as_on_chip(monkeypatch):
    """Take every gate's compiled branch, with the persistent cache off:
    a compile for a described chip is written to the cache but cannot be
    read back without one, and the next run would warn and recompile."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(device, tree):
    """The tree's shapes, placed on a described device."""
    one = SingleDeviceSharding(device)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
    )


def _compile(fn, *args, device, min_kernels=1):
    """Lower `fn` for `device` from shapes alone and return the HLO text
    (raises what the chip's compiler would raise)."""
    text = jax.jit(fn).lower(*_on(device, args)).compile().as_text()
    assert text.count("tpu_custom_call") >= min_kernels, (
        "the program compiled without its Pallas kernel: it was "
        "interpreted or replaced by the reference"
    )
    return text


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


# --------------------------------------------------------------- kernels
def _flash(causal=True, grad=False, h=H, d=D):
    from ddp_practice_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    x = _sds((B, S, h, d))
    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), (x, x, x)


def _flash_qkv():
    from ddp_practice_tpu.ops.flash_attention import flash_attention_qkv

    def loss(qkv):
        return flash_attention_qkv(qkv, H, causal=True).astype(
            jnp.float32).sum()

    return jax.grad(loss), (_sds((B, S, 3 * HD)),)


def _flash_bwd(batch, seq=S, dtype=BF16, one_kernel=None):
    """The packed backward alone, as the LM cells' rope path calls it."""
    from ddp_practice_tpu.ops.flash_attention import _packed_bwd_calls

    def bwd(q, k, v, do, out, lse):
        return _packed_bwd_calls(
            q, k, v, do, out, lse, one_kernel=one_kernel, n_heads=H,
            causal=True, block_q=512, block_k=1024, interpret=False,
            fused_qkv=False)

    x = _sds((batch, seq, HD), dtype)
    return bwd, (x,) * 5 + (_sds((batch, H // 2, seq, 2), jnp.float32),)


def _rope_flat(d=D):
    """The flat block's rotary passes at the LM cells' rows (8 x 2048 x
    768): forward off the projection, backward into its cotangent."""
    from ddp_practice_tpu.ops.rope import (
        flat_rope_tables,
        rope_flat_bwd,
        rope_flat_qk,
    )

    h = HD // d

    def both(qkv, g):
        cos, sin = flat_rope_tables(jnp.arange(S), HD, h)
        q, k = rope_flat_qk(qkv, cos, sin, n_heads=h)
        return rope_flat_bwd(q + g, k + g, g, cos, sin, n_heads=h)

    return both, (_sds((B, S, 3 * HD)), _sds((B, S, HD)))


def _flash_short(batch=128, seq=196):
    """ViT-B/16's attention core a layer (perf/configs/vit_b16.json under
    perf/traffic/vit_224_b128.json): 128 images of 196 patches, 12 heads
    of 64, off the flat projection; 196 is no multiple of the sublane
    tile, and is the block's whole sequence dim. And the longest sequence
    "auto" hands these kernels (SHORT_SEQ_MAX): one image a cell, whose
    four (s, s) float32 tiles still fit (at 1280 the backward asks for
    17.86 MB of the 16 MB of scoped VMEM)."""
    from ddp_practice_tpu.ops.flash_attention import flash_short_qkv

    def loss(qkv):
        return flash_short_qkv(qkv, H).astype(jnp.float32).sum()

    return jax.grad(loss), (_sds((batch, seq, 3 * HD)),)


def _decode_packed(L, int8=False):
    from ddp_practice_tpu.ops.decode_attention import decode_attention_packed

    def step(q, k, v, cur, start, *scales):
        ks, vs = scales if scales else (None, None)
        return decode_attention_packed(
            q, k, v, cur, start, n_heads=H, k_scale=ks, v_scale=vs
        )

    kv = _sds((B, L, HD), jnp.int8 if int8 else BF16)
    args = (_sds((B, 1, HD)), kv, kv, _sds((), jnp.int32),
            _sds((B,), jnp.int32))
    if int8:
        sc = _sds((B, H, L), jnp.float32)
        args += (sc, sc)
    return step, args


def _paged(block, int8=False, with_start=True, slots=8, blocks_per_slot=32,
           pool=None):
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    def step(q, k, v, table, lengths, start, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_decode_attention(
            q, k, v, table, lengths, start if with_start else None,
            n_heads=H, k_scale=ks, v_scale=vs, impl="auto",
        )

    nb = pool or 1 + slots * blocks_per_slot
    pool = _sds((nb, block, HD), jnp.int8 if int8 else BF16)
    args = (_sds((slots, 1, HD)), pool, pool,
            _sds((slots, blocks_per_slot), jnp.int32),
            _sds((slots,), jnp.int32), _sds((slots,), jnp.int32))
    if int8:
        sc = _sds((nb, H, block), jnp.float32)
        args += (sc, sc)
    return step, args


def _fused_encoder(causal):
    """vit_tiny's layer (d 192, 3 heads, s 64) and lm_tiny_fused's
    (d 256, 4 heads, s 256, causal), forward and backward kernels."""
    from ddp_practice_tpu.models.vit import EncoderBlock
    from ddp_practice_tpu.ops.fused_encoder import fused_encoder_layer

    imgs, s, d, heads, mlp = (
        (256, 256, 256, 4, 1024) if causal else (1024, 64, 192, 3, 768)
    )
    block = EncoderBlock(heads, mlp)
    params = jax.eval_shape(
        lambda: block.init(jax.random.PRNGKey(0), jnp.zeros((1, s, d)))
    )["params"]

    def loss(x, p):
        return fused_encoder_layer(
            x, p, num_heads=heads, causal=causal
        ).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1)), (_sds((imgs, s, d)), params)


def _moe_sorted():
    """ops/moe.py impl="sorted": megablox gmm forward, gmm + tgmm
    backward, at lm_moe's dims (d 768, mlp 3072, 8 experts, top-2) over
    one batch of 8 x 2048 tokens. The row count is part of the case:
    the tiling jax 0.9 refused at 8 rows (18.58 MB of scoped VMEM)
    compiles at 2."""
    from ddp_practice_tpu.ops.moe import MoEMlp

    moe = MoEMlp(num_experts=8, top_k=2, mlp_dim=3072, impl="sorted",
                 dtype=BF16)
    x = _sds((B, S, 768))
    variables = jax.eval_shape(
        lambda: moe.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 768), BF16))
    )

    def loss(x, variables):
        y, _ = moe.apply(variables, x, mutable=["intermediates",
                                                "batch_stats"])
        return y.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1)), (x, variables)


def _paged_grouped(slots=128, blocks_per_slot=114):
    """The hybrid cell's attention (perf/configs/nemotron3_super_ep4.json):
    32 query heads over 2 KV heads of 128, pages 256 lanes wide; the 16
    query heads of a KV head are one matmul's rows."""
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    def step(q, k, v, table, lengths, start):
        return paged_decode_attention(
            q, k, v, table, lengths, start, n_heads=32, n_kv_heads=2)

    pool = _sds((1 + slots * blocks_per_slot, 16, 2 * 128))
    return step, (_sds((slots, 1, 32 * 128)), pool, pool,
                  _sds((slots, blocks_per_slot), jnp.int32),
                  _sds((slots,), jnp.int32), _sds((slots,), jnp.int32))


def _ssm_step(slots=128):
    """ops/ssm.py's one-token recurrence at the hybrid cell's widths: 128
    heads of 64 over a state of 128 in 8 groups, every slot's float32
    state rewritten in place."""
    from ddp_practice_tpu.ops.ssm import ssm_step

    f32 = jnp.float32
    return ssm_step, (
        _sds((slots, 128, 64)), _sds((slots, 128), f32), _sds((128,), f32),
        _sds((slots, 8, 128)), _sds((slots, 8, 128)), _sds((128,)),
        _sds((slots, 128, 64, 128), f32))


def _moe_gmm(rows_a_tile):
    """ops/moe.py's expert kernel at the hybrid cell's widths: 128 held
    experts of 1024 -> 2688 -> 1024, both matrices of an expert (11 MB)
    in VMEM two deep; a decode step's tiles of 16 rows and a 768-token
    prompt's of 64."""
    from ddp_practice_tpu.ops.moe import expert_mlp_tiles

    picks = {16: 128 * 22, 64: 768 * 22}[rows_a_tile]
    tiles = -(-picks // rows_a_tile) + 128

    def mlp(rows, w1, w2, tile_expert, used):
        return expert_mlp_tiles(rows, w1, w2, tile_expert, used,
                                tile=rows_a_tile)

    return mlp, (_sds((tiles * rows_a_tile, 1024)),
                 _sds((128, 1024, 2688)), _sds((128, 2688, 1024)),
                 _sds((tiles,), jnp.int32), _sds((1,), jnp.int32))


def _paged_mla(page=64, slots=128, context=8960, pool_tokens=580_000):
    """The latent-attention cell's decode step
    (perf/configs/kanana2_30b_pp8.json): 32 absorbed query heads over ONE
    640-lane row a token (512 latent + 64 rope + 64 of padding), read once
    as key and value."""
    from ddp_practice_tpu.ops.decode_attention import paged_decode_mla

    def step(q, pool, table, lengths, start):
        return paged_decode_mla(q, pool, table, lengths, start, v_lanes=512,
                                sm_scale=192 ** -0.5)

    return step, (_sds((slots, 32, 640)),
                  _sds((1 + pool_tokens // page, page, 640)),
                  _sds((slots, context // page), jnp.int32),
                  _sds((slots,), jnp.int32), _sds((slots,), jnp.int32))


def _moe_glu(rows_a_tile):
    """ops/moe.py's gated expert kernel at the same cell's widths: 128
    experts of 2048 -> 768 -> 2048, the three matrices of an expert
    (9.4 MB) in VMEM two deep; a decode step's tiles of 16 rows and a
    1024-token chunk's of 64."""
    from ddp_practice_tpu.ops.moe import expert_glu_tiles

    picks = {16: 128 * 6, 64: 1024 * 6}[rows_a_tile]
    tiles = -(-picks // rows_a_tile) + 128

    def mlp(rows, wg, wu, wd, tile_expert, used):
        return expert_glu_tiles(rows, wg, wu, wd, tile_expert, used,
                                tile=rows_a_tile)

    return mlp, (_sds((tiles * rows_a_tile, 2048)),
                 _sds((128, 2048, 768)), _sds((128, 2048, 768)),
                 _sds((128, 768, 2048)),
                 _sds((tiles,), jnp.int32), _sds((1,), jnp.int32))


def _sel(what, slots=256, length=1024):
    """ops/ssm.py's Mamba-1 kernels at the Jamba cell's widths
    (perf/configs/jamba2_3b.json): 5120 channels over a state of 16, a
    sequence's float32 state (16, 40, 128) rewritten in place; the decode
    step over 256 slots, a prompt's scan over the widest bucket."""
    from ddp_practice_tpu.ops import ssm

    f32 = jnp.float32
    c, n = 5120, 16
    lead = (slots,) if what == "step" else (1, length)
    b = lead[0]
    return (ssm.sel_step if what == "step" else ssm.sel_scan), (
        _sds(lead + (c,)), _sds(lead + (c,), f32), _sds((c, n), f32),
        _sds(lead + (n,)), _sds(lead + (n,)), _sds((c,)),
        _sds(ssm.sel_state_shape(b, c, n), f32))


def _paged_group20(slots=256, blocks_per_slot=48, page=64):
    """The Jamba cell's attention: 20 query heads on ONE KV head of 128,
    pages of 64 tokens 128 lanes wide; the group's rows padded to 24."""
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    def step(q, k, v, table, lengths, start):
        return paged_decode_attention(
            q, k, v, table, lengths, start, n_heads=20, n_kv_heads=1)

    pool = _sds((1 + slots * blocks_per_slot, page, 128))
    return step, (_sds((slots, 1, 20 * 128)), pool, pool,
                  _sds((slots, blocks_per_slot), jnp.int32),
                  _sds((slots,), jnp.int32), _sds((slots,), jnp.int32))


def _gdn(what, slots=128, length=4096):
    """ops/gdn.py's Gated DeltaNet kernels at the Qwen3-Next cell's widths
    (perf/configs/qwen3next_80b_ep4.json): 16 key heads serving 32 value
    heads of 128 x 128 float32 state, rewritten in place; the decode step
    over 128 slots, a prompt's chunked scan over the widest bucket and the
    narrowest (the chunk terms are the kernel `gdn_terms`, the carry the
    kernel `gdn_scan`), and the terms alone."""
    from ddp_practice_tpu.ops import gdn

    f32 = jnp.float32
    lead = (slots,) if what == "step" else (1, length)
    args = (_sds(lead + (16, 128), f32), _sds(lead + (16, 128), f32),
            _sds(lead + (32, 128), f32), _sds(lead + (32,), f32),
            _sds(lead + (32,), f32), _sds((lead[0], 32, 128, 128), f32))
    if what == "terms":
        return gdn.gdn_terms_kernel, args[:5]
    return (gdn.gdn_step if what == "step" else gdn.gdn_scan), args


def _kda(what, slots=128, length=2048):
    """ops/kda.py's Kimi Delta Attention kernels at the Ling-3.0-flash
    cell's widths (perf/configs/ling3_flash_ep4.json): 32 heads of a
    128 x 128 float32 state, a decay a key lane; the decode step over 128
    slots, a prompt chunk's scan over the widest bucket and the narrowest
    (the terms are the kernel `kda_terms`, the carry `kda_scan`: ops/gdn.py's
    carry with the end-of-chunk decay a column), and the terms alone."""
    from ddp_practice_tpu.ops import kda

    f32 = jnp.float32
    lead = (slots,) if what == "step" else (1, length)
    wide = _sds(lead + (32, 128), f32)
    args = (wide, wide, wide, wide, _sds(lead + (32,), f32),
            _sds((lead[0], 32, 128, 128), f32))
    if what == "terms":
        return kda.kda_terms_kernel, args[:5]
    return (kda.kda_step if what == "step" else kda.kda_scan), args


def _sala(what, slots=32, blocks_per_slot=536, page=64):
    """ops/sparse_attention.py's kernels and `ssm_step` at the MiniCPM-SALA
    cell's widths (perf/configs/minicpm_sala_9b_pp4.json, perf/traffic/
    long_docs_s32.json): 32 query heads of 128 on 2 KV heads, pages of 64
    tokens 256 lanes wide, 32 slots of 536 pages; the list walk over 128
    columns a KV head, a 2,048-token chunk against the whole table with its
    selection, the selection of a decode step, and the step kernel at a
    group a head (32 x 128 x 128 float32 a slot)."""
    from ddp_practice_tpu.ops import sparse_attention as sa, ssm

    i32, f32, spec = jnp.int32, jnp.float32, sa.SparseSpec()
    blocks = 1 + slots * blocks_per_slot
    pool = _sds((blocks, page, 256))
    index = _sds((blocks, spec.rows, 256))
    if what == "walk":
        return sa.sparse_walk, (
            _sds((slots, 32, 128)), pool, pool,
            _sds((slots, 2, spec.list_pages), i32), _sds((slots, 2), i32),
            _sds((slots, 2), i32))
    if what == "select":
        return functools.partial(sa.sparse_select, spec=spec, kv_heads=2), (
            _sds((slots, 32, 128)), index,
            _sds((slots, blocks_per_slot), i32), _sds((slots,), i32),
            _sds((slots,), i32))
    if what == "prefill":
        def chunk(q, k, v, index, table, pos0):
            rows = jnp.take(index, table, axis=0).reshape(-1, 2, 128)
            picked = sa.prefill_selection(
                q, rows, pos0 + jnp.arange(2048), jnp.int32(0), spec)
            return sa.sparse_prefill(q, k, v, picked, table, pos0,
                                     block=page)

        return chunk, (_sds((2048, 2, 16, 128)), pool, pool, index,
                       _sds((blocks_per_slot,), i32), _sds((), i32))
    return ssm.ssm_step, (
        _sds((slots, 32, 128), f32), _sds((slots, 32), f32),
        _sds((32,), f32), _sds((slots, 32, 128), f32),
        _sds((slots, 32, 128), f32), _sds((32,), f32),
        _sds((slots, 32, 128, 128), f32))


def _thinker(what, slots=32, blocks_per_slot=240, page=64, window=4096):
    """The SmallThinker cell's kernels at its widths (perf/configs/
    smallthinker_21b_pp7.json, perf/traffic/short_long_s32.json): 28 query
    heads of 128 on 4 KV heads, pages of 64 tokens 512 lanes wide, 32 slots
    of 240 table columns in both page groups; a 2,048-token chunk through
    `window_prefill` under a run-time window (a window layer's pool backs 97
    pages a slot), a decode step's walk from the window's first page under
    the name `window_walk`, a global layer's walk of the whole context as
    `paged_decode` (its pool backs all 240), and `moe_gmm_glu` with the ReGLU
    body at 64 experts of 2560 -> 768 -> 2560 (11.8 MB an expert, two deep
    in VMEM)."""
    from ddp_practice_tpu.ops import window_attention as wa
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention
    from ddp_practice_tpu.ops.moe import expert_glu_tiles

    i32 = jnp.int32
    pool = _sds((1 + slots * 97, page, 512))
    if what == "prefill":
        def chunk(q, k, v, table, pos0, win, real):
            return wa.window_prefill(q, k, v, table, pos0, window=win,
                                     real=real)

        return chunk, (_sds((2048, 4, 7, 128)), pool, pool,
                       _sds((blocks_per_slot,), i32), _sds((), i32),
                       _sds((), i32), _sds((), i32))
    if what == "walk":
        def step(q, k, v, table, lengths):
            return paged_decode_attention(
                q, k, v, table, lengths,
                wa.window_start(lengths, None, window), n_heads=28,
                n_kv_heads=4, name="window_walk")

        return step, (_sds((slots, 1, 28 * 128)), pool, pool,
                      _sds((slots, blocks_per_slot), i32),
                      _sds((slots,), i32))
    if what == "global_walk":
        def step(q, k, v, table, lengths, start):
            return paged_decode_attention(
                q, k, v, table, lengths, start, n_heads=28, n_kv_heads=4)

        pool = _sds((1 + slots * blocks_per_slot, page, 512))
        return step, (_sds((slots, 1, 28 * 128)), pool, pool,
                      _sds((slots, blocks_per_slot), i32),
                      _sds((slots,), i32), _sds((slots,), i32))
    rows_a_tile = {"glu_decode": 16, "glu_chunk": 128}[what]
    picks = {16: slots * 6, 128: 2048 * 6}[rows_a_tile]
    tiles = -(-picks // rows_a_tile) + 64

    def mlp(rows, wg, wu, wd, tile_expert, used):
        return expert_glu_tiles(rows, wg, wu, wd, tile_expert, used,
                                tile=rows_a_tile, activation="relu")

    return mlp, (_sds((tiles * rows_a_tile, 2560)),
                 _sds((64, 2560, 768)), _sds((64, 2560, 768)),
                 _sds((64, 768, 2560)),
                 _sds((tiles,), i32), _sds((1,), i32))


def _paged_hd256(slots=128, blocks_per_slot=76, page=64):
    """The Qwen3-Next cell's attention: 16 query heads of 256 lanes on 2 KV
    heads (a group of 8), pages of 64 tokens 512 lanes wide."""
    from ddp_practice_tpu.ops.decode_attention import paged_decode_attention

    def step(q, k, v, table, lengths, start):
        return paged_decode_attention(
            q, k, v, table, lengths, start, n_heads=16, n_kv_heads=2)

    pool = _sds((1 + slots * blocks_per_slot, page, 512))
    return step, (_sds((slots, 1, 16 * 256)), pool, pool,
                  _sds((slots, blocks_per_slot), jnp.int32),
                  _sds((slots,), jnp.int32), _sds((slots,), jnp.int32))


def _moe_glu_qwen(rows_a_tile):
    """The gated expert kernel at the Qwen3-Next cell's widths: 128 held
    experts of 2048 -> 512 -> 2048 under a 512-wide router with 10 picks; a
    decode step's tiles of 16 rows and a 4,096-token prompt's of 128."""
    from ddp_practice_tpu.ops.moe import expert_glu_tiles

    picks = {16: 128 * 10, 128: 4096 * 10}[rows_a_tile]
    tiles = -(-picks // rows_a_tile) + 128

    def mlp(rows, wg, wu, wd, tile_expert, used):
        return expert_glu_tiles(rows, wg, wu, wd, tile_expert, used,
                                tile=rows_a_tile)

    return mlp, (_sds((tiles * rows_a_tile, 2048)),
                 _sds((128, 2048, 512)), _sds((128, 2048, 512)),
                 _sds((128, 512, 2048)),
                 _sds((tiles,), jnp.int32), _sds((1,), jnp.int32))


def _moe_rows(which, n, k, experts, width, held=128):
    """The held experts' row movement (ops/moe.py, PR 39) at a cell's
    widths: `moe_rows_fill` holds all n tokens' rows in VMEM (16 MB at a
    4,096-token prompt of width 2,048) and `moe_rows_sum` all n float32 sums
    (32 MB there); sorted tokens and weights of every pick in SMEM."""
    from ddp_practice_tpu.ops import moe

    tile = moe._row_tile(n * k / experts)
    tiles = -(-n * k // tile) + held
    i32 = lambda *shape: _sds(shape, jnp.int32)

    def lay_of(sorted_pick, first, rows, tile_expert, used):
        return {"sorted_pick": sorted_pick, "sorted_token": sorted_pick // k,
                "tile_first_pick": first, "tile_rows": rows,
                "tile_expert": tile_expert, "tiles_used": used}

    def fill(src, *lay):
        return moe.held_rows_fill_kernel(src, lay_of(*lay), tile=tile)

    def total(out, weights, *lay):
        return moe.held_rows_sum_kernel(out, lay_of(*lay), weights, BF16,
                                        tile=tile)

    lay = (i32(n * k), i32(tiles), i32(tiles), i32(tiles), i32(1))
    if which == "fill":
        return fill, (_sds((n, width)),) + lay
    return total, (_sds((tiles * tile, width)),
                   _sds((n, k), jnp.float32)) + lay


def _kernel_calls(text):
    """Names of the compiled Pallas custom calls, in program order."""
    return [ln.split("=")[0].split("%")[-1].strip().split(".")[0]
            for ln in text.splitlines()
            if "custom-call(" in ln and "tpu_custom_call" in ln]


def _scoped_vmem(text):
    """{kernel name: bytes of scoped VMEM the compiler gave it}."""
    return {
        re.search(r"%(\w+?)(\.\d+)? = ", ln).group(1): int(re.search(
            r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
            r'"offset":"0","size":"(\d+)"', ln).group(1))
        for ln in text.splitlines()
        if "custom-call(" in ln and "tpu_custom_call" in ln}


KERNELS = {
    "hybrid_paged_grouped_32q_2kv": _paged_grouped,
    "hybrid_ssm_step": _ssm_step,
    "hybrid_ssm_step_32_slots": functools.partial(_ssm_step, 32),
    "hybrid_moe_gmm_decode_tiles": functools.partial(_moe_gmm, 16),
    "hybrid_moe_gmm_prompt_tiles": functools.partial(_moe_gmm, 64),
    "jamba_sel_step_256_slots": functools.partial(_sel, "step"),
    "jamba_sel_scan_1024": functools.partial(_sel, "scan"),
    "jamba_sel_scan_128": functools.partial(_sel, "scan", length=128),
    "jamba_paged_group20_page64": _paged_group20,
    "qwen_gdn_step_128_slots": functools.partial(_gdn, "step"),
    "qwen_gdn_scan_4096": functools.partial(_gdn, "scan"),
    "qwen_gdn_scan_256": functools.partial(_gdn, "scan", length=256),
    "qwen_gdn_terms_4096": functools.partial(_gdn, "terms"),
    "qwen_gdn_terms_256": functools.partial(_gdn, "terms", length=256),
    "qwen_paged_hd256_group8_page64": _paged_hd256,
    "ling_kda_step_128_slots": functools.partial(_kda, "step"),
    "ling_kda_scan_2048": functools.partial(_kda, "scan"),
    "ling_kda_scan_256": functools.partial(_kda, "scan", length=256),
    "ling_kda_terms_2048": functools.partial(_kda, "terms"),
    "sala_sparse_walk_32_slots": functools.partial(_sala, "walk"),
    "sala_sparse_prefill_2048": functools.partial(_sala, "prefill"),
    "sala_ssm_step_group_a_head": functools.partial(_sala, "step"),
    "thinker_window_prefill_2048": functools.partial(_thinker, "prefill"),
    "thinker_window_walk_32_slots": functools.partial(_thinker, "walk"),
    "thinker_global_walk_32_slots": functools.partial(_thinker,
                                                      "global_walk"),
    "thinker_reglu_decode_tiles": functools.partial(_thinker, "glu_decode"),
    "thinker_reglu_chunk_tiles": functools.partial(_thinker, "glu_chunk"),
    **{f"rows_{which}_{cell}_{n}": functools.partial(
        _moe_rows, which, n, k, experts, width)
       for cell, k, experts, width, ns in (
           ("qwen", 10, 512, 2048, (128, 4096)), ("nemo", 22, 512, 1024,
                                                   (768,)),
           ("kanana", 6, 128, 2048, (1024,)))
       for n in ns for which in ("fill", "sum")},
    "qwen_moe_glu_decode_tiles": functools.partial(_moe_glu_qwen, 16),
    "qwen_moe_glu_prompt_tiles": functools.partial(_moe_glu_qwen, 128),
    "latent_paged_mla_page64": _paged_mla,
    "latent_moe_glu_decode_tiles": functools.partial(_moe_glu, 16),
    "latent_moe_glu_chunk_tiles": functools.partial(_moe_glu, 64),
    "flash_fwd": functools.partial(_flash, grad=False),
    "flash_fwd_bwd": functools.partial(_flash, grad=True),
    "flash_qkv_fwd_bwd": _flash_qkv,
    # the one-kernel backward (PR 31) at the LM cells' shape, at what a
    # device of the four-chip cell gets, and at the ends of its shape
    # rule: the longest bf16 sequence it takes, and float32
    "flash_bwd_one_kernel_cell": functools.partial(_flash_bwd, B),
    "flash_bwd_one_kernel_dp4_share": functools.partial(_flash_bwd, B // 4),
    "flash_bwd_one_kernel_longest": functools.partial(_flash_bwd, 1, 6144),
    "flash_bwd_one_kernel_float32": functools.partial(
        _flash_bwd, 1, dtype=jnp.float32),
    # past the rule the two kernels run; ONE kernel there is refused
    "flash_bwd_two_kernels_s16384": functools.partial(_flash_bwd, 1, 16384),
    # two heads a 128-lane tile (a roll each way and a select), one head
    # a tile, one head over two tiles
    "rope_flat_d64": _rope_flat,
    "rope_flat_d128": functools.partial(_rope_flat, 128),
    "rope_flat_d256": functools.partial(_rope_flat, 256),
    "flash_short_fwd_bwd": _flash_short,
    "flash_short_fwd_bwd_longest": lambda: _flash_short(
        8, __import__("ddp_practice_tpu.ops.flash_attention", fromlist=["x"]
                      ).SHORT_SEQ_MAX),
    # h 5 x d 48 does not pack into 128 lanes: the folded (b*h, s, d) path
    "flash_folded_fwd_bwd": functools.partial(_flash, grad=True, h=5, d=48),
    "decode_single_block_L640": functools.partial(_decode_packed, 640),
    "decode_multi_block_L2048": functools.partial(_decode_packed, 2048),
    "decode_int8_L1024": functools.partial(_decode_packed, 1024, int8=True),
    "decode_int8_L2048": functools.partial(_decode_packed, 2048, int8=True),
    "paged_b16": functools.partial(_paged, 16),
    "paged_b16_no_start": functools.partial(_paged, 16, with_start=False),
    # the flood cell's engine (PERF.md section 4): 64 slots, 66 table
    # columns (not a multiple of the walk's 8 pages), a 4,352-page pool
    "paged_b16_flood": functools.partial(
        _paged, 16, slots=64, blocks_per_slot=66, pool=4352),
    "paged_b32": functools.partial(_paged, 32),
    "paged_int8_b16": functools.partial(_paged, 16, int8=True),
    "paged_int8_b32": functools.partial(_paged, 32, int8=True),
    "fused_encoder_vit_tiny": functools.partial(_fused_encoder, False),
    "fused_encoder_lm_tiny_causal": functools.partial(_fused_encoder, True),
    "moe_sorted_gmm_tgmm": _moe_sorted,
}


# the grouped page walks at their cells' shapes: eight pages a chunk since
# PR 45 (tests/test_decode_attention.py holds the rule at these shapes),
# 512 tokens but for the hybrid's 16-token pages
GROUPED_WALKS = {
    "hybrid_paged_grouped_32q_2kv", "jamba_paged_group20_page64",
    "qwen_paged_hd256_group8_page64", "thinker_window_walk_32_slots",
    "thinker_global_walk_32_slots"}


# the MoE case spends 12 s in the sort around its kernels: `slow` by
# pytest.ini's own rule
@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.slow) if n.startswith("moe") else n
    for n in sorted(KERNELS)
])
def test_kernel_compiles_for_v5e(topo, name):
    fn, args = KERNELS[name]()
    text = _compile(fn, *args, device=topo.devices[0])
    if name.startswith("flash_bwd"):
        # a kernel is scoped 16 MiB of VMEM by default and no flag raises
        # it: the whole-sequence dq and the strip's score tiles fit
        vmem = _scoped_vmem(text)
        want = (["flash_bwd_packed"] if "one_kernel" in name else
                ["flash_bwd_dkv_packed", "flash_bwd_dq_packed"])
        assert sorted(vmem) == want, vmem
        assert max(vmem.values()) <= 16 * 2**20, vmem
    if name.startswith("rope_flat"):
        # named apart from the flash kernels: train_flash_dev_pct sums
        # the ops named `flash_*`
        assert _kernel_calls(text) == ["rope_flat_qk", "rope_flat_bwd"]
    if name.startswith("paged"):
        # ONE device op a call, named by the kernel's `name=`:
        # perf/lib/readers.py sums every traced op whose name holds
        # "paged_decode" (a gather beside the compute would be a second)
        calls = _kernel_calls(text)
        assert len(calls) == 1 and "paged_decode" in calls[0], calls
    if name.startswith("hybrid"):
        # each is ONE device op under the name the benchmark's readers
        # look for (perf/layer_metrics/flood_ssm_*, flood_moe_*)
        want = {"hybrid_paged": "paged_decode", "hybrid_ssm_s": "ssm_step",
                "hybrid_moe_g": "moe_gmm"}[name[:12]]
        calls = _kernel_calls(text)
        assert len(calls) == 1 and want in calls[0], calls
    if name.startswith("hybrid_ssm"):
        # two groups of 16 heads a cell since PR 51 (1 MiB each way, two
        # deep): inside the 16 MiB a kernel is scoped by default
        assert max(_scoped_vmem(text).values()) <= 16 * 2**20
    if name.startswith("jamba"):
        # the names perf/layer_metrics/flood_sel_* and
        # flood_paged_decode_roofline sum by
        want = {"jamba_sel_st": "sel_step", "jamba_sel_sc": "sel_scan",
                "jamba_paged_": "paged_decode"}[name[:12]]
        calls = _kernel_calls(text)
        assert len(calls) == 1 and calls[0].endswith(want), calls
    if name.startswith("qwen"):
        # the names perf/layer_metrics/flood_gdn_*, flood_moe_glu_* and
        # flood_paged_decode_roofline sum by
        # (a prompt's call is two ops: the terms, then the carry)
        want = {"qwen_gdn_st": ["gdn_step"],
                "qwen_gdn_sc": ["gdn_terms", "gdn_scan"],
                "qwen_gdn_te": ["gdn_terms"],
                "qwen_paged_": ["paged_decode"],
                "qwen_moe_gl": ["moe_gmm_glu"]}[name[:11]]
        calls = [c.split("/")[-1] for c in _kernel_calls(text)]
        assert calls == want, calls
    if name.startswith("ling"):
        # the names perf/layer_metrics/flood_kda_* sum by (a prompt's call
        # is two ops: the terms, then the carry)
        want = {"ling_kda_st": ["kda_step"],
                "ling_kda_sc": ["kda_terms", "kda_scan"],
                "ling_kda_te": ["kda_terms"]}[name[:11]]
        calls = [c.split("/")[-1] for c in _kernel_calls(text)]
        assert calls == want, calls
    if name.startswith("sala"):
        # the names perf/layer_metrics/flood_sparse_* and
        # flood_ssm_step_roofline sum by; none is named `paged_decode`
        want = {"sala_sparse_w": "sparse_walk", "sala_sparse_p":
                "sparse_prefill", "sala_ssm_step": "ssm_step"}[name[:13]]
        calls = _kernel_calls(text)
        assert len(calls) == 1 and calls[0].endswith(want), calls
    if name.startswith("thinker"):
        # the names perf/layer_metrics/flood_window_* and flood_moe_glu_*
        # sum by; a window layer's walk is not named `paged_decode`
        want = {"thinker_window_p": "window_prefill", "thinker_window_w":
                "window_walk", "thinker_global_w": "paged_decode",
                "thinker_reglu_de": "moe_gmm_glu",
                "thinker_reglu_ch": "moe_gmm_glu"}[name[:16]]
        calls = _kernel_calls(text)
        assert len(calls) == 1 and calls[0].endswith(want), calls
    if name == "thinker_window_prefill_2048":
        # 8 pages = 512 keys a step by the rule (PR 46): every head's q,
        # output and state, the pages' buffers two deep and a head's score
        # tile stay inside what the call asks for (`vmem_limit_bytes`)
        from ddp_practice_tpu.ops import window_attention as wa

        assert wa.pages_per_step(64, 512, 7 * wa.WINDOW_TILE, 240) == 8
        (vmem,) = _scoped_vmem(text).values()
        assert vmem <= 24 * 2**20, vmem
    if name in GROUPED_WALKS:
        # the chunk's four buffers and the tile's scores stay inside what a
        # kernel is scoped, 16 MiB
        (vmem,) = _scoped_vmem(text).values()
        assert vmem <= 16 * 2**20, vmem
    if name.startswith("rows"):
        # ONE device op each, named `moe_` and not `moe_gmm*`: the rooflines
        # of the expert kernels sum the ops named `moe_gmm*` and must not
        # hold these
        calls = _kernel_calls(text)
        assert len(calls) == 1 and calls[0].endswith(
            "moe_rows_" + name.split("_")[1]), calls
    if name.startswith("latent"):
        # the names perf/layer_metrics/flood_mla_*, flood_moe_glu_* sum by
        want = "paged_decode_mla" if "mla" in name else "moe_gmm_glu"
        calls = _kernel_calls(text)
        assert len(calls) == 1 and calls[0].endswith(want), calls


def test_one_backward_kernel_past_its_rule_is_refused(topo):
    """What `_ONE_KERNEL_BWD_VMEM` keeps from the compiler: at 16,384
    positions the whole-sequence dq alone is 16 MiB of VMEM."""
    fn, args = _flash_bwd(1, 16384, one_kernel=True)
    with pytest.raises(Exception, match="vmem"):
        _compile(fn, *args, device=topo.devices[0])


def test_flash_compiles_sharded_over_four_devices(topo):
    """The refusal the first rehearsal found, now a guard: a flash call
    directly under GSPMD `jit` with its batch sharded over data=4 is
    "Mosaic kernels cannot be automatically partitioned". Through
    ops.attention the kernel runs in a shard_map island, so each device
    gets its own 2 of the 8 rows and nothing is gathered."""
    from ddp_practice_tpu.ops.attention import dot_product_attention

    mesh = build_mesh(MeshConfig(), devices=topo.devices)
    assert mesh.devices.size == 4
    set_current_mesh(mesh)
    sharded = NamedSharding(mesh, P(MeshConfig.AXIS_DATA))
    x = jax.ShapeDtypeStruct((B, S, H, D), BF16, sharding=sharded)

    def loss(q, k, v):
        return dot_product_attention(
            q, k, v, causal=True, impl="flash"
        ).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 2, len(calls)  # fwd, and ONE backward
    # the compiled custom calls are named by the kernels' `name=`, not by
    # the island around them ("shard_map"): a device trace's "XLA Ops"
    # line shows these names, and perf/layer_metrics/train_flash_dev_pct
    # reads them
    named = sorted(ln.split("=")[0].strip().lstrip("%").split(".")[0]
                   for ln in calls)
    assert named == ["flash_bwd_packed", "flash_fwd_packed"], named
    per_device = f"bf16[{B // 4},{S},{HD}]"
    assert all(per_device in ln for ln in calls), calls[0][:300]
    assert "all-gather" not in text


def _attention_grad(device, *, batch, seq, causal=False, mesh=None,
                    **attn_kw):
    """HLO of the loss and d loss / d (params, x) of one bf16
    SelfAttention of 12 heads of 64 (ViT-B/16's and lm_base's), compiled
    for `device`, or for `mesh` with the batch split over 'data'."""
    from ddp_practice_tpu.models.vit import SelfAttention

    attn = SelfAttention(num_heads=H, dtype=BF16, causal=causal, **attn_kw)
    variables = jax.eval_shape(      # 4 rows: one a device of a data=4 mesh
        lambda: attn.init(jax.random.PRNGKey(0), jnp.zeros((4, seq, HD), BF16)))

    def loss(variables, x):
        return attn.apply(variables, x).astype(jnp.float32).sum()

    fn = jax.value_and_grad(loss, argnums=(0, 1))
    if mesh is None:
        return _compile(fn, variables, _sds((batch, seq, HD)),
                        device=device, min_kernels=2)
    rep = NamedSharding(mesh, P())
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        variables)
    x = jax.ShapeDtypeStruct(
        (batch, seq, HD), BF16,
        sharding=NamedSharding(mesh, P(MeshConfig.AXIS_DATA)))
    return jax.jit(fn).lower(variables, x).compile().as_text()


def test_vit_attention_takes_the_short_kernels_unasked(topo):
    """ViT-B/16's SelfAttention with NO attn_impl given, at the benchmark
    cell's shape: both short kernels are in the program, no (b, h, s, s)
    float32 tensor is, the projection reaches the kernels as the
    convolution wrote it (no `pad`, no relayout `copy` of it), and the
    cotangent leaves the backward kernel flat."""
    text = _attention_grad(topo.devices[0], batch=128, seq=196)
    assert _kernel_calls(text) == ["flash_short_fwd", "flash_short_bwd"]
    assert "f32[128,12,196,196]" not in text
    assert "bf16[128,12,196,196]" not in text
    qkv = r"bf16\[128,196,(2304|3,12,64)\]"
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(rf"= {qkv}\S* (pad|copy)\(", ln)]
    # ONE relayout is XLA's own: it keeps (128, 196, .) activations
    # sequence-major, and the weight gradient's convolution takes the
    # flat cotangent that way ({2,0,1}); under _attention there were
    # five such copies of the projection and three pads
    assert len(moved) <= 1 and not any(" pad(" in m for m in moved), moved
    assert all("{2,0,1" in m for m in moved), moved
    assert re.search(r"flash_short_bwd\S* = bf16\[128,196,2304\]", text)
    # the forward kernel reads what the projection's convolution wrote
    fwd = next(ln for ln in text.splitlines()
               if "custom-call(" in ln and "flash_short_fwd" in ln)
    operands = set(re.findall(r"custom-call\((.*?)\), custom_call_target",
                              fwd)[0].split(", "))
    assert len(operands) == 1 and "fusion" in operands.pop(), fwd[:300]


def test_lm_attention_keeps_the_streaming_kernels(topo):
    """The LM cells' block (8 x 2048, causal, "flash" named): the packed
    streaming forward and, since PR 31, ONE backward kernel, not the
    short kernels; and with nothing named, 2048 is past the short
    kernels' range: plain XLA, no kernel."""
    text = _attention_grad(topo.devices[0], batch=B, seq=S, causal=True,
                           attn_impl="flash")
    assert sorted(_kernel_calls(text)) == [
        "flash_bwd_packed", "flash_fwd_packed"]
    from ddp_practice_tpu.models.vit import SelfAttention

    assert SelfAttention(num_heads=H, causal=True).resolve_attn_impl(
        S, D) == "xla"


def _defs(text):
    """{name: (op, operand names)} of every instruction of the HLO."""
    defs = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*)", ln)
        if m:
            defs[m.group(1)] = (
                m.group(2), re.findall(r"%([\w.\-]+)", m.group(3).split(
                    "), ")[0]))
    return defs


def _through(defs, name):
    """`name`, or the instruction it is a view of (bitcasts and tuple
    elements are looked through)."""
    while defs[name][0] in ("bitcast", "get-tuple-element"):
        name = defs[name][1][0]
    return name


@pytest.mark.parametrize("layout", ["one_device", "data4"])
def test_lm_attention_block_stays_flat(topo, layout):
    """The LM cells' block with rope (8 rows of 2048 a device, 12 heads
    of 64, "flash" named) with its gradient: every (8, 2048, .)
    activation stays row-major from the qkv projection to the out
    projection. The forward kernel's q and k are the rotary kernel's
    outputs and its v the projection's own fusion, its output reaches
    the out projection's dot as written, and the program holds at most
    one relayout `copy` of such an activation (through the 4-D code:
    eight, two of them float32: PERF.md section 6, PR 33)."""
    mesh = None
    if layout == "data4":
        mesh = build_mesh(MeshConfig(), devices=topo.devices)
        set_current_mesh(mesh)
    text = _attention_grad(
        topo.devices[0], batch=B * (4 if mesh is not None else 1), seq=S,
        causal=True, rope=True, attn_impl="flash", mesh=mesh)
    assert sorted(_kernel_calls(text)) == [
        "flash_bwd_packed", "flash_fwd_packed", "rope_flat_bwd",
        "rope_flat_qk"]
    assert "all-gather" not in text
    defs = _defs(text)
    by_kernel = {n.split(".")[0]: n for n, (op, _) in defs.items()
                 if op == "custom-call" and n.split(".")[0] in (
                     "flash_fwd_packed", "rope_flat_qk")}
    fwd = defs[by_kernel["flash_fwd_packed"]][1]
    q, k, v = (_through(defs, o) for o in fwd[:3])
    assert q == k == by_kernel["rope_flat_qk"], (q, k)
    assert defs[v][0] == "fusion", defs[v]          # the qkv matmul
    rope_in = {_through(defs, o)
               for o in defs[by_kernel["rope_flat_qk"]][1][2:]}
    assert rope_in == {v}, rope_in
    # whoever reads the kernel's output (the out projection's fusion, the
    # backward kernel) reads the custom call's own tuple element
    out_views = {n for n, (op, ops) in defs.items()
                 if op in ("get-tuple-element", "bitcast")
                 and _through(defs, n) == by_kernel["flash_fwd_packed"]}
    readers = {op for n, (op, ops) in defs.items()
               if op not in ("get-tuple-element", "bitcast")
               and set(ops) & out_views}
    assert readers and "copy" not in readers, readers
    assert "fusion" in readers, readers
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if re.search(rf"= (bf16|f32)\[{B},{S},[^\]]*\]\S* copy\(", ln)]
    assert len(moved) <= 1, moved


@pytest.mark.parametrize("layout", ["data4", "data2_tensor2"])
def test_short_attention_compiles_on_the_mesh(topo, layout):
    """Under a mesh "auto" opens the same shard_map island as "flash":
    each device runs the short kernels on its own images (data) and its
    own heads (tensor), nothing is gathered. With the heads whole the
    flat projection feeds them; split over tensor=2 each device flattens
    its own 6 heads of the (3, h, hd) projection."""
    mesh_cfg = (MeshConfig(data=2, tensor=2) if layout == "data2_tensor2"
                else MeshConfig())
    mesh = build_mesh(mesh_cfg, devices=topo.devices)
    set_current_mesh(mesh)
    from ddp_practice_tpu.models.vit import SelfAttention

    attn = SelfAttention(num_heads=H, dtype=BF16)
    variables = jax.eval_shape(
        lambda: attn.init(jax.random.PRNGKey(0), jnp.zeros((4, 196, HD), BF16)))
    rep = NamedSharding(mesh, P())
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        variables)
    x = jax.ShapeDtypeStruct(
        (128, 196, HD), BF16,
        sharding=NamedSharding(mesh, P(MeshConfig.AXIS_DATA)))

    def loss(variables, x):
        return attn.apply(variables, x).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables, x).compile().as_text()
    assert _kernel_calls(text) == ["flash_short_fwd", "flash_short_bwd"]
    dp, tp = mesh.shape["data"], mesh.shape["tensor"]
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    per_device = f"bf16[{128 // dp},196,{3 * HD // tp}]"
    assert all(per_device in ln for ln in calls), calls[0][:300]
    assert "all-gather" not in text


def test_forced_fused_refuses_a_mesh_at_trace_time(topo):
    from ddp_practice_tpu.models.vit import EncoderBlock

    block = EncoderBlock(3, 768, fused=True)
    x = jnp.zeros((8, 64, 192))
    variables = block.init(jax.random.PRNGKey(0), x)
    set_current_mesh(build_mesh(MeshConfig(), devices=topo.devices))
    with pytest.raises(ValueError, match="4-device mesh"):
        jax.eval_shape(block.apply, variables, x)


# ------------------------------------------------------- scopes (PR 34)
# The contract of perf/lib/scopes.py, held at the benchmark cells' own
# shapes (widths, batch, sequence, engine) with the depth cut to one layer
# of each kind: a layer more adds no kind of op, and a whole step of twelve
# takes a tier-1 minute.
CELL_DEPTH = {
    "lm": lambda cfg: dict(cfg, n_layer=1),
    "vit": lambda cfg: dict(cfg, num_hidden_layers=1),
    "nemotron_h": lambda cfg: dict(cfg, hybrid_override_pattern="ME*"),
    "deepseek_v3": lambda cfg: dict(cfg, layers_run=2),   # dense, experts
    "jamba": lambda cfg: dict(cfg, num_hidden_layers=2, attn_layer_period=2,
                              attn_layer_offset=1),       # Mamba, attention
    "qwen3_next": lambda cfg: dict(cfg, layers_run=2,     # DeltaNet,
                                   full_attention_interval=2),  # attention
    "minicpm_sala": lambda cfg: dict(cfg, layers_run=2,   # sparse, lightning
                                     layers_published=[9, 10]),
    "smallthinker": lambda cfg: dict(cfg, layers_run=2,   # global, window
                                     layers_published=[0, 1]),
    "ling3": lambda cfg: dict(cfg, layers_run=2,          # KDA + dense,
                              layer_group_size=2,         # latent + experts
                              first_k_dense_replace=1),
}


def _cell_files(name, whole=False):
    """(cell, config, traffic) of a benchmark cell, the depth cut unless
    `whole`."""
    from perf import run as harness

    _, cell, cfg, traffic = harness.load_cell(name)
    return cell, (cfg if whole else CELL_DEPTH[cfg["family"]](cfg)), traffic


def _train_program(topo, name, whole=False):
    """{"train_step": a thunk that lowers the cell's resident train step}:
    what `Trainer` builds for the cell (perf/drivers/train.py
    build_trainer), from shapes alone on the described chips."""
    from perf.drivers import train as driver
    from ddp_practice_tpu.train import steps

    cell, cfg, traffic = _cell_files(name, whole)
    family = driver.family_of(cfg)
    family.prepare(cfg)
    opts = dict(traffic["trainer"], **family.trainer_options(cfg, traffic))
    b = traffic["batch_per_chip"] * cell["chips"]
    depth = cfg.get("n_layer", cfg.get("num_hidden_layers"))
    if cfg["family"] == "lm":
        s = traffic["seq_len"]
        kw = dict(vocab_size=cfg["vocab_size"], max_len=s, depth=depth,
                  attn_impl=opts["attn_impl"], pos_emb=opts["pos_emb"],
                  tied_embeddings=opts["tied_embeddings"])
        sample = jnp.zeros((b, s), jnp.int32)
    else:
        side = cfg["image_size"]
        kw = dict(num_classes=cfg["num_labels"], axis_name=None, depth=depth)
        sample = jnp.zeros((b, side, side, cfg["num_channels"]), jnp.float32)
    mesh, net, tx, abstract, shardings = _abstract_trainer(
        topo, model=cfg["program_model"],
        mesh_cfg=MeshConfig(data=cell["chips"]), model_kwargs=kw,
        sample=sample)
    if cfg["family"] == "lm":
        step = steps.make_resident_lm_train_step(
            net, tx, window=s + 1, seed=0, mesh=mesh,
            state_shardings=shardings)
        args = ({"tokens": _sds((b * 12 * (s + 1),), jnp.int32)},
                _sds((1, b), jnp.int32))
    else:
        step = steps.make_resident_train_step(
            net, tx, seed=0, mesh=mesh, state_shardings=shardings)
        args = ({"image": _sds((b * 12,) + sample.shape[1:], jnp.uint8),
                 "label": _sds((b * 12,), jnp.int32)},
                _sds((1, b), jnp.int32))
    return {"train_step": lambda: step.lower(abstract, *args)}


def _serve_programs(topo, name, whole=False):
    """{program: a thunk that lowers it} of the cell's `PagedEngine`, built
    as perf/drivers/serve.py build_engine does: the first bucket's
    admission prefill (the prefix-cache chunk where the cell has one) and
    the decode burst. The engine allocates its pool where jax.devices() says
    (the CPU); only its traced programs are lowered for the described
    chip."""
    import dataclasses

    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine
    from perf.drivers import serve as driver

    cell, cfg, traffic = _cell_files(name, whole)
    family = driver.family_of(cfg)
    opts = family.model_options(cfg)
    if cfg["family"] == "lm":
        opts["depth"] = cfg["n_layer"]
    model = create_model(cfg["program_model"], policy=PrecisionPolicy.bf16(),
                         **opts)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, BF16),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    eng = traffic["engine"]
    if not whole:   # the pool is allocated for real, on this host
        eng = dict(eng, num_blocks=2 * eng["max_blocks_per_slot"] + 1)
        if "window_blocks" in eng:
            eng["window_blocks"] = eng["num_blocks"]
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    engine = PagedEngine(model, params, EngineConfig(
        prompt_buckets=tuple(eng["buckets"]), block_size=eng["page"],
        decode_burst=eng["burst"], temperature=0.0,
        **{k: v for k, v in eng.items() if k in fields}))
    slots, mb, i32 = eng["max_slots"], engine.max_blocks_per_slot, jnp.int32
    on_chip = functools.partial(_on, topo.devices[0])
    logits = _sds((slots, model.vocab_size), model.dtype)
    w = engine.buckets[0]
    # a table a page group where the model has a window group
    table = lambda rows: _sds((rows, mb), i32) if engine.wgroup is None \
        else {"global": _sds((rows, mb), i32),
              "window": _sds((rows, mb), i32)}
    if not engine._canonical:
        prefill = lambda: engine._prefill_jit.lower(*on_chip((
            params, engine._cache, logits, _sds((1, w), i32), _sds((), i32),
            _sds((-(-w // eng["page"]),), i32), _sds((), i32))))
    else:
        prefill = lambda: engine._prefix_jit.lower(*on_chip((
            params, engine._cache, logits, _sds((1, w), i32), _sds((), i32),
            _sds((), i32), table(1), _sds((), i32))))
    decode = lambda: engine._decode_jit.lower(*on_chip((
        params, engine._cache, logits, _sds((slots,), i32),
        _sds((slots,), jnp.bool_), _sds((slots, 2), jnp.uint32),
        table(slots), _sds((slots,), i32))), None)
    return {"prefill": prefill, "decode_burst": decode}


def _cell_programs(topo, name, whole=False):
    cell, cfg, traffic = _cell_files(name, whole)
    build = _train_program if traffic["driver"] == "train" \
        else _serve_programs
    return build(topo, name, whole)


CELLS = ["vitb16_train_224", "gpt2s_train_2k", "gpt2s_train_2k_dp4",
         "gpt2s_serve_flood", "nemo3s_serve_flood", "kanana2_serve_docs",
         "jamba2_serve_batch"]


@contextlib.contextmanager
def _no_frames_in_locations():
    """A Pallas kernel's payload holds its ops' source locations with the
    Python frames that led there (ten of them), the caller's own lines among
    them. While open, a location carries no frames, so that two builds of
    one program from two lines of a test are one text."""
    key = "jax_traceback_in_locations_limit"
    was = getattr(jax.config, key)
    jax.config.update(key, 0)
    try:
        yield
    finally:
        jax.config.update(key, was)


def _holds_the_contract(cell, prog, text, sample=True) -> int:
    """The scopes contract on one compiled program; its kernels, counted.
    `sample` false: the program's `sample` scope holds no op the contract
    counts (a chunk of a recurrent model writes ONE carried row, a bare
    dynamic-update-slice: the model hands back its last real row alone)."""
    import test_scopes as contract

    want = {"loss", "optimizer"} if prog == "train_step" \
        else {"sample"} if sample else set()
    contract.hold(text, want | {"attn", "mlp", "norm"}, f"{cell} {prog}")
    for op, name, path, cls in contract.own_ops(text):
        if op == "custom-call":   # a kernel's own instruction, by name
            assert cls and cls[1] == (
                "bwd" if "_bwd" in name else "fwd"), (name, path)
    return contract.kernels_agree(text)


_CELL_TEXTS: dict = {}


def _cell_texts(topo, cell) -> dict:
    """The optimized HLO of every step program of `cell`, built once a
    process: the two tests below share it, so that each compiles the cell
    once (a build is 7-10 s of the compiler at the cells' widths, and one
    test with both read 37-42 s beside five workers, at the tier-1 line)."""
    if cell not in _CELL_TEXTS:
        with _no_frames_in_locations():
            _CELL_TEXTS[cell] = {
                k: lower().compile().as_text()
                for k, lower in _cell_programs(topo, cell).items()}
    return _CELL_TEXTS[cell]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_programs_carry_their_scopes(topo, cell):
    """Every step program of every benchmark cell, compiled for the
    described v5e at the cell's own widths, under the contract of
    tests/test_scopes.py: at least 95% of its own device ops (dot,
    convolution, fusion, reduce, Pallas call, outside a fusion's body) carry
    a path that perf/lib/scopes.py classifies; a train step holds `loss` and
    `optimizer`, both serving programs `sample`; and every kernel's name
    agrees with the class and direction its path reads."""
    assert sum(_holds_the_contract(cell, prog, text)
               for prog, text in _cell_texts(topo, cell).items()) >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_cell_programs_scopes_are_metadata(topo, cell, monkeypatch):
    """The three scopes the contract added are METADATA: with them patched
    away the optimized HLO of every step program of every cell is the same
    text but for `metadata={...}`."""
    import test_scopes as contract

    texts = _cell_texts(topo, cell)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext()
        if name in contract.NEW_SCOPES else real(name))
    with _no_frames_in_locations():
        bares = {k: lower().compile().as_text()
                 for k, lower in _cell_programs(topo, cell).items()}
    for prog, bare in bares.items():
        assert 'op_name="' in bare and not re.search(
            r"[/(](loss|optimizer|sample)[/)]", bare), prog
        assert contract.without_metadata(bare) \
            == contract.without_metadata(texts[prog]), prog


def _kernel_counts(text):
    from collections import Counter

    return Counter(c.split("/")[-1] for c in _kernel_calls(text))


def _expert_layers(n):
    """`n` expert layers' kernels: the rows in, the experts, the sums."""
    return {"moe_rows_fill": n, "moe_gmm_glu": n, "moe_rows_sum": n}


@pytest.mark.parametrize("prog, kernels", [
    ("decode_burst", {"gdn_step": 1, "paged_decode": 1, **_expert_layers(2)}),
    ("prefill", {"gdn_terms": 1, "gdn_scan": 1, **_expert_layers(2)})])
def test_qwen3_next_programs_carry_their_scopes_and_kernels(topo, prog,
                                                            kernels):
    """The new cell's programs compiled for the described v5e at its widths
    and engine, one layer of each mixer (G A), a program a case: the scopes
    contract as above (the three scopes PR 34 added are shown to be metadata
    there, in the engine this cell shares line for line), and the kernels by
    name and count: a decode step 1 `gdn_step`, 1 `paged_decode` and 2
    `moe_gmm_glu` and no `gdn_terms`; an admission prefill 1 `gdn_scan`, 1
    `gdn_terms` beside it (PR 42) and 2 `moe_gmm_glu` (its attention is
    plain XLA); a `moe_rows_fill` and a `moe_rows_sum` beside each
    `moe_gmm_glu` (PR 39)."""
    cell = "qwen3next_serve_mixed"
    with _no_frames_in_locations():
        text = _cell_programs(topo, cell)[prog]().compile().as_text()
    assert _holds_the_contract(cell, prog, text) >= 1
    assert _kernel_counts(text) == kernels


@pytest.mark.slow
def test_qwen3_next_programs_hold_their_kernels_by_count(topo):
    """The same programs one period deep (G G G A, half of `layers_run`): a
    decode step holds 3 `gdn_step`, 1 `paged_decode` and 4 `moe_gmm_glu`, an
    admission prefill 3 `gdn_scan`, 3 `gdn_terms` and 4 `moe_gmm_glu`: a
    layer twice over is the cell's 6 / 2 / 8 and 6 / 6 / 8. (26 s of
    compiling at the published widths: `slow`.)"""
    CELL_DEPTH["qwen3_next"], was = (
        lambda cfg: dict(cfg, layers_run=4)), CELL_DEPTH["qwen3_next"]
    try:
        progs = _cell_programs(topo, "qwen3next_serve_mixed")
    finally:
        CELL_DEPTH["qwen3_next"] = was
    decode = _kernel_counts(progs["decode_burst"]().compile().as_text())
    assert decode == {"gdn_step": 3, "paged_decode": 1,
                      **_expert_layers(4)}, decode
    prefill = _kernel_counts(progs["prefill"]().compile().as_text())
    assert prefill == {"gdn_terms": 3, "gdn_scan": 3,
                       **_expert_layers(4)}, prefill


def test_a_six_layer_prefill_lowers_the_gdn_kernels_once(topo):
    """The cell's prefill program at its published depth (G G G A twice: six
    Gated DeltaNet layers), traced and lowered for the described v5e, not
    compiled: the module holds ONE `gdn_terms` and ONE `gdn_scan` kernel,
    each in a function of its own called six times, because their
    `pallas_call`s are jitted on their own (`ops/gdn.py _terms_call`,
    `_carry_call`). A kernel lowered once a layer is host time in every
    bucket's warm-up, with every executable from the cache (PR 39 lost 13 s
    of `setup_s` so: ROADMAP S5); `gdn_scan` was lowered six times before
    PR 42."""
    CELL_DEPTH["qwen3_next"], was = (
        lambda cfg: dict(cfg, layers_run=8)), CELL_DEPTH["qwen3_next"]
    try:
        text = _cell_programs(
            topo, "qwen3next_serve_mixed")["prefill"]().as_text()
    finally:
        CELL_DEPTH["qwen3_next"] = was
    for kernel, fn in (("gdn_terms", "_terms_call"),
                       ("gdn_scan", "_carry_call")):
        assert text.count(f'kernel_name = "{kernel}"') == 1, kernel
        assert len(re.findall(rf"call @{fn}\b", text)) == 6, fn


@pytest.mark.parametrize("prog, kernels", [
    ("decode_burst", {"ssm_step": 1, "sparse_walk": 1}),
    ("prefill", {"sparse_prefill": 1})])
def test_minicpm_sala_programs_carry_their_scopes_and_kernels(topo, prog,
                                                              kernels):
    """The MiniCPM-SALA cell's programs compiled for the described v5e at
    its widths and engine (32 slots of 536 pages, chunks of the first
    bucket), one layer of each mixer (B L), a program a case: the scopes
    contract, and the kernels by name and count: a decode step 1 `ssm_step`
    and 1 `sparse_walk` (the selection is XLA under `sparse_select`), a
    chunk 1 `sparse_prefill` (ONE kernel since PR 41, the list axis of its
    grid bounded at run time; before, one a list width under a
    `lax.switch`, five at 536 pages a slot; its scan is XLA under
    `ssm_scan`); no op of either is named `paged_decode`."""
    cell = "minicpm_sala_serve_long"
    with _no_frames_in_locations():
        text = _cell_programs(topo, cell)[prog]().compile().as_text()
    assert _holds_the_contract(
        cell, prog, text, sample=prog == "decode_burst") >= 1
    assert prog != "prefill" or "/sample/dynamic_update_slice" in text
    assert _kernel_counts(text) == kernels


# ------------------------------------------------------------ whole steps
@pytest.mark.parametrize("prog, kernels", [
    ("decode_burst", {"paged_decode": 1, "window_walk": 1, "moe_gmm_glu": 2,
                      "moe_rows_fill": 2, "moe_rows_sum": 2}),
    ("prefill", {"window_prefill": 2, "moe_gmm_glu": 2, "moe_rows_fill": 2,
                 "moe_rows_sum": 2})])
def test_smallthinker_programs_carry_their_scopes_and_kernels(topo, prog,
                                                              kernels):
    """The SmallThinker cell's programs compiled for the described v5e at
    its widths and engine (32 slots of 240 table columns in two page groups,
    chunks of the first bucket), one layer of each kind (G W), a program a
    case: the scopes contract, and the kernels by name and count: a decode
    step walks the global layer's pages as `paged_decode` and the window
    layer's as `window_walk` (ONE kernel body, two names), a chunk runs
    `window_prefill` in both (one kernel under a run-time window), and the
    ReGLU experts are `moe_gmm_glu` between the two row kernels."""
    cell = "smallthinker_serve_shortlong"
    with _no_frames_in_locations():
        text = _cell_programs(topo, cell)[prog]().compile().as_text()
    assert _holds_the_contract(
        cell, prog, text, sample=prog == "decode_burst") >= 1
    assert prog != "prefill" or "/sample/dynamic_update_slice" in text
    assert _kernel_counts(text) == kernels


@pytest.mark.parametrize("prog, kernels", [
    ("decode_burst", {"kda_step": 1, "paged_decode_mla": 1,
                      **_expert_layers(1)}),
    ("prefill", {"kda_terms": 1, "kda_scan": 1, **_expert_layers(1)})])
def test_ling3_programs_carry_their_scopes_and_kernels(topo, prog, kernels):
    """The Ling-3.0-flash cell's programs compiled for the described v5e at
    its widths and engine (128 slots of 176 table columns, a per-slot state
    pool AND a latent page pool in one donated cache, chunks of the first
    bucket), one layer of each kind (K D, T X), a program a case: the
    scopes contract, and the kernels by name and count: a decode step holds
    1 `kda_step`, 1 `paged_decode_mla` and the expert layer's three; a chunk
    1 `kda_terms`, 1 `kda_scan` and the expert layer's three (its latent
    attention is un-absorbed XLA)."""
    cell = "ling3_serve_reason"
    with _no_frames_in_locations():
        text = _cell_programs(topo, cell)[prog]().compile().as_text()
    assert _holds_the_contract(
        cell, prog, text, sample=prog == "decode_burst") >= 1
    assert prog != "prefill" or "/sample/dynamic_update_slice" in text
    assert _kernel_counts(text) == kernels


def test_an_eight_layer_prefill_lowers_window_prefill_once(topo):
    """The cell's chunk program at its published depth (G W W W twice: two
    global and six window layers), traced and lowered for the described
    v5e, not compiled: the module holds ONE `window_prefill` kernel in a
    function of its own called eight times, because `window` is a run-time
    scalar, the pages a step folds follow from shapes the layers share
    (`ops/window_attention.py pages_per_step`) and the `pallas_call` is
    jitted on its own (`_prefill_call`). Its three bodies are lowered once
    a program, not once a layer (ROADMAP S5)."""
    CELL_DEPTH["smallthinker"], was = (
        lambda cfg: dict(cfg, layers_run=8)), CELL_DEPTH["smallthinker"]
    try:
        text = _cell_programs(
            topo, "smallthinker_serve_shortlong")["prefill"]().as_text()
    finally:
        CELL_DEPTH["smallthinker"] = was
    assert text.count('kernel_name = "window_prefill"') == 1
    assert len(re.findall(r"call @_prefill_call\b", text)) == 8


def _abstract_trainer(topo, *, model, mesh_cfg, model_kwargs, sample,
                      fsdp=False):
    """What Trainer.__init__ builds, from shapes alone on described
    devices: (mesh, model, tx, abstract state, state shardings)."""
    from ddp_practice_tpu.parallel.fsdp import fsdp_rules
    from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
    from ddp_practice_tpu.train.state import create_state, make_optimizer

    mesh = build_mesh(mesh_cfg, devices=topo.devices)
    set_current_mesh(mesh)
    net = create_model(model, policy=PrecisionPolicy.bf16(), **model_kwargs)
    tx = make_optimizer(
        TrainConfig(model=model, optimizer="adamw", learning_rate=3e-4), 14
    )
    abstract = jax.eval_shape(
        lambda r: create_state(net, tx, rng=r, sample_input=sample),
        jax.random.PRNGKey(0),
    )
    rules = param_sharding_rules(model)
    if fsdp:
        rules = fsdp_rules(mesh.shape[MeshConfig.AXIS_DATA], rules)
    return mesh, net, tx, abstract, shard_state(abstract, mesh, rules)


def _collectives(text):
    return {
        k: len(re.findall(rf"\b{k}(?:-start)?\(", text))
        for k in ("all-reduce", "all-gather", "reduce-scatter")
    }


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["data4", "data2_tensor2", "fsdp4"])
@pytest.mark.parametrize("pos_emb", ["rope", "learned"])
def test_lm_base_flash_step_compiles_on_the_mesh(topo, layout, pos_emb):
    """chip_smoke.py --chips 4's program: lm_base (depth cut to 2 for
    compile time), flash, global batch 8 at s 2048 through the resident
    train step Trainer uses, on the described 2x2. With the heads whole
    on a device (data4, fsdp4) the attention block stays flat, rotary
    included; data2_tensor2 keeps the 4-D code: rope the sliced flash
    path, learned positions the packed-QKV one."""
    from ddp_practice_tpu.train.steps import make_resident_lm_train_step

    mesh_cfg = (MeshConfig(data=2, tensor=2) if layout == "data2_tensor2"
                else MeshConfig())
    mesh, net, tx, abstract, shardings = _abstract_trainer(
        topo, model="lm_base", mesh_cfg=mesh_cfg, fsdp=layout == "fsdp4",
        model_kwargs=dict(vocab_size=64, max_len=S, attn_impl="flash",
                          pos_emb=pos_emb, depth=2),
        sample=jnp.zeros((B, S), jnp.int32),
    )
    step = make_resident_lm_train_step(
        net, tx, window=S + 1, seed=0, mesh=mesh, state_shardings=shardings
    )
    text = step.lower(
        abstract, {"tokens": _sds((262272,), jnp.int32)},
        _sds((1, B), jnp.int32),
    ).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    names = _kernel_calls(text)
    # (fwd, ONE backward) x 2 layers
    assert sum(n.startswith("flash_") for n in names) == 4, names
    # the heads whole on every device: the block stays flat, and under
    # rope the two rotary kernels run beside the flash kernels; split
    # over tensor=2, rope rotates in 4-D as before
    rotary = sum(n.startswith("rope_flat_") for n in names)
    flat_rope = pos_emb == "rope" and layout != "data2_tensor2"
    assert rotary == (4 if flat_rope else 0), names
    assert len(names) == 4 + rotary, names
    dp, tp = mesh.shape["data"], mesh.shape["tensor"]
    width = (3 * HD if pos_emb == "learned" else HD) // tp
    per_device = f"bf16[{B // dp},{S},{width}]"
    assert all(per_device in ln for ln in calls), calls[0][:300]
    colls = _collectives(text)
    assert colls["all-reduce"] >= 1, colls  # the gradient all-reduce
    if layout == "data4":
        assert colls["all-gather"] == 0, colls  # nothing is gathered


@pytest.mark.slow
@pytest.mark.parametrize("name", ["vit_tiny", "lm_tiny_fused"])
def test_fused_train_step_compiles_on_one_chip(topo, name):
    """The WHOLE jitted step (optimizer, steps_per_call scan, donation)
    around the fused encoder kernels, at the models' own shapes: the 17 MB
    scoped-VMEM window of ops/fused_encoder.py was found on an older
    compiler inside a real step, where the lone kernel fit and the step
    did not. vit_tiny takes the kernels by default (fused="auto" on a
    one-device TPU mesh)."""
    from ddp_practice_tpu.train import steps

    one = MeshConfig(data=1)
    if name == "vit_tiny":
        b, k = 1024, 32
        mesh, net, tx, abstract, shardings = _abstract_trainer(
            topo, model="vit_tiny", mesh_cfg=one,
            model_kwargs=dict(num_classes=10, axis_name=None),
            sample=jnp.zeros((b, 32, 32, 3), jnp.float32),
        )
        factory, batch = steps.make_chunked_train_step, {
            "image": _sds((k, b, 32, 32, 3), jnp.float32),
            "label": _sds((k, b), jnp.int32),
            "weight": _sds((k, b), jnp.float32),
        }
        kernels = 2 * 12  # fwd + bwd per layer
    else:
        b, k, s = 256, 16, 256
        mesh, net, tx, abstract, shardings = _abstract_trainer(
            topo, model="lm_tiny", mesh_cfg=one,
            model_kwargs=dict(vocab_size=64, max_len=s, num_heads=4,
                              fused=True),
            sample=jnp.zeros((b, s), jnp.int32),
        )
        factory, batch = steps.make_chunked_lm_train_step, {
            "tokens": _sds((k, b, s + 1), jnp.int32),
        }
        kernels = 2 * net.depth
    from ddp_practice_tpu.parallel.mesh import batch_sharding

    step = factory(
        net, tx, num_steps=k, mesh=mesh, state_shardings=shardings,
        batch_shardings=batch_sharding(mesh),
    )
    text = step.lower(abstract, batch).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


@pytest.mark.slow
def test_paged_engine_programs_compile_with_donation(topo):
    """PagedEngine's prefill, decode-burst and copy-on-write programs at
    lm_base widths, as chip_smoke.py builds them. Donation is on only on
    TPU (serve/engine.py _decode_donate), so no CPU test ever lowered
    it; the decode burst must hold the compiled paged kernel, once per
    layer."""
    from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine

    model = create_model(
        "lm_base", policy=PrecisionPolicy.bf16(), vocab_size=64,
        max_len=S, pos_emb="rope",
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    )["params"]
    # the engine allocates its pool where jax.devices() says (the CPU);
    # only its traced programs are lowered for the described chip
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=8, max_len=512, prompt_buckets=(32, 128), block_size=16,
        decode_burst=8,
    ))
    slots, mb = 8, engine.max_blocks_per_slot
    i32 = jnp.int32
    logits = _sds((slots, model.vocab_size), model.dtype)
    on_chip = functools.partial(_on, topo.devices[0])
    for w in engine.buckets:
        engine._prefill_jit.lower(*on_chip((
            params, engine._cache, logits, _sds((1, w), i32), _sds((), i32),
            _sds((-(-w // 16),), i32), _sds((), i32),
        ))).compile()
    # the engine's own jits of the two pool-rewriting programs: built
    # with donate_argnums because on_tpu() said so
    decode = engine._decode_jit.lower(*on_chip((
        params, engine._cache, logits, _sds((slots,), i32),
        _sds((slots,), jnp.bool_), _sds((slots, 2), jnp.uint32),
        _sds((slots, mb), i32), _sds((slots,), i32))), None,
    ).compile()
    assert decode.as_text().count(
        'custom_call_target="tpu_custom_call"') == model.depth
    cow = engine._cow_jit.lower(*on_chip((
        engine._cache, _sds((), i32), _sds((), i32)))).compile()
    for compiled in (decode, cow):
        # donation took: the pool's bytes are aliased, not copied
        assert compiled.memory_analysis().alias_size_in_bytes > 0


@pytest.mark.slow
def test_paged_prefill_compiles_at_s2048(topo):
    """models/vit.py's paged PREFILL (s > 1: scatter through the page
    table, gather_pages, dense masked attention) at a 2048-token chunk —
    the span a 16k-token prompt's last chunk attends is what bounds it,
    here 4096 positions per slot."""
    from ddp_practice_tpu.inference import decode_apply
    from ddp_practice_tpu.serve.kv_pages import make_paged_cache

    model = create_model(
        "lm_base", policy=PrecisionPolicy.bf16(), vocab_size=64,
        max_len=S, pos_emb="rope", depth=2,
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    )["params"]
    pool = jax.eval_shape(lambda: make_paged_cache(model, 1 + 256, 16))

    def prefill(params, pool, tokens, table, pos0):
        return decode_apply(model, params, pool, tokens,
                            page_table=table, kv_lengths=pos0)

    _compile(
        prefill, params, pool, _sds((1, S), jnp.int32),
        _sds((1, 256), jnp.int32), _sds((1,), jnp.int32),
        device=topo.devices[0], min_kernels=0,
    )
