"""Rotary position embeddings (ops/rope.py) and their composition with the
LM family, KV-cache decode, and sequence parallelism.

Nothing to cite in the reference (no sequence axis; SURVEY §5.7). Pinned:
the defining relative-position property (scores depend only on i - j),
causality of the rope LM, cached decode == full forward (the cursor offset
is the part a naive port gets wrong), and the seq-sharded rope decoder
matching the dense one (rotation happens before the SP island, so ring
K/V blocks travel pre-rotated).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.inference import make_cache, make_generate_fn
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.ops.rope import apply_rope
from ddp_practice_tpu.parallel.mesh import build_mesh
from ddp_practice_tpu.parallel.ring import set_current_mesh

VOCAB = 32


def _rope_lm(**kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("max_len", 64)
    kw.setdefault("hidden_dim", 64)
    kw.setdefault("depth", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("mlp_dim", 128)
    kw.setdefault("pos_emb", "rope")
    return create_model("lm_tiny", **kw)


@pytest.mark.fast
def test_rope_scores_are_relative(devices):
    """q_i · k_j after rotation depends only on i - j: shifting both
    positions by the same amount leaves the dot product unchanged."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)

    def score(i, j):
        qi = apply_rope(q, jnp.asarray([i]))
        kj = apply_rope(k, jnp.asarray([j]))
        return float(jnp.sum(qi * kj))

    np.testing.assert_allclose(score(3, 1), score(10, 8), rtol=1e-5)
    np.testing.assert_allclose(score(0, 0), score(7, 7), rtol=1e-5)
    # and it DOES vary with the offset (not a no-op)
    assert abs(score(3, 1) - score(3, 2)) > 1e-6


def test_rope_rejects_odd_head_dim(devices):
    with pytest.raises(ValueError, match="even"):
        apply_rope(jnp.zeros((1, 2, 1, 5)), jnp.arange(2))


def test_rope_lm_has_no_position_table_and_is_causal(devices):
    model = _rope_lm()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, VOCAB, (1, 16)), jnp.int32
    )
    variables = model.init(jax.random.PRNGKey(0), tokens)
    assert "pos_embed" not in variables["params"]
    base = model.apply(variables, tokens)
    t = 9
    perturbed = tokens.at[0, t].set((int(tokens[0, t]) + 5) % VOCAB)
    out = model.apply(variables, perturbed)
    np.testing.assert_array_equal(np.asarray(base[:, :t]), np.asarray(out[:, :t]))
    assert not np.allclose(np.asarray(base[:, t]), np.asarray(out[:, t]))
    # position is not ignored either: swapping two prompt tokens changes
    # downstream logits
    swapped = tokens.at[0, 2].set(int(tokens[0, 3])).at[0, 3].set(int(tokens[0, 2]))
    assert not np.allclose(np.asarray(base[:, -1]), np.asarray(model.apply(variables, swapped)[:, -1]))


def test_rope_cached_decode_matches_full_forward(devices):
    """The decode path rotates the incoming block at its ABSOLUTE positions
    (cursor offset) — prefill + steps must equal the full forward."""
    model = _rope_lm()
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    full = model.apply({"params": params}, tokens)

    prompt_len, total = 5, 12
    cache = make_cache(model, 2, total)
    logits, mut = model.apply(
        {"params": params, "cache": cache},
        tokens[:, :prompt_len], decode=True, mutable=["cache"],
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, :prompt_len]),
        rtol=2e-5, atol=2e-5,
    )
    cache = mut["cache"]
    for t in range(prompt_len, total):
        step_logits, mut = model.apply(
            {"params": params, "cache": cache},
            tokens[:, t:t + 1], decode=True, mutable=["cache"],
        )
        cache = mut["cache"]
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full[:, t]),
            rtol=2e-5, atol=2e-5,
        )


@pytest.mark.slow    # 10.9s measured — over the tier-1 10s line
def test_rope_greedy_generate_matches_naive(devices):
    model = _rope_lm()
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    prompt = jnp.asarray([[3, 1, 4]], jnp.int32)
    n_new = 8
    fast = np.asarray(
        jax.jit(make_generate_fn(model, max_new_tokens=n_new, temperature=0.0))(
            params, prompt
        )
    )
    seq = prompt
    for _ in range(n_new):
        logits = model.apply({"params": params}, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(fast, np.asarray(seq))


@pytest.mark.parametrize("sp_impl", ["ring", "ulysses"])
def test_rope_lm_sequence_parallel_matches_dense(devices, sp_impl):
    """Rotation is applied before the SP shard_map island, so the sharded
    rope decoder must reproduce the dense one bit-for-float."""
    mesh = build_mesh(MeshConfig(data=1, seq=8))
    set_current_mesh(mesh)
    try:
        dense = _rope_lm(num_heads=8)
        sharded = _rope_lm(
            num_heads=8, seq_axis=MeshConfig.AXIS_SEQ, sp_impl=sp_impl
        )
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, VOCAB, (2, 32)), jnp.int32
        )
        variables = dense.init(jax.random.PRNGKey(0), tokens)
        base = dense.apply(variables, tokens)
        sp = sharded.apply(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(sp), np.asarray(base), rtol=2e-4, atol=2e-4
        )
    finally:
        set_current_mesh(None)


# ------------------------------------------------------------------ #
# Rotary on the flat rows of the qkv projection (ops/rope.py
# rope_flat_qk / rope_flat_bwd) and the attention block that stays flat
# around it (models/vit.py SelfAttention, "flash_flat").
# ------------------------------------------------------------------ #


def _as_written(fn, *args):
    """`fn(*args)` compiled without XLA:CPU's optimisations: at its
    default level the backend contracts a * b + c * d into one fused
    multiply-add inside the interpreted kernel (a different last bit in
    a quarter of the float32 results), which no eager `apply_rope` and
    no TPU vector unit does. Level 0 keeps the arithmetic as written."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_flat_rotary_equals_apply_rope_to_the_bit(devices, dtype, head_dim,
                                                  direction):
    """The kernel over (rows, 128)-lane tiles of the flat projection is
    `apply_rope` over (b, s, h, d): the same float32 angles and
    multiply-adds, the same one cast. The backward is the transpose
    autodiff takes of `apply_rope`, beside dv as it came."""
    from ddp_practice_tpu.ops.rope import (
        flat_rope_tables,
        rope_flat_bwd,
        rope_flat_qk,
    )

    b, s, h = 2, 512, 256 // head_dim
    hd = h * head_dim
    positions = jnp.arange(s) + 7          # (s,), shared by the batch
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, 3 * hd)).astype(dtype)
    cos, sin = flat_rope_tables(positions, hd, h)
    four_d = lambda a: a.reshape(b, s, h, head_dim)
    if direction == "forward":
        got = _as_written(
            lambda *a: rope_flat_qk(*a, n_heads=h), x, cos, sin)
        want = [apply_rope(four_d(x[..., i * hd:(i + 1) * hd]), positions)
                for i in (0, 1)]
    else:
        got = _as_written(
            lambda x, cos, sin: rope_flat_bwd(
                x[..., :hd], x[..., hd:2 * hd], x[..., 2 * hd:], cos, sin,
                n_heads=h), x, cos, sin)
        assert got.shape == x.shape and got.dtype == x.dtype
        np.testing.assert_array_equal(          # dv passes through
            np.asarray(got[..., 2 * hd:], np.float32),
            np.asarray(x[..., 2 * hd:], np.float32))
        _, vjp = jax.vjp(lambda a: apply_rope(a, positions),
                         jnp.zeros((b, s, h, head_dim), dtype))
        want = [vjp(four_d(x[..., i * hd:(i + 1) * hd]))[0] for i in (0, 1)]
        got = [got[..., :hd], got[..., hd:2 * hd]]
    for g, w in zip(got, want):
        assert g.dtype == dtype and g.shape == (b, s, hd)
        np.testing.assert_array_equal(
            np.asarray(g, np.float32),
            np.asarray(w.reshape(b, s, hd), np.float32))


def test_flat_rotary_refuses_heads_that_do_not_pack(devices):
    from ddp_practice_tpu.ops.rope import flat_rope_tables

    with pytest.raises(ValueError, match="do not pack"):
        flat_rope_tables(jnp.arange(8), 5 * 48, 5)
    with pytest.raises(ValueError, match=r"\(s,\) positions"):
        flat_rope_tables(jnp.zeros((2, 8), jnp.int32), 256, 4)


def _attention_block(rope, attn_impl="flash", **kw):
    from ddp_practice_tpu.models.vit import SelfAttention

    attn = SelfAttention(num_heads=4, causal=True, rope=rope,
                         attn_impl=attn_impl, **kw)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 256), jnp.float32)
    return attn, attn.init(jax.random.PRNGKey(0), x), x


@pytest.mark.parametrize("against", ["flash_4d", "xla"])
@pytest.mark.parametrize("rope", [True, False], ids=["rope", "learned"])
def test_flat_block_matches_the_4d_block(devices, monkeypatch, rope, against):
    """One SelfAttention (4 heads of 64, interpret-mode kernels) on the
    flat path and on the 4-D code, over the same parameters: the output
    and the gradients with respect to x and every parameter agree within
    what the flash tests allow against XLA."""
    from ddp_practice_tpu.models.vit import SelfAttention, resolved_attn_impls

    attn, variables, x = _attention_block(rope)

    def run(module):
        def loss(variables, x):
            y = module.apply(variables, x)
            return jnp.sum(y * jnp.cos(y)), y

        with resolved_attn_impls() as seen:
            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(variables, x)
        return seen, y, grads

    seen, y, grads = run(attn)
    assert seen == {"flash_flat"}
    if against == "xla":
        seen_ref, y_ref, grads_ref = run(attn.clone(attn_impl="xla"))
        assert seen_ref == {"xla"}
    else:
        monkeypatch.setattr(SelfAttention, "_flat_block",
                            lambda self, head_dim, decode: False)
        seen_ref, y_ref, grads_ref = run(attn)
        assert seen_ref == {"flash"}
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
    flat, tree = jax.tree.flatten(grads)
    flat_ref, tree_ref = jax.tree.flatten(grads_ref)
    assert tree == tree_ref and len(flat) == 5  # qkv, out: kernel, bias; x
    for g, g_ref in zip(flat, flat_ref):
        assert g.shape == g_ref.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-4)


def test_lm_base_parameter_tree_is_unchanged(devices, monkeypatch):
    """The flat block reads the SAME `qkv` and `out` parameters
    DenseGeneral declares: names and shapes of lm_base's tree are what the
    4-D code makes (sharding rules, perf/lib/weights.py and checkpoints
    go by them)."""
    from ddp_practice_tpu.models.vit import SelfAttention

    model = create_model("lm_base", vocab_size=64, max_len=256,
                         attn_impl="flash", pos_emb="rope")

    def shapes():
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                              jnp.zeros((2, 256), jnp.int32))["params"]
        return {jax.tree_util.keystr(k): v.shape
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}

    flat = shapes()
    monkeypatch.setattr(SelfAttention, "_flat_block",
                        lambda self, head_dim, decode: False)
    assert flat == shapes()
    attn = {k.split("['attn']")[1]: v for k, v in flat.items()
            if k.startswith("['block0']['attn']")}
    assert attn == {
        "['qkv']['kernel']": (768, 3, 12, 64), "['qkv']['bias']": (3, 12, 64),
        "['out']['kernel']": (12, 64, 768), "['out']['bias']": (768,),
    }, attn


@pytest.mark.parametrize("caller", ["decode_prefill", "vit_block",
                                    "grouped_kv", "xla_named"])
def test_flat_rotary_is_for_the_training_block_alone(devices, monkeypatch,
                                                     caller):
    """A `decode=True` call (all of serving, prefill included), a ViT
    block, grouped K/V heads and `attn_impl="xla"` keep the 4-D code:
    none reaches the flat rotary helpers."""
    import ddp_practice_tpu.ops.flash_attention as fa
    from ddp_practice_tpu.models.vit import resolved_attn_impls

    def never(*a, **kw):
        raise AssertionError("the flat rotary path was entered")

    kw = {"decode_prefill": dict(rope=True),
          "vit_block": dict(rope=False),
          "grouped_kv": dict(rope=True, kv_heads=2),
          "xla_named": dict(rope=True, attn_impl="xla")}[caller]
    attn, variables, x = _attention_block(**kw)  # its init is no decode call
    for name in ("rope_flat_qk", "rope_flat_bwd", "flat_rope_tables"):
        monkeypatch.setattr(fa, name, never)
    if caller == "vit_block":
        attn = attn.clone(causal=False)
    with resolved_attn_impls() as seen:
        if caller == "decode_prefill":
            cache = attn.init(jax.random.PRNGKey(0), x, decode=True)["cache"]
            y, _ = attn.apply({**variables, "cache": cache}, x[:, :64],
                              decode=True, mutable=["cache"])
        else:
            y = jax.grad(lambda x: attn.apply(variables, x).sum())(x)
    assert bool(jnp.isfinite(y).all())
    # decode reports nothing; a flat block without rope is still flat
    assert seen == {"decode_prefill": set(), "vit_block": {"flash_flat"},
                    "grouped_kv": {"flash"}, "xla_named": {"xla"}}[caller]


@pytest.mark.parametrize("layout,want", [
    ("one_device", "flash_flat"),
    ("data4", "flash_flat"),
    ("data2_tensor2", "flash"),
])
def test_resolved_attn_impls_says_where_the_block_is_flat(devices, layout,
                                                          want):
    """What the Trainer's abstract init collects for lm_base with rope and
    "flash" named: the flat block where every device holds whole heads,
    the 4-D path where the registered mesh splits them."""
    from ddp_practice_tpu.models.vit import resolved_attn_impls

    mesh_cfg = {"one_device": MeshConfig(data=1), "data4": MeshConfig(data=4),
                "data2_tensor2": MeshConfig(data=2, tensor=2)}[layout]
    n = mesh_cfg.data * mesh_cfg.tensor
    set_current_mesh(build_mesh(mesh_cfg, devices=devices[:n]))
    model = create_model("lm_base", vocab_size=64, max_len=256, depth=1,
                         attn_impl="flash", pos_emb="rope")
    with resolved_attn_impls() as seen:
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((4, 256), jnp.int32))
    assert seen == {want}
