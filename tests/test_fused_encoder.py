"""Fused Pallas encoder layer (ops/fused_encoder.py).

Contract: the one-kernel layer computes the SAME function as the unfused
flax EncoderBlock — forward outputs match, and the hand-derived backward
kernel's gradients (params AND input) match autodiff of the unfused
block. Runs in interpret mode on the CPU backend, compiled on TPU
(BENCHMARKS.md records the hardware numbers under both of its
measurement conventions: 44% vs 18.7% MFU per-layer forward, 30.5% vs
17.0% train in the bench suite's convention, at d=192).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.models.vit import EncoderBlock

HEADS, MLP, D, S = 3, 768, 192, 64


def _block(**kw):
    return EncoderBlock(HEADS, MLP, **kw)


def _x(b=4, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((b, S, D)), jnp.float32
    )


@pytest.fixture(scope="module")
def variables():
    return _block().init(jax.random.PRNGKey(0), _x(1))


@pytest.mark.fast
def test_forward_matches_unfused(devices, variables):
    x = _x(b=6, seed=1)  # 6 also exercises _fit_tile on a non-pow2 batch
    want = _block().apply(variables, x)
    got = _block(fused=True).apply(variables, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("bwd_impl", ["kernel", "reference"])
def test_grads_match_unfused(devices, variables, bwd_impl):
    """Param AND input grads from the fused layer equal unfused autodiff
    — for the hand-derived Pallas backward and the recompute fallback."""
    from ddp_practice_tpu.ops.fused_encoder import fused_encoder_layer

    x = _x(b=4, seed=2)
    p = variables["params"]
    block = _block()

    def fused_loss(p, x):
        y = fused_encoder_layer(
            x, p, num_heads=HEADS, compute_dtype=jnp.float32,
            reference_apply=lambda pp, xx: block.apply({"params": pp}, xx),
            bwd_impl=bwd_impl,
        )
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def unfused_loss(p, x):
        return jnp.sum(block.apply({"params": p}, x).astype(jnp.float32) ** 2)

    gp_w, gx_w = jax.grad(unfused_loss, argnums=(0, 1))(p, x)
    gp_f, gx_f = jax.grad(fused_loss, argnums=(0, 1))(p, x)
    flat_w = jax.tree_util.tree_leaves_with_path(gp_w)
    flat_f = jax.tree.leaves(gp_f)
    for (path, w), f in zip(flat_w, flat_f):
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(w), rtol=2e-4, atol=2e-4,
            err_msg=jax.tree_util.keystr(path),
        )
    np.testing.assert_allclose(
        np.asarray(gx_f), np.asarray(gx_w), rtol=2e-4, atol=2e-4
    )


def test_vit_model_fused_matches_unfused(devices):
    """Model-level: vit_tiny(fused=True) logits == the per-op model."""
    kw = dict(depth=2, hidden_dim=D, num_heads=HEADS, mlp_dim=MLP)
    dense = create_model("vit_tiny", **kw)
    fused = create_model("vit_tiny", fused=True, **kw)
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((4, 32, 32, 3)), jnp.float32
    )
    v = dense.init(jax.random.PRNGKey(0), x, train=False)
    np.testing.assert_allclose(
        np.asarray(fused.apply(v, x)), np.asarray(dense.apply(v, x)),
        rtol=2e-5, atol=2e-5,
    )


def test_fused_train_step_moves_params(devices):
    from ddp_practice_tpu.config import TrainConfig
    from ddp_practice_tpu.train.state import create_state, make_optimizer
    from ddp_practice_tpu.train.steps import make_train_step

    model = create_model(
        "vit_tiny", fused=True, depth=2, hidden_dim=D, num_heads=HEADS,
        mlp_dim=MLP,
    )
    tx = make_optimizer(TrainConfig(optimizer="adamw", learning_rate=1e-3))
    state = create_state(
        model, tx, rng=jax.random.PRNGKey(0),
        sample_input=jnp.zeros((1, 32, 32, 3)),
    )
    rng = np.random.default_rng(4)
    batch = {
        "image": jnp.asarray(rng.uniform(size=(8, 32, 32, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, 8), jnp.int32),
    }
    before = np.asarray(jax.tree.leaves(state.params)[0])
    state, metrics = make_train_step(model, tx)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert not np.allclose(before, np.asarray(jax.tree.leaves(state.params)[0]))


def test_fused_gates_unsupported_configs(devices, variables):
    x = _x(b=2)
    # causal is SUPPORTED since round 4 (test_causal_fused_matches_unfused);
    # rope and dropout still keep the per-op path
    with pytest.raises(ValueError, match="fused"):
        EncoderBlock(HEADS, MLP, fused=True, rope=True).apply(variables, x)
    with pytest.raises(ValueError, match="fused"):
        EncoderBlock(HEADS, MLP, fused=True, dropout_rate=0.1).apply(
            variables, x, False, True
        )


def test_causal_fused_matches_unfused(devices):
    """Round 4: the fused kernel's causal path (decoder-LM blocks) —
    forward AND both grads against the unfused causal block."""
    from ddp_practice_tpu.ops.fused_encoder import fused_encoder_layer

    block = _block(causal=True)
    variables = block.init(jax.random.PRNGKey(3), _x(1))
    x = _x(b=4, seed=4)
    p = variables["params"]

    want = block.apply(variables, x)
    got = _block(causal=True, fused=True).apply(variables, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )

    def fused_loss(p, x):
        y = fused_encoder_layer(
            x, p, num_heads=HEADS, compute_dtype=jnp.float32, causal=True,
        )
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def unfused_loss(p, x):
        return jnp.sum(block.apply({"params": p}, x).astype(jnp.float32) ** 2)

    gp_w, gx_w = jax.grad(unfused_loss, argnums=(0, 1))(p, x)
    gp_f, gx_f = jax.grad(fused_loss, argnums=(0, 1))(p, x)
    flat_w = jax.tree_util.tree_leaves_with_path(gp_w)
    flat_f = jax.tree.leaves(gp_f)
    for (path, w), f in zip(flat_w, flat_f):
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(w), rtol=2e-4, atol=2e-4,
            err_msg=jax.tree_util.keystr(path),
        )
    np.testing.assert_allclose(
        np.asarray(gx_f), np.asarray(gx_w), rtol=2e-4, atol=2e-4
    )


def test_causality_of_fused_kernel(devices):
    """Perturbing a late token must not change earlier outputs."""
    block = _block(causal=True, fused=True)
    variables = block.init(jax.random.PRNGKey(5), _x(1))
    x = _x(b=2, seed=6)
    y1 = block.apply(variables, x)
    x2 = x.at[:, -1].add(3.0)
    y2 = block.apply(variables, x2)
    np.testing.assert_allclose(
        np.asarray(y1[:, :-1]), np.asarray(y2[:, :-1]), rtol=1e-5, atol=1e-5
    )
    assert float(jnp.max(jnp.abs(y1[:, -1] - y2[:, -1]))) > 1e-3


def test_fused_lm_matches_unfused(devices):
    """TransformerLM(fused=True): same logits and grads as the unfused
    model (params are identical — fused is an execution strategy)."""
    # depth 2 keeps the layer-chaining pin (residual handoff between
    # fused layers); mlp 128 halves the interpret-mode cost that made
    # this the suite's slowest test (18s at mlp 256)
    kw = dict(vocab_size=64, max_len=32, hidden_dim=128, depth=2,
              num_heads=2, mlp_dim=128)
    lm = create_model("lm_tiny", policy=None, **kw)
    lm_f = create_model("lm_tiny", policy=None, fused=True, **kw)
    toks = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (2, 32)), jnp.int32
    )
    variables = lm.init(jax.random.PRNGKey(8), toks)
    want = lm.apply(variables, toks)
    got = lm_f.apply(variables, toks)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )

    def loss(p, model):
        lg = model.apply({"params": p}, toks).astype(jnp.float32)
        return jnp.sum(lg ** 2) / lg.size

    gw = jax.grad(lambda p: loss(p, lm))(variables["params"])
    gf = jax.grad(lambda p: loss(p, lm_f))(variables["params"])
    for (path, w), f in zip(
        jax.tree_util.tree_leaves_with_path(gw), jax.tree.leaves(gf)
    ):
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(w), rtol=3e-4, atol=3e-4,
            err_msg=jax.tree_util.keystr(path),
        )


# ---------------------------------------------------------------------
# attn_impl="auto" (PR 29): SelfAttention picks its attention core from
# the shape at trace time, as `fused="auto"` picks the layer kernels.
# ---------------------------------------------------------------------

def _resolve(on_tpu=True, seq=196, heads=12, head_dim=64, decode=False,
             mesh=None, **attn_kw):
    from ddp_practice_tpu.models.vit import SelfAttention
    from ddp_practice_tpu.parallel.ring import set_current_mesh
    from ddp_practice_tpu.utils import backend

    was = backend.on_tpu
    backend.on_tpu = lambda: on_tpu
    set_current_mesh(mesh)
    try:
        return SelfAttention(num_heads=heads, **attn_kw).resolve_attn_impl(
            seq, head_dim, decode=decode)
    finally:
        backend.on_tpu = was
        set_current_mesh(None)


@pytest.mark.parametrize("kw,want", [
    # ViT-B/16's shape on the chip, nothing named: the short kernels
    (dict(), "flash_short"),
    (dict(causal=True), "flash_short"),
    (dict(seq=576), "flash_short"),
    (dict(heads=2, head_dim=128), "flash_short"),
    # off the TPU: ALWAYS _attention (a CPU test never interprets a
    # kernel it did not ask for)
    (dict(on_tpu=False), "xla"),
    # the range: from the shortest length the chip timed to the longest
    # a cell holds (lm_base's 2048 stays with whoever names "flash")
    (dict(seq=64), "flash_short"),
    (dict(seq=63), "xla"),
    (dict(seq=1024), "flash_short"),
    (dict(seq=1025), "xla"),
    (dict(seq=2048), "xla"),
    # what the kernels cannot express
    (dict(decode=True, causal=True), "xla"),
    (dict(rope=True), "xla"),
    (dict(seq_axis="seq"), "xla"),
    (dict(heads=3), "xla"),                  # vit_tiny: 3 heads, no pack
    (dict(heads=12, head_dim=48), "xla"),
    (dict(heads=12, kv_heads=2), "xla"),     # no fused qkv projection
    # a named kernel is taken at its word, wherever it runs
    (dict(attn_impl="xla"), "xla"),
    (dict(attn_impl="flash"), "flash"),
    (dict(attn_impl="flash", on_tpu=False, seq=64), "flash"),
    (dict(attn_impl="xla", on_tpu=False), "xla"),
])
def test_attn_impl_auto_resolves_from_the_shape(kw, want):
    assert _resolve(**kw) == want


def test_attn_impl_auto_counts_the_heads_a_device_holds(devices):
    """Under a mesh the kernels run in a shard_map island on each device's
    own heads: 12 heads over tensor=2 are 6 (three packs), 2 heads over
    tensor=2 are 1 (no pack of 64-wide heads)."""
    from ddp_practice_tpu.config import MeshConfig
    from ddp_practice_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(data=2, tensor=2), devices=devices[:4])
    assert _resolve(mesh=mesh) == "flash_short"
    assert _resolve(mesh=mesh, heads=2) == "xla"
    assert _resolve(mesh=mesh, heads=3) == "xla"


def test_auto_attention_keeps_the_fused_encoder_its_precedence():
    """`_plain_block` treats "auto" as plain: the small-d models keep the
    one-kernel layer under fused="auto"; a named kernel still opts out."""
    assert _block()._plain_block(decode=False)
    assert _block(attn_impl="xla")._plain_block(decode=False)
    assert not _block(attn_impl="flash")._plain_block(decode=False)
    assert EncoderBlock(HEADS, MLP).attn_impl == "auto"
    assert create_model("vit_tiny").attn_impl == "auto"
    assert create_model("lm_tiny", vocab_size=64).attn_impl == "auto"


def test_conv_model_takes_auto_and_refuses_a_named_kernel(devices):
    """train/loop.py forwards attn_impl only when one is named: "auto" on
    a conv model is not an error, "flash" on one is."""
    from ddp_practice_tpu.config import MeshConfig, TrainConfig
    from ddp_practice_tpu.train.loop import Trainer

    cfg = dict(dataset="synthetic", epochs=1, batch_size=4,
               mesh=MeshConfig(data=1))
    assert TrainConfig(**cfg).attn_impl == "auto"
    assert Trainer(TrainConfig(**cfg)).attn_impl is None
    with pytest.raises(TypeError, match="attn_impl"):
        Trainer(TrainConfig(attn_impl="flash", **cfg))


def test_auto_short_attention_matches_xla_through_the_model(monkeypatch):
    """SelfAttention under "auto" on the (pretended) TPU: the flat
    projection + short kernels give what the DenseGeneral projection +
    _attention give, outputs and parameter gradients."""
    import ddp_practice_tpu.ops.flash_attention as fa
    from ddp_practice_tpu.models.vit import (
        SelfAttention,
        resolved_attn_impls,
    )
    from ddp_practice_tpu.utils import backend

    x = jnp.asarray(np.random.default_rng(7).standard_normal((3, 36, 128)),
                    jnp.float32)
    ref = SelfAttention(num_heads=2, attn_impl="xla")
    variables = ref.init(jax.random.PRNGKey(0), x)

    def loss(mod):
        return lambda v, x: jnp.sum(jnp.square(mod.apply(v, x)))

    want = jax.value_and_grad(loss(ref), argnums=(0, 1))(variables, x)
    auto = SelfAttention(num_heads=2)
    monkeypatch.setattr(fa, "SHORT_SEQ_MIN", 16)
    with resolved_attn_impls() as seen:
        # on_tpu() also switches the kernels to compiled mode: pretend
        # only while the choice is made
        real = auto.resolve_attn_impl
        monkeypatch.setattr(backend, "on_tpu", lambda: True)
        picked = real(36, 64)
        monkeypatch.undo()
        monkeypatch.setattr(SelfAttention, "resolve_attn_impl",
                            lambda self, *a, **k: picked)
        got = jax.value_and_grad(loss(auto), argnums=(0, 1))(variables, x)
    assert picked == "flash_short" and seen == {"flash_short"}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
        got, want)
