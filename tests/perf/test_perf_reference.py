"""Each plain reference against the system at a toy size on the CPU, and the
same comparison failing in the next precision down."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perf_toy
from perf.lib import compare, weights
from perf.reference import blocks, follow, gpt2_small, vit_b16


def program_model(name, monkeypatch, **kw):
    from ddp_practice_tpu.config import PrecisionPolicy
    from ddp_practice_tpu.models import create_model

    perf_toy.shrink_registry(monkeypatch)
    return create_model(name, policy=PrecisionPolicy.fp32(), **kw)


def made_params(model, sample, seed=7):
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample)["params"])
    return weights.make_params(abstract, seed)


def test_gpt2_reference_agrees_with_the_program_and_fp8_does_not(monkeypatch):
    cfg = perf_toy.lm_config()
    model = program_model("lm_tiny", monkeypatch, vocab_size=256, max_len=64,
                          pos_emb="rope", tied_embeddings=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    params = made_params(model, tokens)
    got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(jax.jit(
        lambda p, t: gpt2_small.forward(p, t, cfg))(params, tokens))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-5 * scale
    low = np.asarray(jax.jit(
        lambda p, t: gpt2_small.forward(p, t, cfg, "fp8"))(params, tokens))
    assert np.abs(low - want).max() > 100 * 2e-5 * scale


def test_vit_reference_agrees_with_the_program_and_fp8_does_not(monkeypatch):
    cfg = perf_toy.vit_config()
    model = program_model("vit_tiny", monkeypatch, num_classes=10,
                          patch_size=2)
    images = jax.random.randint(jax.random.PRNGKey(2), (3, 8, 8, 3), 0, 256
                                ).astype(jnp.uint8)
    params = made_params(model, jnp.zeros((1, 8, 8, 3), jnp.float32))
    got = np.asarray(jax.jit(model.apply)(
        {"params": params}, images.astype(jnp.float32) / 255.0))
    want = np.asarray(jax.jit(
        lambda p, x: vit_b16.forward(p, x, cfg))(params, images))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-5 * scale
    low = np.asarray(jax.jit(
        lambda p, x: vit_b16.forward(p, x, cfg, "fp8"))(params, images))
    assert np.abs(low - want).max() > 100 * 2e-5 * scale


def test_following_three_steps_matches_optax_adamw():
    import optax

    def loss(params, batch, cfg, quant=None):  # softmax regression
        logits = blocks.mm("bi,io->bo", batch["x"], params["w"], quant) \
            + params["b"]
        return blocks.softmax_xent(logits, batch["y"])

    rng = np.random.default_rng(0)
    batches = [{"x": rng.normal(size=(8, 5)).astype(np.float32),
                "y": rng.integers(0, 3, 8).astype(np.int32)}
               for _ in range(3)]
    params = {"w": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
              "b": jnp.zeros((3,), jnp.float32)}
    mine = follow.follow(loss, params, batches, {}, lr=3e-4, wd=0.01,
                         block_rows=2)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    p, state, first, losses = params, tx.init(params), None, []
    for b in batches:
        b = jax.tree.map(jnp.asarray, b)
        l, g = jax.value_and_grad(lambda q: loss(q, b, {}))(p)
        losses.append(float(l))
        first = g if first is None else first
        upd, state = tx.update(g, state, p)
        p = optax.apply_updates(p, upd)
    delta = jax.tree.map(lambda a, b: a - b, p, params)
    assert np.allclose(mine["loss"], losses, rtol=1e-5)
    assert np.allclose(mine["delta_norms"], follow.leaf_norms(delta),
                       rtol=1e-3)
    assert np.allclose(mine["grad_norms"], follow.leaf_norms(first),
                       rtol=1e-4)
    assert mine["names"] == ["['b']", "['w']"]


def toy_lm(monkeypatch):
    cfg = perf_toy.lm_config()
    model = program_model("lm_tiny", monkeypatch, vocab_size=256, max_len=64,
                          pos_emb="rope", tied_embeddings=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 48), 0, 256)
    return cfg, tokens, made_params(model, tokens, 1)


def test_the_serving_control_is_not_correct_and_bf16_is(monkeypatch):
    """The control at a size a test can hold: the reference put in the
    program's place in the next precision down (e4m3 for a configuration
    that states bfloat16) serves tokens that fail; in bfloat16, the
    precision the program states, they pass. A limit of the toy's own,
    between the two readings (3 seeds, PR 23: e4m3 reads 0.0086-0.0135,
    bfloat16 0)."""
    cfg, tokens, params = toy_lm(monkeypatch)

    def served(quant):  # the tokens the lower precision would serve
        fwd = jax.jit(lambda p, t, q=quant: gpt2_small.forward(p, t, cfg, q))
        low = np.asarray(fwd(params, tokens))
        checks = compare.Checks()
        checks.add("served_token_gap", max(
            compare.served_token_gaps(lg, 1, lo.argmax(-1)[:-1].tolist())
            .max() for lg, lo in zip(logits, low)), 0.004)
        return checks.correct

    logits = np.asarray(jax.jit(
        lambda p, t: gpt2_small.forward(p, t, cfg))(params, tokens))
    assert served("bf16") is True and served("fp8") is False


def test_the_training_control_is_not_correct(monkeypatch):
    """The same control for a training cell: the first gradient's norm of
    the reference in e4m3 fails (3 seeds, PR 23: it reads 0.008-0.021 where
    bfloat16 reads 0.0004-0.0019)."""
    from perf.drivers import train

    cfg, tokens, params = toy_lm(monkeypatch)
    batch = [{"tokens": np.asarray(tokens[:, :17], np.int32)}]
    kw = dict(lr=3e-4, wd=0.01, block_rows=2)
    want = follow.follow(gpt2_small.loss, params, batch, cfg, **kw)
    got = follow.follow(gpt2_small.loss, params, batch, cfg, quant="fp8",
                        **kw)
    trained = compare.Checks()
    trained.add("grad_norm_gap", train.gaps(got, want)["grad_norm_gap"],
                0.005)
    assert trained.correct is False


def test_worst_leaf_gap_and_served_token_gaps():
    ref = np.array([1.0, 1e-9, 2.0, 4.0])
    gap, i = compare.worst_leaf_gap(ref * [1.0, 50.0, 1.01, 1.0], ref)
    assert i == 2 and gap == pytest.approx(0.01)   # the tiny leaf is held
    gap, i = compare.worst_leaf_gap(np.zeros(4), ref)  # to the median's
    assert gap == pytest.approx(1.0)      # a state that never moved
    logits = np.array([[0, 1, 5], [9, 2, 3], [1, 1.5, 1], [0, 0, 7.0]])
    gaps = compare.served_token_gaps(logits, 2, [0, 1, 1])
    assert gaps.tolist() == [0.0, 0.0, 7.0]
    checks = compare.Checks()
    checks.add("a", 0.1, 0.2)
    assert checks.correct
    checks.add("b", float("nan"), 0.2)
    assert not checks.correct and "FAILED" in checks.lines()[1]
    assert not compare.Checks().correct


def test_rounding_passes_gradients_through():
    x = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    g = jax.grad(lambda a: blocks.mm("ij,jk->ik", a, x, "fp8").sum())(x)
    exact = jax.grad(lambda a: blocks.mm("ij,jk->ik", a, x).sum())(x)
    err = float(jnp.abs(g - exact).max() / jnp.abs(exact).max())
    assert 1e-4 < err < 0.1   # rounded, and neither zero nor wild
