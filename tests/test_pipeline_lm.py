"""Pipelined decoder LM (models/pipeline_lm.py): GPipe over causal blocks.

Contract mirrors the ViT pipeline tests: the schedule reorders compute,
not math — pipelined forward/grads equal the depth-sequential apply of
the SAME stacked params; causality survives (microbatching splits the
batch, never the sequence); and the full LM train step runs with
stage+tensor-sharded params on a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.config import MeshConfig, TrainConfig
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.parallel.mesh import batch_sharding, build_mesh, shard_state
from ddp_practice_tpu.parallel.ring import set_current_mesh
from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
from ddp_practice_tpu.train import create_state, make_optimizer
from ddp_practice_tpu.train.steps import make_lm_train_step

VOCAB = 32
KW = dict(vocab_size=VOCAB, max_len=32, hidden_dim=32, depth=4,
          num_heads=4, mlp_dim=64)


def _tokens(b=8, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, VOCAB, (b, s)), jnp.int32)


@pytest.fixture()
def pipe_mesh(devices):
    mesh = build_mesh(MeshConfig(data=2, pipe=4))
    set_current_mesh(mesh)
    yield mesh
    set_current_mesh(None)


@pytest.mark.parametrize("pos_emb", ["learned", "rope"])
def test_pipelined_lm_forward_matches_sequential(pipe_mesh, pos_emb):
    piped = create_model("lm_pipe", num_stages=4, num_microbatches=2,
                         pos_emb=pos_emb, **KW)
    seq = create_model("lm_pipe", num_stages=1, pos_emb=pos_emb, **KW)
    tokens = _tokens()
    variables = seq.init(jax.random.PRNGKey(0), tokens)
    want = seq.apply(variables, tokens)
    got = piped.apply(variables, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_pipelined_lm_grads_match_sequential(pipe_mesh):
    piped = create_model("lm_pipe", num_stages=4, num_microbatches=2, **KW)
    seq = create_model("lm_pipe", num_stages=1, **KW)
    tokens = _tokens(seed=1)
    variables = seq.init(jax.random.PRNGKey(1), tokens)

    def loss(model, params):
        return jnp.sum(model.apply({"params": params}, tokens) ** 2)

    g_seq = jax.grad(lambda p: loss(seq, p))(variables["params"])
    g_pipe = jax.grad(lambda p: loss(piped, p))(variables["params"])
    for a, b in zip(jax.tree.leaves(g_seq), jax.tree.leaves(g_pipe)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


@pytest.mark.parametrize("microbatches", [2, 4])
def test_1f1b_loss_and_grads_match_sequential(pipe_mesh, microbatches):
    """The 1F1B schedule (parallel/pipeline_1f1b.py) computes the SAME
    mean loss, accuracy counts and grads as autodiff of the sequential
    model — interleaving reorders compute, not math. M=2 exercises a
    bubble-heavy schedule, M=4 the steady state."""
    piped = create_model("lm_pipe", num_stages=4, schedule="1f1b",
                         num_microbatches=microbatches, **KW)
    seq = create_model("lm_pipe", num_stages=1, **KW)
    tokens = _tokens(seed=5)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    variables = seq.init(jax.random.PRNGKey(2), tokens[:, :-1])

    from ddp_practice_tpu.ops.losses import accuracy_counts, cross_entropy

    def seq_loss(p):
        logits = seq.apply({"params": p}, inputs)
        return cross_entropy(logits, targets), logits

    (want_loss, want_logits), want_grads = jax.value_and_grad(
        seq_loss, has_aux=True
    )(variables["params"])
    want_correct, want_total = accuracy_counts(want_logits, targets)
    (loss, counts), grads = jax.jit(
        lambda p: piped.loss_and_grad(p, inputs, targets)
    )(variables["params"])

    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    assert float(counts["correct"]) == float(want_correct)
    assert float(counts["total"]) == float(want_total)
    flat_w, tdef = jax.tree_util.tree_flatten_with_path(want_grads)
    flat_g = jax.tree.leaves(grads)
    assert len(flat_w) == len(flat_g)
    for (path, w), g in zip(flat_w, flat_g):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=5e-4, atol=5e-4,
            err_msg=jax.tree_util.keystr(path),
        )


def test_1f1b_sharded_train_step(devices):
    """dp x pp x tp with the 1F1B schedule: the full train step (metrics,
    optimizer update) runs on sharded params and moves them."""
    mesh = build_mesh(MeshConfig(data=2, pipe=2, tensor=2))
    set_current_mesh(mesh)
    try:
        model = create_model("lm_pipe", num_stages=2, num_microbatches=2,
                             schedule="1f1b", **KW)
        cfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
        tx = make_optimizer(cfg)
        B, S = 8, 17

        def init_fn(r):
            return create_state(
                model, tx, rng=r, sample_input=jnp.zeros((B, S - 1), jnp.int32)
            )

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        rules = param_sharding_rules("lm_pipe")
        shardings = shard_state(abstract, mesh, rules)
        state = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(0))
        bsh = batch_sharding(mesh)
        step = make_lm_train_step(
            model, tx, mesh=mesh, state_shardings=shardings,
            batch_shardings=bsh,
        )
        batch = {"tokens": _tokens(B, S, seed=6)}
        before = np.asarray(jax.device_get(
            jax.tree.leaves(state.params)[0]))
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        after = np.asarray(jax.device_get(jax.tree.leaves(state.params)[0]))
        assert not np.allclose(before, after)
    finally:
        set_current_mesh(None)


def test_pipelined_lm_is_causal(pipe_mesh):
    """Perturbing token t must not change logits before t, THROUGH the
    pipeline schedule (microbatching splits batch, not sequence)."""
    model = create_model("lm_pipe", num_stages=4, num_microbatches=2, **KW)
    tokens = _tokens(b=4, seed=2)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    base = model.apply(variables, tokens)
    t = 9
    perturbed = tokens.at[0, t].set((int(tokens[0, t]) + 7) % VOCAB)
    out = model.apply(variables, perturbed)
    np.testing.assert_allclose(
        np.asarray(base[:, :t]), np.asarray(out[:, :t]), atol=1e-6
    )
    assert not np.allclose(np.asarray(base[0, t]), np.asarray(out[0, t]))


def test_pipelined_lm_numerically_equals_dense_lm(devices):
    """lm_pipe's embed/blocks/head are hand-synchronized copies of
    TransformerLM's inline logic (generate.py calls the families
    'equivalent') — pin that mechanically: map a dense lm_tiny param tree
    into the lm_pipe layout and require IDENTICAL logits."""
    dense = create_model("lm_tiny", **KW)
    piped = create_model("lm_pipe", num_stages=1, **KW)
    tokens = _tokens(b=2, s=12, seed=4)
    dp = dense.init(jax.random.PRNGKey(0), tokens)["params"]
    stacked_blocks = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[dp[f"block{i}"] for i in range(KW["depth"])],
    )
    pipe_params = {
        "embed": {"tok_embed": dp["tok_embed"], "pos_embed": dp["pos_embed"]},
        "blocks": stacked_blocks,
        "head": {"ln_f": dp["ln_f"], "lm_head": dp["lm_head"]},
    }
    want = dense.apply({"params": dp}, tokens)
    got = piped.apply({"params": pipe_params}, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-6, rtol=1e-6
    )


def test_pipelined_lm_sharded_train_step(devices):
    """dp x pp x tp LM train step: stacked blocks shard over pipe AND
    tensor, loss finite, params update."""
    mesh = build_mesh(MeshConfig(data=2, pipe=2, tensor=2))
    set_current_mesh(mesh)
    try:
        model = create_model("lm_pipe", num_stages=2, num_microbatches=2, **KW)
        cfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
        tx = make_optimizer(cfg)
        B, S = 8, 17

        def init_fn(r):
            return create_state(
                model, tx, rng=r, sample_input=jnp.zeros((B, S - 1), jnp.int32)
            )

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        rules = param_sharding_rules("lm_pipe")
        shardings = shard_state(abstract, mesh, rules)
        state = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(0))
        qkv = state.params["blocks"]["attn"]["qkv"]["kernel"]
        shard_shape = qkv.addressable_shards[0].data.shape
        assert shard_shape[0] == qkv.shape[0] // 2  # pipe (depth dim)
        assert shard_shape[3] == qkv.shape[3] // 2  # tensor (heads dim)
        emb = state.params["embed"]["tok_embed"]["embedding"]
        assert emb.addressable_shards[0].data.shape[0] == VOCAB // 2  # vocab/T

        bsh = batch_sharding(mesh)
        step = make_lm_train_step(
            model, tx, mesh=mesh, state_shardings=shardings,
            batch_shardings=bsh,
        )
        batch = {"tokens": _tokens(B, S, seed=3)}
        before = np.asarray(jax.device_get(
            jax.tree.leaves(state.params)[0]))
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        after = np.asarray(jax.device_get(jax.tree.leaves(state.params)[0]))
        assert not np.allclose(before, after)
    finally:
        set_current_mesh(None)


def test_interleave_tables_valid_and_smaller_bubble():
    """The generated interleaved schedules satisfy every data dependency
    (parallel/interleave.py simulate) and idle fewer device-ticks than
    plain 1F1B (V=1) at the same P and M."""
    from ddp_practice_tpu.parallel.interleave import build_tables, simulate

    for (P_, V, M) in [(2, 2, 4), (4, 2, 8), (2, 3, 4), (4, 3, 8)]:
        tb = build_tables(P_, V, M)
        simulate(tb, P_, V, M)
        flat = build_tables(P_, 1, M)
        simulate(flat, P_, 1, M)
        assert tb.bubble_fraction() < flat.bubble_fraction(), (
            P_, V, M, tb.bubble_fraction(), flat.bubble_fraction()
        )


@pytest.mark.parametrize("microbatches", [4])
def test_interleaved_loss_and_grads_match_sequential(devices, microbatches):
    """Interleaved 1F1B (virtual chunks, schedule tables from
    parallel/interleave.py) computes the SAME mean loss, counts, and
    grads as autodiff of the sequential model — P=2 devices x V=2
    chunks over the 4 blocks."""
    mesh = build_mesh(MeshConfig(data=2, pipe=2))
    set_current_mesh(mesh)
    try:
        piped = create_model("lm_pipe", num_stages=2, schedule="interleaved",
                             num_virtual=2, num_microbatches=microbatches,
                             **KW)
        seq = create_model("lm_pipe", num_stages=1, **KW)
        tokens = _tokens(seed=11)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        variables = seq.init(jax.random.PRNGKey(2), tokens[:, :-1])

        from ddp_practice_tpu.ops.losses import accuracy_counts, cross_entropy

        def seq_loss(p):
            logits = seq.apply({"params": p}, inputs)
            return cross_entropy(logits, targets), logits

        (want_loss, want_logits), want_grads = jax.value_and_grad(
            seq_loss, has_aux=True
        )(variables["params"])
        want_correct, want_total = accuracy_counts(want_logits, targets)
        (loss, counts), grads = jax.jit(
            lambda p: piped.loss_and_grad(p, inputs, targets)
        )(variables["params"])

        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
        assert float(counts["correct"]) == float(want_correct)
        assert float(counts["total"]) == float(want_total)
        flat_w, _ = jax.tree_util.tree_flatten_with_path(want_grads)
        flat_g = jax.tree.leaves(grads)
        assert len(flat_w) == len(flat_g)
        for (path, w), g in zip(flat_w, flat_g):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=5e-4, atol=5e-4,
                err_msg=jax.tree_util.keystr(path),
            )
    finally:
        set_current_mesh(None)
