"""Pipeline-parallelism tests: GPipe schedule over the 'pipe' mesh axis.

Contract: the pipelined forward equals the depth-sequential application of
the SAME stacked block parameters (GPipe reorders compute, not math), its
gradients match, and a full sharded train step runs with stage-sharded
parameters composed with data parallelism.
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
from ddp_practice_tpu.train import create_state, make_optimizer, make_train_step


MODEL_KW = dict(depth=4, hidden_dim=32, num_heads=4, mlp_dim=64, patch_size=4)


@pytest.fixture()
def pipe_mesh(devices):
    mesh = build_mesh(MeshConfig(data=2, pipe=4))
    set_current_mesh(mesh)
    yield mesh
    set_current_mesh(None)


def _images(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(size=(n, 16, 16, 3)), jnp.float32)


def _models():
    piped = create_model(
        "vit_tiny_pipe", num_stages=4, num_microbatches=2, **MODEL_KW
    )
    seq = create_model("vit_tiny_pipe", num_stages=1, **MODEL_KW)
    return piped, seq


@pytest.mark.fast
def test_pipeline_forward_matches_sequential(pipe_mesh):
    piped, seq = _models()
    x = _images()
    variables = seq.init(jax.random.PRNGKey(0), x)
    want = seq.apply(variables, x)
    got = piped.apply(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_pipeline_grads_match_sequential(pipe_mesh):
    piped, seq = _models()
    x = _images(seed=1)
    variables = seq.init(jax.random.PRNGKey(1), x)

    def loss(model, params):
        return jnp.sum(model.apply({"params": params}, x) ** 2)

    g_seq = jax.grad(lambda p: loss(seq, p))(variables["params"])
    g_pipe = jax.grad(lambda p: loss(piped, p))(variables["params"])
    for a, b in zip(jax.tree.leaves(g_seq), jax.tree.leaves(g_pipe)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_pipeline_sharded_train_step(pipe_mesh):
    """Stage-sharded params + data-sharded batch through make_train_step."""
    model = create_model(
        "vit_tiny_pipe", num_stages=4, num_microbatches=2, **MODEL_KW
    )
    cfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    tx = make_optimizer(cfg)
    sample = jnp.zeros((8, 16, 16, 3))

    def init_fn(r):
        return create_state(model, tx, rng=r, sample_input=sample)

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    rules = param_sharding_rules("vit_tiny_pipe")
    shardings = shard_state(abstract, pipe_mesh, rules)
    state = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(0))

    # block params are really split across the pipe axis
    qkv = state.params["blocks"]["attn"]["qkv"]["kernel"]
    assert qkv.addressable_shards[0].data.shape[0] == qkv.shape[0] // 4

    bsh = batch_sharding(pipe_mesh)
    step = make_train_step(
        model, tx, mesh=pipe_mesh, state_shardings=shardings, batch_shardings=bsh
    )
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.uniform(size=(8, 16, 16, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, 8), jnp.int32),
        "weight": jnp.ones((8,), jnp.float32),
    }
    before = np.asarray(jax.tree.leaves(state.params)[0])
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    after = np.asarray(jax.tree.leaves(state.params)[0])
    assert not np.allclose(before, after)  # params actually updated


@pytest.mark.parametrize("sp_impl", ["ring", "ulysses"])
def test_pipeline_composes_sequence_parallelism(devices, sp_impl):
    """SP x PP: ring/Ulysses attention opens a nested shard_map island
    over the still-automatic 'seq' axis inside each pipeline stage; the
    sharded pipelined forward must match the sequential dense apply."""
    mesh = build_mesh(MeshConfig(data=2, seq=2, pipe=2))
    set_current_mesh(mesh)
    try:
        piped = create_model(
            "vit_tiny_pipe", num_stages=2, num_microbatches=2,
            seq_axis=MeshConfig.AXIS_SEQ, sp_impl=sp_impl, **MODEL_KW
        )
        seq = create_model("vit_tiny_pipe", num_stages=1, **MODEL_KW)
        x = _images()
        variables = seq.init(jax.random.PRNGKey(0), x)
        want = seq.apply(variables, x)
        got = piped.apply(variables, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
    finally:
        set_current_mesh(None)


@pytest.fixture()
def tp_pipe_mesh(devices):
    mesh = build_mesh(MeshConfig(data=2, pipe=2, tensor=2))
    set_current_mesh(mesh)
    yield mesh
    set_current_mesh(None)


def test_pipeline_composes_tensor_parallelism_forward(tp_pipe_mesh):
    """TP x PP: the pipelined forward on params sharded over BOTH 'pipe'
    (stage dim) and 'tensor' (Megatron inner dims) matches the sequential
    unsharded apply — the pipeline shard_map is manual over 'pipe'/'data'
    only, so GSPMD partitions the stage body over 'tensor'."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.tree_util import tree_map_with_path

    piped = create_model(
        "vit_tiny_pipe", num_stages=2, num_microbatches=2, **MODEL_KW
    )
    seq = create_model("vit_tiny_pipe", num_stages=1, **MODEL_KW)
    x = _images()
    variables = seq.init(jax.random.PRNGKey(0), x)
    want = seq.apply(variables, x)

    rules = param_sharding_rules("vit_tiny_pipe")
    sharded_params = tree_map_with_path(
        lambda p, leaf: jax.device_put(
            leaf, NamedSharding(tp_pipe_mesh, rules(p, leaf) or P())
        ),
        variables["params"],
    )
    # the TP spec really splits the stacked qkv kernel over 'tensor' too
    qkv = sharded_params["blocks"]["attn"]["qkv"]["kernel"]
    shard_shape = qkv.addressable_shards[0].data.shape
    assert shard_shape[0] == qkv.shape[0] // 2  # pipe (stage dim)
    assert shard_shape[3] == qkv.shape[3] // 2  # tensor (heads dim)

    got = piped.apply({"params": sharded_params}, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_pipeline_tensor_parallel_train_step(tp_pipe_mesh):
    """A full dp x pp x tp train step: state sharded by the composed rules,
    loss finite, params update."""
    model = create_model(
        "vit_tiny_pipe", num_stages=2, num_microbatches=2, **MODEL_KW
    )
    cfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    tx = make_optimizer(cfg)
    sample = jnp.zeros((8, 16, 16, 3))

    def init_fn(r):
        return create_state(model, tx, rng=r, sample_input=sample)

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    rules = param_sharding_rules("vit_tiny_pipe")
    shardings = shard_state(abstract, tp_pipe_mesh, rules)
    state = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(0))
    qkv = state.params["blocks"]["attn"]["qkv"]["kernel"]
    shard_shape = qkv.addressable_shards[0].data.shape
    assert shard_shape[0] == qkv.shape[0] // 2
    assert shard_shape[3] == qkv.shape[3] // 2

    bsh = batch_sharding(tp_pipe_mesh)
    step = make_train_step(
        model, tx, mesh=tp_pipe_mesh, state_shardings=shardings,
        batch_shardings=bsh,
    )
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.uniform(size=(8, 16, 16, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, 8), jnp.int32),
        "weight": jnp.ones((8,), jnp.float32),
    }
    before = np.asarray(jax.tree.leaves(state.params)[0])
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    after = np.asarray(jax.tree.leaves(state.params)[0])
    assert not np.allclose(before, after)
