"""MoE / expert-parallelism tests.

Contracts: the dense one-hot gating respects capacity and produces
normalized combine weights; a single-expert MoE reduces exactly to a dense
MLP; and ViT-MoE trains under an 'expert'-sharded mesh with the
load-balance aux loss flowing into the total loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.config import MeshConfig, TrainConfig
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.ops.moe import MoEMlp, top_k_gating
from ddp_practice_tpu.parallel.mesh import batch_sharding, build_mesh, shard_state
from ddp_practice_tpu.parallel.ring import set_current_mesh
from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
from ddp_practice_tpu.train import create_state, make_optimizer, make_train_step


@pytest.mark.fast
def test_gating_capacity_and_normalization():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 16, 4)), jnp.float32)
    dispatch, combine, aux, _ = top_k_gating(logits, k=2, capacity=3)
    d = np.asarray(dispatch)
    # every (expert, slot) receives at most one token per group
    assert d.sum(axis=1).max() <= 1.0 + 1e-6
    # capacity respected: at most C tokens per expert
    assert d.sum(axis=(1, 3)).max() <= 3 + 1e-6
    # each token dispatched at most k times
    assert d.sum(axis=(2, 3)).max() <= 2 + 1e-6
    # kept tokens have combine weights summing to 1
    c = np.asarray(combine).sum(axis=(2, 3))
    kept = d.sum(axis=(2, 3)) > 0
    np.testing.assert_allclose(c[kept], 1.0, rtol=1e-5)
    assert np.isfinite(float(aux))


def test_single_expert_equals_dense_mlp():
    """E=1, k=1, capacity >= T routes every token through the one expert:
    output must equal that expert's MLP applied densely."""
    layer = MoEMlp(num_experts=1, top_k=1, capacity_factor=1.0, mlp_dim=32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 16)), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    y = layer.apply(variables, x)
    p = variables["params"]
    w1, b1 = p["expert_w_in"][0], p["expert_b_in"][0]
    w2, b2 = p["expert_w_out"][0], p["expert_b_out"][0]
    import flax.linen as nn

    want = nn.gelu(x @ w1 + b1) @ w2 + b2
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_all_tokens_kept_with_ample_capacity():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(2, 16, 4)), jnp.float32)
    dispatch, _, _, _ = top_k_gating(logits, k=1, capacity=16)
    assert np.asarray(dispatch).sum() == 2 * 16  # every token kept once


@pytest.fixture()
def expert_mesh(devices):
    mesh = build_mesh(MeshConfig(data=2, expert=4))
    set_current_mesh(mesh)
    yield mesh
    set_current_mesh(None)


def test_vit_moe_sharded_train_step(expert_mesh):
    model = create_model(
        "vit_tiny_moe",
        depth=2,
        hidden_dim=32,
        num_heads=4,
        mlp_dim=64,
        num_experts=4,
        top_k=2,
        moe_every=2,
    )
    cfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    tx = make_optimizer(cfg)
    sample = jnp.zeros((8, 16, 16, 3))

    def init_fn(r):
        return create_state(model, tx, rng=r, sample_input=sample)

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    rules = param_sharding_rules("vit_tiny_moe")
    shardings = shard_state(abstract, expert_mesh, rules)
    state = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(0))

    w = state.params["block1"]["moe"]["expert_w_in"]
    assert w.addressable_shards[0].data.shape[0] == w.shape[0] // 4  # E-sharded

    bsh = batch_sharding(expert_mesh)
    step = make_train_step(
        model, tx, mesh=expert_mesh, state_shardings=shardings, batch_shardings=bsh
    )
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(rng.uniform(size=(8, 16, 16, 3)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, 8), jnp.int32),
        "weight": jnp.ones((8,), jnp.float32),
    }
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_lm_moe_every_zero_is_dense_lm(devices):
    """lm_moe with moe_every=0 IS the dense decoder: identical param tree
    and bit-identical logits to lm_tiny — the MoE composition is additive,
    not a fork of the family."""
    kw = dict(vocab_size=32, max_len=32, hidden_dim=32, depth=2,
              num_heads=4, mlp_dim=64)
    moe0 = create_model("lm_moe", moe_every=0, **kw)
    dense = create_model("lm_tiny", **kw)
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, 32, (2, 16)), jnp.int32
    )
    v = dense.init(jax.random.PRNGKey(0), tokens)
    assert (
        jax.tree.structure(moe0.init(jax.random.PRNGKey(0), tokens))
        == jax.tree.structure(v)
    )
    np.testing.assert_array_equal(
        np.asarray(moe0.apply(v, tokens)), np.asarray(dense.apply(v, tokens))
    )


def test_lm_moe_sharded_train_step_with_router_metrics(expert_mesh):
    """dp x ep MoE LM: expert-sharded params train; the step surfaces
    router health (load fractions bounded, drop rate in [0,1])."""
    from ddp_practice_tpu.train.steps import make_lm_train_step

    model = create_model(
        "lm_moe", vocab_size=32, max_len=32, hidden_dim=32, depth=2,
        num_heads=4, mlp_dim=64, num_experts=4, moe_every=2,
    )
    cfg = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    tx = make_optimizer(cfg)
    sample = jnp.zeros((8, 16), jnp.int32)

    def init_fn(r):
        return create_state(model, tx, rng=r, sample_input=sample)

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    shardings = shard_state(
        abstract, expert_mesh, param_sharding_rules("lm_moe")
    )
    state = jax.jit(init_fn, out_shardings=shardings)(jax.random.PRNGKey(0))
    w = state.params["block1"]["moe"]["expert_w_in"]
    assert w.addressable_shards[0].data.shape[0] == w.shape[0] // 4

    step = make_lm_train_step(
        model, tx, mesh=expert_mesh, state_shardings=shardings,
        batch_shardings=batch_sharding(expert_mesh),
    )
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 32, (8, 17)), jnp.int32
    )
    state, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["moe_drop_rate"]) <= 1.0
    assert 0.0 <= float(metrics["moe_load_min"]) <= float(
        metrics["moe_load_max"]
    ) <= 1.0


def test_aux_loss_increases_total_loss(expert_mesh):
    """The sown aux loss reaches the optimized objective: total loss with
    aux weight > 0 differs from the pure CE value."""
    from ddp_practice_tpu.ops.losses import cross_entropy

    model = create_model(
        "vit_tiny_moe", depth=2, hidden_dim=32, num_heads=4, mlp_dim=64,
        num_experts=4, top_k=1, moe_every=2,
    )
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.uniform(size=(8, 16, 16, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits, updated = model.apply(
        variables, x, train=True, mutable=["intermediates"]
    )
    aux = sum(
        float(jnp.sum(leaf))
        for leaf in jax.tree.leaves(updated["intermediates"])
    )
    assert aux > 0.0  # switch loss is >= 1 at uniform routing, scaled by 0.01
    ce = float(cross_entropy(logits, labels))
    assert np.isfinite(ce)


@pytest.mark.slow  # >10s on the tier-1 box (pytest.ini: excluded from the gate)
def test_router_balances_over_training(devices):
    """VERDICT round-3 item 3: the balancing machinery (fixed Switch aux
    + aux-free selection bias) must actually BALANCE load over training,
    not just add a loss term. Trains a small lm_moe on the synthetic
    Markov corpus and asserts the router health trajectory: drop rate
    falls well below its early value, and no expert is dead at the end.
    """
    from ddp_practice_tpu.data.lm_corpus import synthetic_token_corpus
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.train.state import create_state, make_optimizer
    from ddp_practice_tpu.train.steps import _lm_train_step_fn

    seq, bsz = 128, 8
    corpus = synthetic_token_corpus(n_tokens=1 << 16, seed=11)
    windows = jnp.asarray(corpus.windows(seq))
    n_win = windows.shape[0]
    model = create_model(
        "lm_moe",
        policy=None,
        vocab_size=corpus.vocab_size,
        max_len=seq,
        hidden_dim=128,
        depth=2,
        num_heads=4,
        mlp_dim=256,
        moe_every=1,
        num_experts=8,
        # zero-headroom capacity so the INITIAL router skew produces real
        # drops for the balancers to fix (the default cf=2.0 gives this
        # small config so much slack that drops are 0 from step one and
        # the trajectory would assert nothing); the absolute <5% warm
        # claim at cf=2.0 is a 2026-07 chip record (BENCHMARKS.md, MoE
        # router balance: drop under 1% after 40 warm steps)
        capacity_factor=1.0,
    )
    tx = make_optimizer(
        TrainConfig(model="lm_moe", optimizer="adamw", learning_rate=1e-3)
    )
    sample = jnp.zeros((bsz, seq), jnp.int32)
    state = create_state(
        model, tx, rng=jax.random.PRNGKey(0), sample_input=sample
    )
    assert state.batch_stats is not None  # the router bias lives here
    step = jax.jit(_lm_train_step_fn(model, tx))

    key = jax.random.PRNGKey(1)
    drops, load_mins = [], []
    for i in range(30):
        key, sub = jax.random.split(key)
        idx = jax.random.randint(sub, (bsz,), 0, n_win, jnp.int32)
        state, metrics = step(state, {"tokens": windows[idx]})
        drops.append(float(metrics["moe_drop_rate"]))
        load_mins.append(float(metrics["moe_load_min"]))

    early = float(np.mean(drops[:3]))
    late = float(np.mean(drops[-5:]))
    # the aux loss + selection bias must bite: late drops well under the
    # early rate (at capacity_factor 1.0 a per-group stochastic floor of
    # ~0.12 remains — headroom, not balancing, removes that part)
    assert late < early * 0.6, (early, late)
    assert late < 0.2, drops
    # no dead expert once warm
    assert float(np.mean(load_mins[-5:])) > 0.05, load_mins
    # the selection bias actually moved (the balancer ran)
    bias_leaves = jax.tree.leaves(state.batch_stats)
    assert any(float(jnp.max(jnp.abs(b))) > 0.0 for b in bias_leaves)


def test_group_size_permutation_exact():
    """group_size routing (round 4) must be a pure regrouping: with one
    expert and ample capacity nothing can drop, gates are 1, and the
    expert MLP is row-wise — so grouped (strided AND contiguous) outputs
    must match the ungrouped module EXACTLY. This pins the interleave
    permutation and its inverse."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_practice_tpu.ops.moe import MoEMlp

    x = jnp.asarray(
        np.random.default_rng(11).standard_normal((2, 64, 16)), jnp.float32
    )
    outs = {}
    for name, kw in [
        ("ungrouped", {}),
        ("strided", {"group_size": 16, "group_stride": True}),
        ("contig", {"group_size": 16, "group_stride": False}),
    ]:
        m = MoEMlp(num_experts=1, top_k=1, capacity_factor=4.0,
                   mlp_dim=32, expert_axis=None, **kw)
        params = m.init(jax.random.PRNGKey(0), x)
        outs[name] = m.apply(params, x)
    np.testing.assert_array_equal(
        np.asarray(outs["ungrouped"]), np.asarray(outs["strided"])
    )
    np.testing.assert_array_equal(
        np.asarray(outs["ungrouped"]), np.asarray(outs["contig"])
    )


def test_group_size_must_divide_seq():
    import jax
    import jax.numpy as jnp
    import pytest

    from ddp_practice_tpu.ops.moe import MoEMlp

    m = MoEMlp(num_experts=2, top_k=1, mlp_dim=32, group_size=48,
               expert_axis=None)
    x = jnp.zeros((1, 64, 16))
    with pytest.raises(ValueError, match="must divide"):
        m.init(jax.random.PRNGKey(0), x)


@pytest.mark.slow  # >10s on the tier-1 box (pytest.ini: excluded from the gate)
def test_sorted_impl_matches_dropless_einsum():
    """The sorted (counting-sort + grouped-matmul) expert path computes
    the SAME function as the einsum path when the latter has enough
    capacity to drop nothing — forward, parameter grads, and input
    grads (ops/moe.py MoEMlp impl)."""
    G, T, D, E, F, K = 2, 64, 32, 4, 64, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (G, T, D), jnp.float32)
    kw = dict(num_experts=E, top_k=K, mlp_dim=F, bias_update_rate=0.0,
              expert_axis=None)
    # capacity_factor E/K makes capacity == T: dropless by construction
    m_e = MoEMlp(impl="einsum", capacity_factor=float(E) / K, **kw)
    m_s = MoEMlp(impl="sorted", **kw)
    v = m_e.init(jax.random.PRNGKey(0), x)

    def loss(params, mod, xx):
        y, _ = mod.apply(
            {"params": params, "batch_stats": v["batch_stats"]}, xx,
            mutable=["intermediates", "batch_stats"],
        )
        return jnp.sum(y * y)

    ye, _ = m_e.apply(v, x, mutable=["intermediates", "batch_stats"])
    ys, _ = m_s.apply(v, x, mutable=["intermediates", "batch_stats"])
    np.testing.assert_allclose(np.asarray(ye), np.asarray(ys),
                               rtol=2e-5, atol=2e-5)
    ge, gxe = jax.grad(loss, argnums=(0, 2))(v["params"], m_e, x)
    gs, gxs = jax.grad(loss, argnums=(0, 2))(v["params"], m_s, x)
    np.testing.assert_allclose(np.asarray(gxe), np.asarray(gxs),
                               rtol=5e-4, atol=5e-4)
    import jax.tree_util as jtu

    for (pe, le), (_, ls) in zip(
        jtu.tree_leaves_with_path(ge), jtu.tree_leaves_with_path(gs)
    ):
        np.testing.assert_allclose(
            np.asarray(le), np.asarray(ls), rtol=5e-4, atol=5e-4,
            err_msg=jtu.keystr(pe),
        )


def test_sorted_impl_router_metrics_and_bias_update():
    """Sorted path keeps the router-health contract: drop rate exactly 0,
    load fractions sum to 1, and the aux-free bias moves against
    measured overload just like the einsum path."""
    G, T, D, E, F, K = 2, 32, 16, 4, 32, 2
    x = jax.random.normal(jax.random.PRNGKey(2), (G, T, D), jnp.float32)
    m = MoEMlp(impl="sorted", num_experts=E, top_k=K, mlp_dim=F,
               bias_update_rate=0.05, expert_axis=None)
    v = m.init(jax.random.PRNGKey(0), x)
    _, mut = m.apply(v, x, mutable=["intermediates", "batch_stats"])
    inter = mut["intermediates"]
    drop = float(inter["moe_drop_rate"][0])
    load = np.asarray(inter["moe_load_frac"][0])
    assert drop == 0.0
    np.testing.assert_allclose(load.sum(), 1.0, rtol=1e-5)
    bias = np.asarray(mut["batch_stats"]["router_bias"])
    assert np.any(bias != 0.0)  # the online balancer moved


def test_assignment_permutation_is_counting_sort():
    """dest/inv from _assignment_permutation are mutually inverse and
    order assignments by (expert, arrival)."""
    from ddp_practice_tpu.ops.moe import _assignment_permutation

    rng = np.random.RandomState(0)
    cf = jnp.asarray(rng.randint(0, 5, size=64), jnp.int32)
    counts, dest, inv = _assignment_permutation(cf, 5)
    dest_np, inv_np = np.asarray(dest), np.asarray(inv)
    assert sorted(dest_np.tolist()) == list(range(64))
    np.testing.assert_array_equal(dest_np[inv_np], np.arange(64))
    sorted_experts = np.asarray(cf)[inv_np]
    assert (np.diff(sorted_experts) >= 0).all()
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(cf), minlength=5)
    )


@pytest.mark.parametrize("cf,group_kw", [
    (1.0, {}),
    (1.25, {"group_size": 16}),
    (2.0, {"group_size": 16, "group_stride": False}),
])
def test_gather_impl_matches_einsum(cf, group_kw):
    """The gather path (per-slot lookup tables + custom gather-only
    VJPs, ops/moe.py _gather) computes the SAME function as the einsum
    path — same drops, same combine weights, same bias updates, same
    grads — across capacity regimes and routing groups. (The measured
    shootout left einsum the auto default — BENCHMARKS.md round-5 MoE
    section — so gather is opt-in; this equality keeps it honest.)"""
    import jax.tree_util as jtu

    G, T, D, E, F, K = 2, 64, 32, 4, 64, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (G, T, D), jnp.float32)
    kw = dict(num_experts=E, top_k=K, mlp_dim=F, bias_update_rate=0.05,
              expert_axis=None, capacity_factor=cf, **group_kw)
    m_e = MoEMlp(impl="einsum", **kw)
    m_g = MoEMlp(impl="gather", **kw)
    v = m_e.init(jax.random.PRNGKey(0), x)

    ye, me = m_e.apply(v, x, mutable=["intermediates", "batch_stats"])
    yg, mg = m_g.apply(v, x, mutable=["intermediates", "batch_stats"])
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yg),
                               rtol=2e-5, atol=2e-5)
    assert (
        float(me["intermediates"]["moe_drop_rate"][0])
        == float(mg["intermediates"]["moe_drop_rate"][0])
    )
    np.testing.assert_allclose(
        np.asarray(me["batch_stats"]["router_bias"]),
        np.asarray(mg["batch_stats"]["router_bias"]),
    )

    def loss(params, mod, xx):
        y, _ = mod.apply(
            {"params": params, "batch_stats": v["batch_stats"]}, xx,
            mutable=["intermediates", "batch_stats"],
        )
        return jnp.sum(y * y)

    ge, gxe = jax.grad(loss, argnums=(0, 2))(v["params"], m_e, x)
    gg, gxg = jax.grad(loss, argnums=(0, 2))(v["params"], m_g, x)
    np.testing.assert_allclose(np.asarray(gxe), np.asarray(gxg),
                               rtol=5e-4, atol=5e-4)
    for (pe, le), (_, lg) in zip(
        jtu.tree_leaves_with_path(ge), jtu.tree_leaves_with_path(gg)
    ):
        np.testing.assert_allclose(
            np.asarray(le), np.asarray(lg), rtol=5e-4, atol=5e-4,
            err_msg=f"cf={cf} {jtu.keystr(pe)}",
        )


def test_expert_choice_single_expert_is_dense_mlp():
    """router='expert_choice' with one expert at capacity T picks every
    token once with gate 1.0 — exactly the dense expert MLP."""
    G, T, D, F = 2, 32, 16, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (G, T, D), jnp.float32)
    m = MoEMlp(router="expert_choice", num_experts=1, top_k=1,
               capacity_factor=1.0, mlp_dim=F, expert_axis=None)
    v = m.init(jax.random.PRNGKey(0), x)
    y, mut = m.apply(v, x, mutable=["intermediates"])
    p = v["params"]
    h = jax.nn.gelu(x @ p["expert_w_in"][0] + p["expert_b_in"][0])
    ref = h @ p["expert_w_out"][0] + p["expert_b_out"][0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert float(mut["intermediates"]["moe_drop_rate"][0]) == 0.0


def test_expert_choice_perfect_balance_no_state():
    """Expert choice fills every buffer slot (load exactly 1/E), needs
    no batch_stats balancing state, and the router still receives
    gradients through the combine weights."""
    G, T, D, F, E, K = 2, 32, 16, 32, 4, 2
    x = jax.random.normal(jax.random.PRNGKey(1), (G, T, D), jnp.float32)
    m = MoEMlp(router="expert_choice", num_experts=E, top_k=K,
               capacity_factor=1.0, mlp_dim=F, expert_axis=None)
    v = m.init(jax.random.PRNGKey(0), x)
    assert "batch_stats" not in v
    y, mut = m.apply(v, x, mutable=["intermediates"])
    np.testing.assert_allclose(
        np.asarray(mut["intermediates"]["moe_load_frac"][0]),
        np.full(E, 1.0 / E), rtol=1e-6,
    )
    g = jax.grad(lambda pp: jnp.sum(m.apply(
        {"params": pp}, x, mutable=["intermediates"])[0] ** 2))(v["params"])
    assert float(jnp.linalg.norm(g["router"]["kernel"])) > 0


@pytest.mark.fast
def test_expert_choice_gating_slots_full():
    """Every (expert, slot) pair selects exactly one token — zero
    padding by construction (ops/moe.py expert_choice_gating)."""
    from ddp_practice_tpu.ops.moe import expert_choice_gating

    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 4))
    dispatch, combine, uncovered = expert_choice_gating(logits, capacity=4)
    np.testing.assert_allclose(np.asarray(jnp.sum(dispatch, axis=1)), 1.0)
    assert 0.0 <= float(uncovered) <= 1.0
    # combine weights are the router gates at the picked pairs
    gates = jax.nn.softmax(logits, axis=-1)
    w = np.asarray(jnp.sum(combine, axis=-1))  # (G, T, E), nonzero where picked
    picked = np.asarray(jnp.sum(dispatch, axis=-1)) > 0
    np.testing.assert_allclose(w[picked], np.asarray(gates)[picked], rtol=1e-6)


def test_expert_choice_lm_trains():
    """lm_moe with moe_router='expert_choice' trains end-to-end (loss
    decreases) through the standard step machinery."""
    model = create_model(
        "lm_moe", policy=None, vocab_size=64, max_len=32,
        hidden_dim=32, depth=2, num_heads=4, mlp_dim=64,
        num_experts=4, moe_router="expert_choice", capacity_factor=1.0,
    )
    import optax

    from ddp_practice_tpu.train.state import create_state
    from ddp_practice_tpu.train.steps import make_lm_train_step

    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (4, 33), 0, 64, dtype=jnp.int32
    )
    state = create_state(model, optax.adam(1e-2), rng=jax.random.PRNGKey(1),
                         sample_input=tokens[:, :-1])
    step = make_lm_train_step(model, optax.adam(1e-2))
    first = None
    for i in range(8):
        state, metrics = step(state, {"tokens": tokens})
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_expert_choice_lm_generates():
    """An expert-choice lm_moe checkpoint generates through the KV-cache
    decode path: EC has no serving story at T=1 (every expert would pick
    the lone token), so decode falls back to per-token top-k over the
    gates — the standard EC serving approximation (ops/moe.py)."""
    from ddp_practice_tpu.inference import make_generate_fn

    model = create_model(
        "lm_moe", policy=None, vocab_size=32, max_len=64,
        hidden_dim=32, depth=2, num_heads=4, mlp_dim=64,
        num_experts=4, moe_router="expert_choice", capacity_factor=1.0,
    )
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    gen = make_generate_fn(model, max_new_tokens=6)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, (2, 8)), jnp.int32
    )
    out = gen(params, prompt, jax.random.PRNGKey(1))
    assert out.shape == (2, 14)
    assert (np.asarray(out[:, :8]) == np.asarray(prompt)).all()


# ------------------------------------------- a chip's share of the experts
# `LatentMoE` / `GatedMoE` (ops/moe.py): the rows in and out of the expert
# kernel are the held picks' (`moe_rows_fill`, `moe_rows_sum`, in interpret
# mode here), whatever the router does.

def _held_layer(kind, *, k, held, experts, router="sigmoid"):
    from ddp_practice_tpu.ops import moe

    if kind == "latent":
        return moe.LatentMoE(
            num_experts=experts, top_k=k, latent_dim=128, expert_dim=24,
            shared_dim=40, experts_held=held, expert_offset=experts - held,
            routed_scaling=1.5)
    return moe.GatedMoE(
        num_experts=experts, top_k=k, expert_dim=24, shared_dim=40,
        experts_held=held, expert_offset=experts - held, routed_scaling=1.5,
        router=router, shared_gate=router == "softmax")


def _steer(params, routing, *, k, held, experts):
    """The layer's parameters with its router made to do `routing`: the
    first input feature is a constant 1 (`_held_case`) and the router's
    first row says which experts every token prefers."""
    prefer = np.full((experts,), -30.0, np.float32)
    first = experts - held
    if routing == "all_held":
        prefer[first:first + k] = 30.0
    elif routing == "none_held":
        prefer[:k] = 30.0
    elif routing == "one_expert":
        prefer[first + held // 2] = 30.0
    else:
        assert routing == "random", routing
        prefer[:] = 0.0
    router = np.array(params["router"]["kernel"])
    router[0] += prefer
    params = dict(params, router={"kernel": jnp.asarray(router)})
    if "e_score_correction_bias" in params:   # it selects; make it count
        params["e_score_correction_bias"] = jnp.asarray(
            np.random.default_rng(1).normal(size=experts) * 0.05,
            jnp.float32)
    return params


def _held_loop(layer, params, x):
    """The layer token by token in float32 numpy: the router's picks and
    weights (the layer's own router functions over the same logits), then a
    plain loop over each token's HELD picks."""
    from ddp_practice_tpu.ops import moe

    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = np.asarray(x, np.float64)
    logits = jnp.asarray(x, jnp.float32) @ params["router"]["kernel"]
    if getattr(layer, "router", "sigmoid") == "softmax":
        choices, weights = moe.route_softmax_topk(
            logits, k=layer.top_k, scaling=layer.routed_scaling)
    else:
        choices, weights = moe.route_sigmoid_topk(
            logits, params["e_score_correction_bias"], k=layer.top_k,
            scaling=layer.routed_scaling)
    choices, weights = np.asarray(choices), np.asarray(weights, np.float64)
    silu = lambda v: v / (1.0 + np.exp(-v))
    latent = isinstance(layer, moe.LatentMoE)
    u = x @ p["down"]["kernel"] if latent else x
    routed = np.zeros_like(u)
    lo = layer.expert_offset
    for t in range(x.shape[0]):
        for e, w in zip(choices[t], weights[t]):
            if not lo <= e < lo + layer.experts_held:
                continue
            if latent:
                h = np.maximum(u[t] @ p["expert_w1"][e - lo], 0.0) ** 2
                routed[t] += w * (h @ p["expert_w2"][e - lo])
            else:
                h = silu(u[t] @ p["expert_gate"][e - lo]) \
                    * (u[t] @ p["expert_up"][e - lo])
                routed[t] += w * (h @ p["expert_down"][e - lo])
    if latent:
        shared = np.maximum(x @ p["shared_in"]["kernel"], 0.0) ** 2
        return routed @ p["up"]["kernel"] + shared @ p["shared_out"]["kernel"]
    s = p["shared"]
    shared = (silu(x @ s["gate"]["kernel"]) * (x @ s["up"]["kernel"])) \
        @ s["down"]["kernel"]
    if layer.shared_gate:
        shared = shared / (1.0 + np.exp(
            -(x @ p["shared_expert_gate"]["kernel"])))
    return routed + shared


def _held_case(kind, n, k, held, experts, routing, router="sigmoid"):
    layer = _held_layer(kind, k=k, held=held, experts=experts, router=router)
    x = jax.random.normal(jax.random.PRNGKey(n), (n, 128), jnp.float32)
    x = x.at[:, 0].set(1.0)
    params = layer.init(jax.random.PRNGKey(7), x)["params"]
    params = jax.tree.map(   # weights large enough for every term to show
        lambda a: a * 8.0 if a.ndim == 3 else a, params)
    return layer, _steer(params, routing, k=k, held=held, experts=experts), x


@pytest.fixture()
def rows_by_kernel(monkeypatch):
    """The layers' row movement through the kernels, interpreted (off the
    TPU a layer takes the gathers: a kernel nobody asked for is not
    interpreted)."""
    import functools

    from ddp_practice_tpu.ops import moe

    monkeypatch.setattr(moe, "held_rows_fill", functools.partial(
        moe.held_rows_fill_kernel, interpret=True))
    monkeypatch.setattr(moe, "held_rows_sum", functools.partial(
        moe.held_rows_sum_kernel, interpret=True))


HELD_CASES = [
    # kind, n, k, held of experts, routing[, router]
    ("gated", 24, 3, 4, 16, "random"),
    ("gated", 24, 3, 4, 16, "all_held"),
    ("gated", 24, 3, 4, 16, "none_held"),
    ("gated", 24, 1, 4, 16, "one_expert"),
    ("gated", 7, 3, 8, 8, "random"),          # all held; n k = 21, tile 16
    ("gated", 24, 4, 4, 16, "random", "softmax"),
    ("gated", 24, 4, 4, 16, "all_held", "softmax"),
    ("latent", 24, 3, 4, 16, "random"),
    ("latent", 24, 3, 4, 16, "all_held"),
    ("latent", 24, 3, 4, 16, "none_held"),
    ("latent", 24, 1, 4, 16, "one_expert"),
    ("latent", 5, 2, 3, 6, "random"),         # n k = 10, under one tile
]


@pytest.mark.parametrize("rows", ["gathers", "kernels"])
@pytest.mark.parametrize("case", HELD_CASES, ids=lambda c: "-".join(
    str(v) for v in c))
def test_held_layer_is_the_loop_over_its_held_picks(case, rows, request):
    """Exact for any routing: the layer against a plain per-token float32
    loop over the picks that are held, for picks drawn at random, all held,
    none held, every token on one expert, and `n k` off the tile; with the
    gathers (what the CPU runs) and with the kernels (what the chip runs)."""
    if rows == "kernels":
        request.getfixturevalue("rows_by_kernel")
    layer, params, x = _held_case(*case)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(
            lambda p, x: layer.apply({"params": p}, x))(params, x))
    want = _held_loop(layer, params, x)
    assert np.isfinite(got).all()
    # the routing is the one asked for (the steering worked)
    _, mut = layer.apply({"params": params}, x, decode=True,
                         mutable=["cache"])
    held_picks = int(mut["cache"]["moe_stats"][0])
    n, k, routing = case[1], case[2], case[5]
    assert held_picks == {"all_held": n * k, "none_held": 0,
                          "one_expert": n}.get(routing, held_picks)
    scale = max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=2e-4 * scale, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rows", ["gathers", "kernels"])
def test_no_row_without_a_valid_pick_reaches_a_token(rows, dtype):
    """A non-finite value in every buffer row that holds no valid pick (the
    tiles' tails, the idle tiles, row 0 where it is one) leaves every sum
    finite and as it was: a pick that is not held adds an exact 0."""
    from ddp_practice_tpu.ops import moe

    n, k, experts, held, tile, d = 24, 3, 16, 5, 16, 128
    ka, kb, kc = jax.random.split(jax.random.PRNGKey(3), 3)
    _, choices = jax.lax.top_k(jax.random.normal(ka, (n, experts)), k)
    weights = jax.random.uniform(kb, (n, k), jnp.float32)
    lay = moe.held_tile_layout(choices.astype(jnp.int32), offset=2,
                               held=held, tile=tile)
    assert not bool(lay["pick_held"].all()) and bool(lay["pick_held"].any())
    clean = jnp.where(lay["row_valid"][:, None], jax.random.normal(
        kc, (lay["row_valid"].shape[0], d)), 0.0).astype(dtype)
    poisoned = jnp.where(lay["row_valid"][:, None], clean, jnp.nan)
    if rows == "kernels":
        total = lambda out: moe.held_rows_sum_kernel(
            out, lay, weights, jnp.float32, tile=tile, interpret=True)
    else:
        total = lambda out: moe.held_rows_sum_reference(
            out, lay, weights, jnp.float32)
    got = np.asarray(total(poisoned))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, np.asarray(total(clean)))
    want = np.zeros((n, d))
    for t in range(n):
        for j in range(k):
            if lay["pick_held"][t, j]:
                want[t] += float(weights[t, j]) * np.asarray(
                    clean[lay["pick_row"][t, j]], np.float64)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n, k, experts, held, offset", [
    (24, 3, 16, 5, 2), (7, 3, 4, 4, 0), (40, 1, 16, 4, 12), (33, 2, 8, 2, 6)])
def test_rows_fill_kernel_is_the_gather_to_the_bit(n, k, experts, held,
                                                   offset, dtype):
    """`moe_rows_fill`: the used tiles row for row what the gather over the
    whole layout gives (16-bit rows are moved as halves of 32-bit words:
    odd and even tokens both)."""
    from ddp_practice_tpu.ops import moe

    ka, kb = jax.random.split(jax.random.PRNGKey(n), 2)
    _, choices = jax.lax.top_k(jax.random.normal(ka, (n, experts)), k)
    src = jax.random.normal(kb, (n, 256)).astype(dtype)
    lay = moe.held_tile_layout(choices.astype(jnp.int32), offset=offset,
                               held=held, tile=16)
    used = int(lay["tiles_used"][0]) * 16
    assert used
    got = moe.held_rows_fill_kernel(src, lay, tile=16, interpret=True)
    want = moe.held_rows_fill_reference(src, lay)
    np.testing.assert_array_equal(
        np.asarray(got[:used].astype(jnp.float32)),
        np.asarray(want[:used].astype(jnp.float32)))
    moved, layout = (int(v) for v in moe.held_rows_moved(lay, 16))
    assert moved == used + int(lay["pick_held"].sum()) <= layout
    assert layout == lay["row_valid"].shape[0] + n * k
