"""Profile the lm_moe bench step (per-op device time) via utils/xprof —
the round-4 method, pointed at the MoE dispatch/combine glue (round-5
verdict item 2: lm_moe 37.66% MFU vs dense lm_long 47.27%)."""
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))


def main():
    from ddp_practice_tpu.config import MeshConfig, PrecisionPolicy, TrainConfig
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.parallel.mesh import (
        batch_sharding, build_mesh, replicated, shard_state)
    from ddp_practice_tpu.parallel.ring import set_current_mesh
    from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
    from ddp_practice_tpu.train.state import create_state, make_optimizer
    from ddp_practice_tpu.train.steps import _lm_train_step_fn
    from ddp_practice_tpu.utils.xprof import op_summary

    # lm_moe at lm_base dims (the 2026-07 suite's entry)
    seq_len, vocab, bsz, K = 2048, 32768, 8, 4
    model_kwargs = dict(
        hidden_dim=768, depth=12, num_heads=12, mlp_dim=3072,
        moe_every=2, num_experts=8, moe_group_size=256,
        capacity_factor=1.5,
    )
    mesh = build_mesh(MeshConfig(data=-1))
    set_current_mesh(mesh)
    policy = PrecisionPolicy.from_name("bf16")
    model = create_model("lm_moe", policy=policy, vocab_size=vocab,
                         max_len=seq_len, attn_impl="flash", **model_kwargs)
    tcfg = TrainConfig(model="lm_moe", optimizer="adamw", learning_rate=3e-4)
    tx = make_optimizer(tcfg)
    sample = jnp.zeros((bsz, seq_len), jnp.int32)
    abstract = jax.eval_shape(
        lambda r: create_state(model, tx, rng=r, sample_input=sample),
        jax.random.PRNGKey(0))
    shardings = shard_state(abstract, mesh, param_sharding_rules("lm_moe"))
    state = jax.jit(
        lambda r: create_state(model, tx, rng=r, sample_input=sample),
        out_shardings=shardings)(jax.random.PRNGKey(0))

    step_fn = _lm_train_step_fn(model, tx, with_accuracy=False)
    bsh = batch_sharding(mesh)
    rep = replicated(mesh)
    base_key = jax.random.PRNGKey(1)

    def chunk(state):
        def body(st, key):
            tokens = jax.random.randint(
                key, (bsz, seq_len + 1), 0, vocab, dtype=jnp.int32)
            batch = {"tokens": lax.with_sharding_constraint(tokens, bsh)}
            return step_fn(st, batch)
        keys = jax.random.split(jax.random.fold_in(base_key, state.step), K)
        state, ms = lax.scan(body, state, keys)
        return state, jax.tree.map(lambda v: v[-1], ms)

    jchunk = jax.jit(chunk, donate_argnums=0, in_shardings=(shardings,),
                     out_shardings=(shardings, rep))
    state, m = jchunk(state)
    _ = float(m["loss"])
    state, m = jchunk(state)
    _ = float(m["loss"])

    tmp = tempfile.mkdtemp(prefix="xp_moe_")
    with jax.profiler.trace(tmp):
        state, m = jchunk(state)
        _ = float(m["loss"])
    s = op_summary(tmp)
    total = s["total_ps"] / 1e9 / K
    print(f"device op time: {total:.2f} ms/step ({K} steps)")
    cats = sorted(s["categories"].items(), key=lambda kv: -kv[1]["ps"])
    for cat, v in cats[:10]:
        print(f"  {v['ps']/1e9/K:7.2f} ms/step  {cat}")
    for (cat, nm), ps in sorted(s["ops"].items(), key=lambda kv: -kv[1])[:30]:
        print(f"  {ps/1e9/K:7.3f} ms/step  [{cat}] {nm[:78]}")
    print("---- glue categories ----")
    for (cat, nm), ps in sorted(s["ops"].items(), key=lambda kv: -kv[1]):
        if cat in ("custom fusion", "loop fusion", "data formatting",
                   "pad", "sort", "non-fusion elementwise") and (
                       ps / 1e9 / K > 0.15):
            print(f"  {ps/1e9/K:7.3f} ms/step  [{cat}] {nm[:78]}")
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
