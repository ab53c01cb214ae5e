"""The SmallThinker configuration at toy widths (eight of twelve layers, two
periods G W W W by the toy's own `sliding_window_layout`; 4 query heads of 16
on 2 KV heads at a hidden size of 48, so a head is NOT width / heads; a
window of 8; 8 ReGLU experts of 3, 2 a token), and seeded weights for it with
unit-scale outputs (the benchmark's own 0.02 would leave every score flat and
every pick of the router a tie)."""

import jax
import jax.numpy as jnp
import numpy as np

LAYOUT = [0, 1, 1, 1] * 3
CONFIG = {
    "family": "smallthinker", "program_model": "smallthinker",
    "reference": "smallthinker",
    "head_dim": 16, "hidden_size": 48, "max_position_embeddings": 4096,
    "moe_ffn_hidden_size": 3, "moe_num_active_primary_experts": 2,
    "moe_num_primary_experts": 8, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_hidden_layers": 12, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": LAYOUT, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 8,
    "tie_word_embeddings": False, "vocab_size": 96,
    "layers_run": 8, "layers_published": list(range(8)),
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None, **options):
    from ddp_practice_tpu.models import create_model
    from perf.families import smallthinker as family

    model = create_model(cfg["program_model"], policy=policy,
                         **dict(family.model_options(cfg), **options))
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = str(path[-1].key)
        z = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), i), a.shape,
            jnp.float32)
        if name == "scale":
            z = 1.0 + 0.1 * z
        elif name.startswith("expert_"):   # (experts, in, out)
            z = z / np.sqrt(a.shape[1])
        elif name != "embedding":  # unit-scale outputs: normal over the fan-in
            fan = int(np.prod(a.shape[:-1])) if "out" == str(path[-2].key) \
                else a.shape[0]
            z = z / np.sqrt(fan)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)


def perf_config() -> dict:
    """The benchmark's configuration file, at the published widths."""
    import perf_toy

    return perf_toy.load("perf/configs/smallthinker_21b_pp7.json")
