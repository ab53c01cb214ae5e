"""The DeepSeek-V3 configuration at toy widths, and seeded weights for it at
unit scale (the benchmark's own 0.02-normal rule gives a toy's attention and
router nothing to decide)."""

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = {
    "family": "deepseek_v3", "program_model": "deepseek_v3",
    "reference": "deepseek_v3",
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 8,
    "layers_run": 3, "first_k_dense_replace": 1,
    "max_position_embeddings": 4096, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 32, "q_lora_rank": None, "rope_theta": 10000,
    "rope_interleave": True, "rope_scaling": None,
    "intermediate_size": 128, "moe_intermediate_size": 48,
    "n_routed_experts": 16, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "routed_scaling_factor": 2.448,
    "rms_norm_eps": 1e-6,
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None):
    from ddp_practice_tpu.models import create_model
    from perf.families import deepseek_v3 as family

    model = create_model(cfg["program_model"], policy=policy,
                         **family.model_options(cfg))
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
        elif name == "e_score_correction_bias":
            z = 0.1 * z
        elif name == "kv_b":          # (latent, heads, nope + v): fan-in
            z = z / np.sqrt(a.shape[0])
        elif name == "embedding":
            pass
        else:   # unit-scale outputs: normal over the fan-in
            fan = a.shape[-2] if name.startswith("expert_") else \
                int(np.prod(a.shape[:-1])) if a.ndim == 2 else a.shape[0]
            if a.ndim == 3 and not name.startswith("expert_"):
                # q (d, h, e): fan-in d; out (h, v, d): fan-in h v
                fan = a.shape[0] if str(path[-2].key) == "q" \
                    else a.shape[0] * a.shape[1]
            z = z / np.sqrt(fan)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)
