"""The Nemotron-H configuration at toy widths, and seeded weights for it
whose recurrent state lives for hundreds of tokens (`dt_bias` near -4: the
benchmark's own weights rule gives a state that forgets half of itself a
token, which would hide a wrong state behind a short memory)."""

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = {
    "family": "nemotron_h", "program_model": "nemotron_h",
    "reference": "nemotron_h",
    "hybrid_override_pattern": "MEM*EME",
    "vocab_size": 96, "hidden_size": 64, "max_position_embeddings": 4096,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 3,
    "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96,
    "n_routed_experts_held": 4, "expert_offset": 0,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None):
    from ddp_practice_tpu.models import create_model
    from perf.families import nemotron_h as family

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
        elif name == "dt_bias":
            z = -4.0 + 0.3 * z
        elif name in ("A_log", "D", "conv_bias", "e_score_correction_bias"):
            z = 0.1 * z
        elif name == "conv_kernel":
            z = 0.5 * z
        else:  # unit-scale outputs: normal over the fan-in
            z = z / np.sqrt(a.shape[-2] if a.ndim > 1 else 1.0)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)
