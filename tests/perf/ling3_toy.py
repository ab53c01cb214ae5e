"""The Ling-3.0-flash configuration at toy widths (four layers at a period of
three, K K T K, one leading dense layer; 4 heads of 16 on a stream of 64;
latent attention of 16 nope + 8 rope lanes over a latent of 32; 16 experts
in 4 groups of which 2 are kept, top-3), and seeded weights for it with
unit-scale outputs whose recurrent state lives for tens of tokens (`dt_bias`
near -4: with a bias near 0 the safe gate sits near -2.5 and a state forgets
nine tenths of itself a token, which would hide a wrong state behind a short
memory)."""

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = {
    "family": "ling3", "program_model": "ling3", "reference": "ling3",
    "num_hidden_layers": 4, "hidden_size": 64, "intermediate_size": 96,
    "first_k_dense_replace": 1, "max_position_embeddings": 4096,
    "moe_intermediate_size": 24, "num_experts_per_tok": 3,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": 16, "num_key_value_heads": 4, "rope_theta": 6000000,
    "rms_norm_eps": 1e-6, "head_dim": 16, "vocab_size": 96,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 4, "topk_group": 2,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 24, "layer_group_size": 3,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 8, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "layers_run": 4, "num_experts_held": 16, "expert_offset": 0,
    "dt_bias_shift": -4.0,
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None):
    from ddp_practice_tpu.models import create_model
    from perf.families import ling3 as family

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
        elif name in ("A_log", "e_score_correction_bias"):
            z = 0.1 * z
        elif name == "conv_kernel":
            z = 0.5 * z
        else:  # unit-scale outputs: normal over the fan-in
            fan = int(np.prod(a.shape[:-1])) if "out" == str(path[-2].key) \
                else a.shape[-2] if a.ndim > 1 else 1.0
            z = z / np.sqrt(fan)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)
