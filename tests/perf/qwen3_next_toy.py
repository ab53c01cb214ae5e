"""The Qwen3-Next configuration at toy widths (one period of four layers, G
G G A; 4 query heads of 64 on a stream of 128, so the head size is not the
width over the heads; 2 key heads serving 4 value heads), and seeded weights
for it whose recurrent state lives for hundreds of tokens (`dt_bias` near
-4: the benchmark's own weights rule gives a state that forgets half of
itself a token, which would hide a wrong state behind a short memory)."""

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = {
    "family": "qwen3_next", "program_model": "qwen3_next",
    "reference": "qwen3_next",
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 64,
    "hidden_size": 128, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 32, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 32,
    "max_position_embeddings": 4096, "mlp_only_layers": [],
    "moe_intermediate_size": 48, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 48, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 96,
    "layers_run": 4, "num_experts_held": 4, "expert_offset": 0,
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None):
    from ddp_practice_tpu.models import create_model
    from perf.families import qwen3_next as family

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
        elif name in ("A_log", "weight"):   # `weight`: a zero-centred norm's
            z = 0.1 * z
        elif name == "conv_kernel":
            z = 0.5 * z
        else:  # unit-scale outputs: normal over the fan-in
            z = z / np.sqrt(a.shape[-2] if a.ndim > 1 else 1.0)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)
