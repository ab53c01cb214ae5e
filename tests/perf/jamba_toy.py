"""The Jamba configuration at toy widths (one period of four layers, the
attention layer second; 4 query heads on ONE KV head), and seeded
weights for it whose recurrent state lives for hundreds of tokens (the dt
bias near -4: the benchmark's own weights rule gives a state that forgets
half of itself a token, which would hide a wrong state behind a short
memory)."""

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = {
    "family": "jamba", "program_model": "jamba", "reference": "jamba",
    "attn_layer_offset": 1, "attn_layer_period": 4,
    "hidden_size": 128, "intermediate_size": 96,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 8, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 4096, "num_attention_heads": 4,
    "num_experts": 1, "num_hidden_layers": 4, "num_key_value_heads": 1,
    "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 96,
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None):
    from ddp_practice_tpu.models import create_model
    from perf.families import jamba as family

    model = create_model(cfg["program_model"], policy=policy,
                         **family.model_options(cfg))
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = str(path[-1].key)
        under = str(path[-2].key) if len(path) > 1 else ""
        z = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), i), a.shape,
            jnp.float32)
        if name == "scale":
            z = 1.0 + 0.1 * z
        elif name == "bias" and under == "dt_proj":
            z = -4.0 + 0.3 * z
        elif name in ("A_log", "D", "conv_bias"):
            z = 0.1 * z
        elif name == "conv_kernel":
            z = 0.5 * z
        elif name == "embedding":   # tied: unit-scale logits out of it
            z = z / np.sqrt(a.shape[-1])
        else:  # unit-scale outputs: normal over the fan-in
            z = z / np.sqrt(a.shape[-2] if a.ndim > 1 else 1.0)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)
