"""The MiniCPM-SALA configuration at toy widths (four of eight layers, L L B
L by the toy's own `mixer_types`; 4 query heads of 16 on 2 KV heads; blocks
of 8 tokens, compressed keys of 4 at stride 2, top-4, dense up to 32 visible
tokens, so that a sequence of some sixty tokens meets the sparse branch, the
forced blocks and the switch inside a chunk), and seeded weights for it with
unit-scale outputs (the benchmark's own 0.02 would leave every score flat
and every pick a tie)."""

import jax
import jax.numpy as jnp
import numpy as np

L, S = "lightning-attn", "minicpm4"
CONFIG = {
    "family": "minicpm_sala", "program_model": "minicpm_sala",
    "reference": "minicpm_sala",
    "attention_bias": False, "attn_use_rope": False, "head_dim": 16,
    "hidden_size": 64, "intermediate_size": 96, "lightning_head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_use_rope": True,
    "max_position_embeddings": 4096, "mixer_types": [S, L, L, L, L, S, L, L],
    "num_attention_heads": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "qk_norm": True, "rms_norm_eps": 1e-6,
    "vocab_size": 96, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
    "layers_run": 4, "layers_published": [3, 4, 5, 6],
    "sparse": {"block": 8, "kernel": 4, "stride": 2, "init_blocks": 1,
               "window": 8, "dense_len": 32, "topk": 4},
}


def config(**kw) -> dict:
    return dict(CONFIG, **kw)


def model_and_params(cfg: dict, seed: int = 0, policy=None):
    from ddp_practice_tpu.models import create_model
    from perf.families import minicpm_sala as family

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
        elif name == "embedding":
            z = z / cfg["scale_emb"]
        else:  # unit-scale outputs: normal over the fan-in
            fan = int(np.prod(a.shape[:-1])) if "out" == str(path[-2].key) \
                else a.shape[0]
            z = z / np.sqrt(fan)
        out.append(z.astype(a.dtype))
    return model, jax.tree_util.tree_unflatten(treedef, out)
