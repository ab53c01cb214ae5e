"""A Ling-3.0-flash configuration file (Kimi Delta Attention layers with a
latent-attention layer every `layer_group_size`-th, a gate a head on either
mixer's output; a dense SwiGLU for the first `first_k_dense_replace` layers,
then experts under a sigmoid router that keeps `topk_group` of `n_group`
groups, with a shared expert), read for the harness.

Everything that knows the KEYS of a `ling3` configuration is here, found by
the file's `family`: the options of the program's `create_model("ling3",
...)`, and the bytes and operations of a decode step that the `flood_*`
readers divide by (the three `kda_*` kernels' own are in `perf/lib/kda.py`,
which reads the sizes through `kda_sizes`). Serving only: no training data.
The depth as run is `layers_run` (`num_hidden_layers` keeps the source's).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def mixers(cfg: dict) -> str:
    """One letter a layer as run: 'T' (latent attention) where (i + 1) is a
    multiple of `layer_group_size`, 'K' (Kimi Delta Attention) else."""
    return "".join(
        "T" if (i + 1) % cfg["layer_group_size"] == 0 else "K"
        for i in range(cfg["layers_run"]))


def counts(cfg: dict) -> dict:
    """Layers of each kind as run: mixers 'K' and 'T', feed-forwards 'D'
    (dense) and 'U' (experts)."""
    m = mixers(cfg)
    dense = min(cfg["first_k_dense_replace"], len(m))
    return {"K": m.count("K"), "T": m.count("T"), "D": dense,
            "U": len(m) - dense}


def kda_sizes(cfg: dict) -> tuple:
    """(heads, key_dim, value_dim) of a Kimi Delta Attention layer: one key
    head a value head (`num_kv_heads_for_linear_attn` 0), both `head_dim`."""
    return cfg["num_attention_heads"], cfg["head_dim"], cfg["head_dim"]


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving: a
    layer is two residual sub-layers, its mixer then its feed-forward."""
    if cfg["q_lora_rank"] is not None or cfg["score_function"] != "sigmoid" \
            or not cfg["norm_topk_prob"] or not cfg["kda_safe_gate"] \
            or not cfg["no_kda_lora"] or cfg["use_kda_lora"] \
            or not cfg["linear_silu"] or cfg["group_norm_size"] != 1 \
            or cfg["num_kv_heads_for_linear_attn"] \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise" \
            or not cfg["moe_router_enable_expert_bias"] \
            or cfg["use_nGPT"] or cfg["scale_router_input"] \
            or cfg["value_norm"] or cfg["up_proj_norm"] \
            or cfg["rotary_dim"] != cfg["qk_rope_head_dim"] \
            or abs(cfg["kda_lower_bound"]) > 5:
        raise ValueError(
            "the program runs full-rank queries and decay projections, the "
            "safe gate down to -5, a head-wise output gate, one key head a "
            "value head and a normalised, biased sigmoid router over groups: "
            "this file asks for another")
    heads, dk, dv = kda_sizes(cfg)
    return {
        "layers": cfg["layers_run"],
        "layer_group_size": cfg["layer_group_size"],
        "first_dense": cfg["first_k_dense_replace"],
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "max_len": cfg["max_position_embeddings"],
        "gdn_value_heads": heads, "gdn_key_dim": dk, "gdn_value_dim": dv,
        "conv_kernel": cfg["short_conv_kernel_size"],
        "kda_lower_bound": float(cfg["kda_lower_bound"]),
        "num_heads": cfg["num_attention_heads"],
        "nope_dim": cfg["qk_nope_head_dim"],
        "rope_dim": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "attn_latent_dim": cfg["kv_lora_rank"],
        "rope_theta": float(cfg["rope_theta"]),
        "mlp_dim": cfg["intermediate_size"],
        "num_experts": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "n_group": cfg["n_group"],
        "topk_group": cfg["topk_group"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["moe_shared_expert_intermediate_size"],
        "experts_held": cfg["num_experts_held"],
        "expert_offset": cfg["expert_offset"],
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "norm_eps": cfg["rms_norm_eps"],
    }


def latent_row(cfg: dict) -> int:
    """Useful values of a cached token in one latent-attention layer: the
    normalised latent and the one rotated key all heads share."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_bytes(cfg: dict) -> tuple:
    """(latent bytes a cached token, absorbed-query and output bytes a slot
    and step), over the latent-attention layers as run, in the served type
    (bf16). The USEFUL bytes: the pool pads a row to whole lane tiles
    (576 -> 640) and the kernel reads the padding too."""
    n, heads = counts(cfg)["T"], cfg["num_attention_heads"]
    q_and_out = heads * (latent_row(cfg) + cfg["kv_lora_rank"]) * BF16 * n
    return latent_row(cfg) * BF16 * n, q_and_out


def mla_decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of the absorbed attention kernel for ONE cached
    token read by one slot and step, over the latent-attention layers."""
    return 2.0 * cfg["num_attention_heads"] \
        * (latent_row(cfg) + cfg["kv_lora_rank"]) * counts(cfg)["T"]


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE routed expert's three matrices (bf16): what
    `moe_gmm_glu` streams for every held expert that has a row."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BF16


def ssm_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's recurrent state in ONE Kimi Delta Attention
    layer: the float32 (heads, key_dim, value_dim) tensor `kda_step` reads
    and writes."""
    heads, dk, dv = kda_sizes(cfg)
    return F32 * heads * dk * dv


def conv_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's conv tail in ONE Kimi Delta Attention layer
    (bf16): the last `short_conv_kernel_size - 1` rows of concat(q, k, v)."""
    heads, dk, dv = kda_sizes(cfg)
    return BF16 * (cfg["short_conv_kernel_size"] - 1) * heads * (2 * dk + dv)


def mixer_params(cfg: dict) -> dict:
    """Parameters of one mixer of each kind, from the shapes."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    _, dk, dv = kda_sizes(cfg)
    keys, values = heads * dk, heads * dv
    kda = d * (2 * keys + values) + d * keys + keys \
        + cfg["short_conv_kernel_size"] * (2 * keys + values) \
        + 2 * d * heads + heads + dv + values * d
    lat = cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = d * heads * qk + d * latent_row(cfg) + lat \
        + lat * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + d * heads + heads * cfg["v_head_dim"] * d
    return {"K": kda, "T": mla}


def param_count(cfg: dict) -> int:
    """Parameters held here, from the shapes: `layers_run` layers with
    `num_experts_held` experts each, embedding and untied head over
    `vocab_size` rows. Handed the published depth, experts and vocabulary it
    counts the whole model."""
    d, c, mix = cfg["hidden_size"], counts(cfg), mixer_params(cfg)
    moe = cfg["num_experts_held"] * 3 * d * cfg["moe_intermediate_size"] \
        + d * cfg["num_experts"] + cfg["num_experts"] \
        + 3 * d * cfg["moe_shared_expert_intermediate_size"]
    dense = 3 * d * cfg["intermediate_size"]
    norms = (2 * cfg["layers_run"] + 1) * d
    return c["K"] * mix["K"] + c["T"] * mix["T"] + c["D"] * dense \
        + c["U"] * moe + norms + 2 * cfg["vocab_size"] * d


def decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of one decoded token through the layers as run
    (held experts only, by the expected share of picks that land on them),
    without the attention over the cache."""
    d, c, heads = cfg["hidden_size"], counts(cfg), cfg["num_attention_heads"]
    _, dk, dv = kda_sizes(cfg)
    keys, values = heads * dk, heads * dv
    kda = d * (3 * keys + values + 2 * heads) + values * d \
        + cfg["short_conv_kernel_size"] * (2 * keys + values) + 3 * dk * values
    lat = cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = d * heads * qk + d * latent_row(cfg) + d * heads \
        + heads * cfg["qk_nope_head_dim"] * lat \
        + heads * lat * cfg["v_head_dim"] + heads * cfg["v_head_dim"] * d
    share = cfg["num_experts_held"] / cfg["num_experts"]
    moe = d * cfg["num_experts"] \
        + 3 * d * cfg["moe_shared_expert_intermediate_size"] \
        + cfg["num_experts_per_tok"] * share \
        * 3 * d * cfg["moe_intermediate_size"]
    dense = 3 * d * cfg["intermediate_size"]
    return 2.0 * (c["K"] * kda + c["T"] * mla + c["D"] * dense + c["U"] * moe
                  + d * cfg["vocab_size"])
