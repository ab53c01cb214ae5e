"""A DeepSeek-V3-style configuration file (multi-head latent attention,
leading dense layers, then gated experts with a shared one), read for the
harness.

Everything that knows the KEYS of a `deepseek_v3` configuration is here,
found by the file's `family`: the options of the program's
`create_model("deepseek_v3", ...)`, and the bytes and operations of a decode
step that the `flood_*` readers divide by. Serving only: no training data.
The depth as run is `layers_run` (`num_hidden_layers` keeps the source's).
"""

from __future__ import annotations

BF16 = 2


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def counts(cfg: dict) -> dict:
    """Layers as run: all have latent attention; the first
    `first_k_dense_replace` a dense MLP, the others experts."""
    n = cfg["layers_run"]
    dense = min(cfg["first_k_dense_replace"], n)
    return {"attn": n, "dense": dense, "moe": n - dense}


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving."""
    if cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("the program runs full-rank queries, plain rotary "
                         "positions and an ungrouped, normalised sigmoid "
                         "router: this file asks for another")
    return {
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "num_layers": cfg["layers_run"],
        "max_len": cfg["max_position_embeddings"],
        "num_heads": cfg["num_attention_heads"],
        "nope_dim": cfg["qk_nope_head_dim"],
        "rope_dim": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "latent_dim": cfg["kv_lora_rank"],
        "rope_theta": float(cfg["rope_theta"]),
        "rope_interleave": bool(cfg["rope_interleave"]),
        "first_dense": cfg["first_k_dense_replace"],
        "mlp_dim": cfg["intermediate_size"],
        "num_experts": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "experts_held": cfg["n_routed_experts"],
        "expert_offset": 0,
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "norm_eps": cfg["rms_norm_eps"],
    }


def latent_row(cfg: dict) -> int:
    """Useful values of a cached token in one layer: the normalised latent
    and the one rotated key all heads share."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_bytes(cfg: dict) -> tuple:
    """(latent bytes a cached token, absorbed-query and output bytes a slot
    and step), over the layers as run, in the served type (bf16). The
    USEFUL bytes: the pool pads a row to whole lane tiles (576 -> 640) and
    the kernel reads the padding too, which counts against it."""
    n, heads = cfg["layers_run"], cfg["num_attention_heads"]
    q_and_out = heads * (latent_row(cfg) + cfg["kv_lora_rank"]) * BF16 * n
    return latent_row(cfg) * BF16 * n, q_and_out


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE routed expert's three matrices (bf16): what
    `moe_gmm_glu` streams for every expert that has a row."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BF16


def param_count(cfg: dict) -> int:
    """Parameters of the model as run (`layers_run` layers, embedding and
    untied head)."""
    d, c = cfg["hidden_size"], counts(cfg)
    h, lat = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = d * h * qk + d * latent_row(cfg) + lat \
        + lat * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + h * cfg["v_head_dim"] * d
    f = cfg["moe_intermediate_size"]
    moe = d * cfg["n_routed_experts"] + cfg["n_routed_experts"] \
        + cfg["n_routed_experts"] * 3 * d * f \
        + 3 * d * cfg["n_shared_experts"] * f
    dense = 3 * d * cfg["intermediate_size"]
    norms = 2 * d * c["attn"] + d
    return c["attn"] * attn + c["dense"] * dense + c["moe"] * moe + norms \
        + 2 * d * cfg["vocab_size"]


def mla_decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of the absorbed attention kernel for ONE cached
    token read by one slot and step, over the layers as run: every head's
    score over the row and its weighted sum of the latent. The MXU bound of
    `paged_decode_mla` beside its byte bound (perf/layer_metrics/
    flood_mla_decode_roofline.py)."""
    heads = cfg["num_attention_heads"]
    return 2.0 * heads * (latent_row(cfg) + cfg["kv_lora_rank"]) \
        * cfg["layers_run"]


def decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of one decoded token through the layers as run
    (top-k experts and the shared one), without the attention over the
    cache."""
    d, c = cfg["hidden_size"], counts(cfg)
    h, lat = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = d * h * qk + d * latent_row(cfg) \
        + h * cfg["qk_nope_head_dim"] * lat + h * lat * cfg["v_head_dim"] \
        + h * cfg["v_head_dim"] * d
    f = cfg["moe_intermediate_size"]
    moe = d * cfg["n_routed_experts"] \
        + (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) * 3 * d * f
    dense = 3 * d * cfg["intermediate_size"]
    return 2.0 * (c["attn"] * attn + c["dense"] * dense + c["moe"] * moe
                  + d * cfg["vocab_size"])
