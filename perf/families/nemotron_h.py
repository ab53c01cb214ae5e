"""A Nemotron-H configuration file (Mamba-2 + LatentMoE + grouped-query
attention by a pattern string), read for the harness.

Everything that knows the KEYS of a `nemotron_h` configuration is here,
found by the file's `family`: the options of the program's
`create_model("nemotron_h", ...)`, and the bytes and operations of a decode
step that the `flood_*` readers divide by. Serving only: no training data.
"""

from __future__ import annotations


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def counts(cfg: dict) -> dict:
    """Layers of each kind in the pattern as run (the pattern IS the
    depth: `num_hidden_layers` keeps the source's 88)."""
    p = cfg["hybrid_override_pattern"]
    return {"M": p.count("M"), "E": p.count("E"), "*": p.count("*")}


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving."""
    return {
        "pattern": cfg["hybrid_override_pattern"],
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "max_len": cfg["max_position_embeddings"],
        "mamba_heads": cfg["mamba_num_heads"],
        "mamba_head_dim": cfg["mamba_head_dim"],
        "ssm_state": cfg["ssm_state_size"],
        "ssm_groups": cfg["n_groups"],
        "conv_kernel": cfg["conv_kernel"],
        "chunk_size": cfg["chunk_size"],
        "num_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "num_experts": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "latent_dim": cfg["moe_latent_size"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["moe_shared_expert_intermediate_size"],
        "experts_held": cfg["n_routed_experts_held"],
        "expert_offset": cfg["expert_offset"],
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "norm_eps": cfg["layer_norm_epsilon"],
    }


def decode_bytes(cfg: dict) -> tuple:
    """(K and V bytes a cached token, q + out bytes a slot and step), over
    the attention layers, in the served type (bf16)."""
    n = counts(cfg)["*"]
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2 * n
    q_and_out = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * 2 * n
    return kv, q_and_out


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE routed expert's two matrices (bf16): what `moe_gmm`
    streams for every held expert that has a row."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"] * 2


def ssm_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's recurrent state in ONE Mamba layer: the float32
    (heads, head_dim, state) tensor `ssm_step` reads and writes."""
    return 4 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of one decoded token through the layers as run
    (held experts only, by the expected share of picks that land on them),
    without the attention over the cache."""
    d, c = cfg["hidden_size"], counts(cfg)
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = d * (2 * inner + 2 * gn + cfg["mamba_num_heads"]) + inner * d \
        + 2 * inner * cfg["ssm_state_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    attn = d * hd + 2 * d * cfg["num_key_value_heads"] * cfg["head_dim"] \
        + hd * d
    lat = cfg["moe_latent_size"]
    share = cfg["n_routed_experts_held"] / cfg["n_routed_experts"]
    moe = d * cfg["n_routed_experts"] + 2 * d * lat \
        + 2 * d * cfg["moe_shared_expert_intermediate_size"] \
        + cfg["num_experts_per_tok"] * share \
        * 2 * lat * cfg["moe_intermediate_size"]
    head = d * cfg["vocab_size"]
    return 2.0 * (c["M"] * mamba + c["*"] * attn + c["E"] * moe + head)
