"""A Qwen3-Next configuration file (Gated DeltaNet layers with a gated
attention layer every `full_attention_interval`-th, an expert layer with a
softmax router and a gated shared expert after every mixer), read for the
harness.

Everything that knows the KEYS of a `qwen3_next` configuration is here, found
by the file's `family`: the options of the program's
`create_model("qwen3_next", ...)`, and the bytes and operations of a decode
step and of a prompt's chunked scan that the `flood_*` readers divide by.
Serving only: no training data.
"""

from __future__ import annotations

BF16, F32 = 2, 4
# positions a chunk of the program's scan holds (ops/gdn.py CHUNK): what the
# kernel's own bytes and operations a token depend on
SCAN_CHUNK = 64


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def mixers(cfg: dict) -> str:
    """One letter a layer as run: 'A' where (i + 1) is a multiple of
    `full_attention_interval`, 'G' (Gated DeltaNet) else."""
    return "".join(
        "A" if (i + 1) % cfg["full_attention_interval"] == 0 else "G"
        for i in range(cfg["layers_run"]))


def counts(cfg: dict) -> dict:
    """Layers of each mixer kind; every layer has an expert layer besides."""
    m = mixers(cfg)
    return {"G": m.count("G"), "A": m.count("A"), "Q": len(m)}


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving: a
    layer is two residual sub-layers, its mixer then the expert layer 'Q'."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] \
            or cfg["tie_word_embeddings"] or not cfg["norm_topk_prob"] \
            or cfg["rope_scaling"] is not None or cfg["use_sliding_window"]:
        raise ValueError("the program runs an expert layer after every mixer, "
                         "an untied head, renormalised picks, plain rotary "
                         "and full causal attention: this file asks for "
                         "another")
    return {
        "pattern": "".join(m + "Q" for m in mixers(cfg)),
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "max_len": cfg["max_position_embeddings"],
        "gdn_key_heads": cfg["linear_num_key_heads"],
        "gdn_value_heads": cfg["linear_num_value_heads"],
        "gdn_key_dim": cfg["linear_key_head_dim"],
        "gdn_value_dim": cfg["linear_value_head_dim"],
        "conv_kernel": cfg["linear_conv_kernel_dim"],
        "num_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "rope_dim": int(cfg["partial_rotary_factor"] * cfg["head_dim"]),
        "rope_theta": float(cfg["rope_theta"]),
        "num_experts": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "expert_dim": cfg["moe_intermediate_size"],
        "shared_dim": cfg["shared_expert_intermediate_size"],
        "experts_held": cfg["num_experts_held"],
        "expert_offset": cfg["expert_offset"],
        "norm_eps": cfg["rms_norm_eps"],
    }


def decode_bytes(cfg: dict) -> tuple:
    """(K and V bytes a cached token, q + out bytes a slot and step), over
    the attention layers, in the served type (bf16)."""
    n, hd = counts(cfg)["A"], cfg["head_dim"]
    kv = 2 * cfg["num_key_value_heads"] * hd * BF16 * n
    q_and_out = 2 * cfg["num_attention_heads"] * hd * BF16 * n
    return kv, q_and_out


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE routed expert's three matrices (bf16): what
    `moe_gmm_glu` streams for every held expert that has a row."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BF16


def ssm_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's recurrent state in ONE Gated DeltaNet layer: the
    float32 (value heads, key_dim, value_dim) tensor `gdn_step` reads and
    writes."""
    return F32 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def conv_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's conv tail in ONE Gated DeltaNet layer (bf16):
    the last `linear_conv_kernel_dim - 1` rows of concat(q, k, v)."""
    wide = 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] \
        + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return BF16 * (cfg["linear_conv_kernel_dim"] - 1) * wide


def scan_bytes_per_token(cfg: dict) -> int:
    """Least HBM bytes the kernel `gdn_scan` moves a real token in ONE
    Gated DeltaNet layer: a value head's rows of the chunk terms read once
    (w, q exp(G) and k exp(G_C - G) of key_dim, u0 of value_dim, the
    chunk's masked q k of SCAN_CHUNK, float32) and its output row written
    once; the state's two passes a call are `ssm_state_bytes` each."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return F32 * cfg["linear_num_value_heads"] * (
        3 * dk + 2 * dv + SCAN_CHUNK)


def scan_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of the kernel `gdn_scan` a token in ONE Gated
    DeltaNet layer: w S, (q exp(G)) S and (k exp(G_C - G))^T U against the
    (key_dim, value_dim) state, and the chunk's (C, C) mask against U. The
    chunk terms themselves (the triangular inverse, XLA) are not the
    kernel's."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 2.0 * cfg["linear_num_value_heads"] * (
        3 * dk * dv + SCAN_CHUNK * dv)


def param_count(cfg: dict) -> int:
    """Parameters held here, from the shapes: what the deployment states."""
    d, c = cfg["hidden_size"], counts(cfg)
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    values = hv * cfg["linear_value_head_dim"]
    gdn = d * (2 * keys + 2 * values) + d * 2 * hv \
        + cfg["linear_conv_kernel_dim"] * (2 * keys + values) + 2 * hv \
        + cfg["linear_value_head_dim"] + values * d
    hd, heads = cfg["head_dim"], cfg["num_attention_heads"]
    attn = d * heads * 2 * hd + 2 * d * cfg["num_key_value_heads"] * hd \
        + heads * hd * d + 2 * hd
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = cfg["num_experts_held"] * expert + d * cfg["num_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d
    norms = (2 * cfg["layers_run"] + 1) * d
    return c["G"] * gdn + c["A"] * attn + c["Q"] * moe + norms \
        + 2 * cfg["vocab_size"] * d


def decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of one decoded token through the layers as run
    (held experts only, by the expected share of picks that land on them),
    without the attention over the cache."""
    d, c = cfg["hidden_size"], counts(cfg)
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    values = hv * cfg["linear_value_head_dim"]
    gdn = d * (2 * keys + 2 * values + 2 * hv) + values * d \
        + cfg["linear_conv_kernel_dim"] * (2 * keys + values) \
        + 3 * cfg["linear_key_head_dim"] * values
    hd, heads = cfg["head_dim"], cfg["num_attention_heads"]
    attn = d * heads * 2 * hd + 2 * d * cfg["num_key_value_heads"] * hd \
        + heads * hd * d
    share = cfg["num_experts_held"] / cfg["num_experts"]
    moe = d * cfg["num_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d \
        + cfg["num_experts_per_tok"] * share \
        * 3 * d * cfg["moe_intermediate_size"]
    head = d * cfg["vocab_size"]
    return 2.0 * (c["G"] * gdn + c["A"] * attn + c["Q"] * moe + head)
