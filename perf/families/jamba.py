"""A Jamba configuration file (Mamba-1 selective-scan layers with one
attention layer a period, a dense SwiGLU MLP after every mixer, tied
embeddings), read for the harness.

Everything that knows the KEYS of a `jamba` configuration is here, found by
the file's `family`: the options of the program's `create_model("jamba",
...)`, and the bytes and operations of a decode step and of a prompt's scan
that the `flood_*` readers divide by. Serving only: no training data.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def mixers(cfg: dict) -> str:
    """One letter a layer: '*' where the layer is attention (every
    `attn_layer_period`-th from `attn_layer_offset`), 'S' (Mamba-1) else."""
    return "".join(
        "*" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        else "S" for i in range(cfg["num_hidden_layers"]))


def counts(cfg: dict) -> dict:
    """Layers of each mixer kind; every layer has a dense MLP besides."""
    m = mixers(cfg)
    return {"S": m.count("S"), "*": m.count("*"), "D": len(m)}


def inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving: a
    layer is two residual sub-layers, its mixer then the dense MLP 'D'."""
    if cfg["num_experts"] != 1 or not cfg["tie_word_embeddings"] \
            or not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"] \
            or cfg["sliding_window"] is not None:
        raise ValueError("the program runs dense feed-forwards, a tied head, "
                         "a conv bias, no projection bias and full causal "
                         "attention: this file asks for another")
    heads = cfg["num_attention_heads"]
    return {
        "pattern": "".join(m + "D" for m in mixers(cfg)),
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "max_len": cfg["max_position_embeddings"],
        "mamba_inner": inner(cfg),
        "ssm_state": cfg["mamba_d_state"],
        "dt_rank": cfg["mamba_dt_rank"],
        "conv_kernel": cfg["mamba_d_conv"],
        "mlp_dim": cfg["intermediate_size"],
        "num_heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "norm_eps": cfg["rms_norm_eps"],
        "tie_embeddings": True,
    }


def decode_bytes(cfg: dict) -> tuple:
    """(K and V bytes a cached token, q + out bytes a slot and step), over
    the attention layers, in the served type (bf16)."""
    n = counts(cfg)["*"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    kv = 2 * cfg["num_key_value_heads"] * hd * BF16 * n
    q_and_out = 2 * cfg["num_attention_heads"] * hd * BF16 * n
    return kv, q_and_out


def ssm_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's recurrent state in ONE Mamba layer: the float32
    (state, channels) tensor `sel_step` reads and writes."""
    return F32 * cfg["mamba_d_state"] * inner(cfg)


def conv_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's conv tail in ONE Mamba layer (bf16)."""
    return BF16 * (cfg["mamba_d_conv"] - 1) * inner(cfg)


def scan_bytes_per_token(cfg: dict) -> int:
    """Least HBM bytes `sel_scan` moves a real token in ONE Mamba layer: u
    and dt read once, y written once (float32, a value a channel), B and C
    read once; the state's two passes a call are `ssm_state_bytes` each."""
    return F32 * (3 * inner(cfg) + 2 * cfg["mamba_d_state"])


def decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of one decoded token through the layers as run,
    without the attention over the cache."""
    d, c, k = cfg["hidden_size"], inner(cfg), counts(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    mamba = d * 2 * c + c * (r + 2 * n) + r * c + c * d \
        + cfg["mamba_d_conv"] * c + 3 * c * n
    hd = d // cfg["num_attention_heads"]
    attn = 2 * d * d + 2 * d * cfg["num_key_value_heads"] * hd
    mlp = 3 * d * cfg["intermediate_size"]
    head = d * cfg["vocab_size"]
    return 2.0 * (k["S"] * mamba + k["*"] * attn + k["D"] * mlp + head)
