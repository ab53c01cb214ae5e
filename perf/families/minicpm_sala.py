"""A MiniCPM-SALA configuration file (a mixer a layer by `mixer_types`:
`lightning-attn` linear attention or `minicpm4` block-sparse attention, a
dense SwiGLU after every mixer, muP scalars on the stream), read for the
harness.

Everything that knows the KEYS of a `minicpm_sala` configuration is here,
found by the file's `family`: the options of the program's
`create_model("minicpm_sala", ...)`, and the bytes and operations of a decode
step and of a prompt's chunk that the `flood_*` readers divide by. Serving
only: no training data.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def mixers(cfg: dict) -> str:
    """One letter a layer as run: 'B' block-sparse attention, 'L' lightning
    attention, by the published `mixer_types` of `layers_published`."""
    if len(cfg["layers_published"]) != cfg["layers_run"]:
        raise ValueError("layers_published lists the layers_run layers")
    return "".join("B" if cfg["mixer_types"][i] == "minicpm4" else "L"
                   for i in cfg["layers_published"])


def counts(cfg: dict) -> dict:
    """Layers of each kind. 'M' are the layers on the Mamba-2 state kernels
    (`ssm_step`: the lightning layers), under the key the reader
    `flood_ssm_step_roofline` asks for."""
    m = mixers(cfg)
    return {"M": m.count("L"), "B": m.count("B"), "D": len(m)}


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving: a
    layer is two residual sub-layers, its mixer then the dense MLP 'D'."""
    from ddp_practice_tpu.ops.sparse_attention import SparseSpec

    if (cfg["lightning_nh"], cfg["lightning_nkv"],
            cfg["lightning_head_dim"]) != (cfg["num_attention_heads"],) * 2 \
            + (cfg["head_dim"],) or cfg["attn_use_rope"] \
            or not cfg["lightning_use_rope"] or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or not cfg["qk_norm"] \
            or not (cfg["use_output_gate"] and cfg["use_output_norm"]
                    and cfg["attn_use_output_gate"]):
        raise ValueError(
            "the program runs lightning heads of the attention's own count "
            "and size, rotary in the lightning layers alone, head norms, "
            "output gates, an untied head and no biases: this file asks "
            "for another")
    m = mixers(cfg)
    return {
        "pattern": "".join(k + "D" for k in m),
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "max_len": cfg["max_position_embeddings"],
        "num_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "mlp_dim": cfg["intermediate_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": cfg["rms_norm_eps"],
        "embed_scale": float(cfg["scale_emb"]),
        "residual_scale": cfg["scale_depth"]
        / cfg["num_hidden_layers"] ** 0.5,
        "head_scale": cfg["dim_model_base"] / cfg["hidden_size"],
        "lightning_layers": tuple(
            i for i, k in zip(cfg["layers_published"], m) if k == "L"),
        "decay_layers": cfg["num_hidden_layers"],
        "sparse": SparseSpec(**cfg["sparse"]),
    }


def decode_bytes(cfg: dict) -> tuple:
    """(K and V bytes a cached token, q + out bytes a slot and step), over
    the block-sparse attention layers, in the served type (bf16)."""
    n, hd = counts(cfg)["B"], cfg["head_dim"]
    kv = 2 * cfg["num_key_value_heads"] * hd * BF16 * n
    q_and_out = 2 * cfg["num_attention_heads"] * hd * BF16 * n
    return kv, q_and_out


def ssm_state_bytes(cfg: dict) -> int:
    """Bytes of one slot's recurrent state in ONE lightning layer: the
    float32 (heads, value, key) tensor `ssm_step` reads and writes."""
    return F32 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def walk_page_bytes(cfg: dict) -> int:
    """Bytes `sparse_walk` reads for ONE page of one KV head in one layer:
    the page's K and V rows of that head's lanes."""
    return 2 * cfg["sparse"]["block"] * cfg["head_dim"] * BF16


def index_row_bytes(cfg: dict) -> int:
    """Bytes of one compressed key of one KV head in one layer."""
    return cfg["head_dim"] * BF16


def index_bytes_per_token(cfg: dict) -> float:
    """Bytes of compressed keys a cached token, over the sparse layers."""
    return counts(cfg)["B"] * cfg["num_key_value_heads"] \
        * index_row_bytes(cfg) / cfg["sparse"]["stride"]


def keys_attended(cfg: dict, first: int, count: int) -> int:
    """Keys the queries at positions [first, first + count) attend, by the
    selection's own rule: all n = t + 1 visible ones up to dense_len, then
    topk blocks of which the query's own is part full."""
    sp = cfg["sparse"]
    total = 0
    for t in range(first, first + count):
        n = t + 1
        total += n if n <= sp["dense_len"] else \
            (sp["topk"] - 1) * sp["block"] + t % sp["block"] + 1
    return total


def prefill_flops(cfg: dict, first: int, count: int) -> float:
    """Multiply-adds x 2 of `sparse_prefill` in ONE layer for the real
    tokens of a chunk at [first, first + count): q k and p v over the keys
    each row attends, every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * keys_attended(cfg, first, count)


def prefill_bytes(cfg: dict, first: int, count: int) -> float:
    """Least HBM bytes of `sparse_prefill` in ONE layer for that chunk: q
    read and the output written once, the context's K and V read once."""
    hd = cfg["head_dim"]
    return BF16 * hd * (2 * count * cfg["num_attention_heads"]
                        + 2 * (first + count) * cfg["num_key_value_heads"])


def param_count(cfg: dict) -> int:
    """Parameters held here, from the shapes: what the deployment states."""
    d, c, hd = cfg["hidden_size"], counts(cfg), cfg["head_dim"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lightning = 4 * d * heads * hd + heads * hd * d + 3 * hd
    sparse = d * heads * 2 * hd + 2 * d * kvh * hd + heads * hd * d + 2 * hd
    mlp = 3 * d * cfg["intermediate_size"]
    norms = (2 * cfg["layers_run"] + 1) * d
    return c["M"] * lightning + c["B"] * sparse + c["D"] * mlp + norms \
        + 2 * cfg["vocab_size"] * d


def decode_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 of one decoded token through the layers as run,
    without the attention over the cache."""
    d, c, hd = cfg["hidden_size"], counts(cfg), cfg["head_dim"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lightning = 5 * d * heads * hd + 2 * heads * hd * hd
    sparse = 3 * d * heads * hd + 2 * d * kvh * hd
    mlp = 3 * d * cfg["intermediate_size"]
    return 2.0 * (c["M"] * lightning + c["B"] * sparse + c["D"] * mlp
                  + d * cfg["vocab_size"])
