"""A SmallThinker configuration file (an attention layer a layer, global
with no positional embedding where `sliding_window_layout[i]` is 0 and a
window of `sliding_window_size` with rotary where it is 1; ReGLU experts
after every attention, routed on the attention's normed input), read for the
harness.

Everything that knows the KEYS of a `smallthinker` configuration is here,
found by the file's `family`: the options of the program's
`create_model("smallthinker", ...)`, and the bytes and operations of a decode
step and of a prompt's chunk that the `flood_*` readers divide by. Serving
only: no training data.
"""

from __future__ import annotations

BF16 = 2


def prepare(cfg: dict) -> None:
    """Nothing to arrange: `program_model` is in the program's registry."""


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


def mixers(cfg: dict) -> str:
    """One letter a layer as run: '*' a global layer, 'W' a window layer,
    by the published `sliding_window_layout` of `layers_published`."""
    if len(cfg["layers_published"]) != cfg["layers_run"]:
        raise ValueError("layers_published lists the layers_run layers")
    for i in cfg["layers_published"]:
        if cfg["rope_layout"][i] != cfg["sliding_window_layout"][i]:
            raise ValueError(
                f"layer {i}: the program rotates in the window layers and "
                "in no other")
    return "".join("W" if cfg["sliding_window_layout"][i] else "*"
                   for i in cfg["layers_published"])


def counts(cfg: dict) -> dict:
    """Layers of each kind; every layer has an expert layer besides."""
    m = mixers(cfg)
    return {"*": m.count("*"), "W": m.count("W"), "R": len(m)}


def model_options(cfg: dict) -> dict:
    """Keyword arguments of the program's `create_model` for serving: a
    layer is two residual sub-layers, its attention then the experts 'R'."""
    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["rope_scaling"] is not None:
        raise ValueError(
            "the program runs a softmax router with renormalised picks, an "
            "untied head and plain rotary: this file asks for another")
    return {
        "pattern": "".join(m + "R" for m in mixers(cfg)),
        "vocab_size": cfg["vocab_size"],
        "hidden_dim": cfg["hidden_size"],
        "max_len": cfg["max_position_embeddings"],
        "num_heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "num_experts": cfg["moe_num_primary_experts"],
        "experts_held": cfg["moe_num_primary_experts"],
        "top_k": cfg["moe_num_active_primary_experts"],
        "expert_dim": cfg["moe_ffn_hidden_size"],
        "norm_eps": cfg["rms_norm_eps"],
    }


def decode_bytes(cfg: dict) -> tuple:
    """(K and V bytes a cached token, q + out bytes a slot and step), over
    the GLOBAL attention layers alone, in the served type (bf16): what the
    kernel named `paged_decode` reads (a window layer's walk is another op,
    `window_walk`, charged the pages the program counted)."""
    n, hd = counts(cfg)["*"], cfg["head_dim"]
    kv = 2 * cfg["num_key_value_heads"] * hd * BF16 * n
    q_and_out = 2 * cfg["num_attention_heads"] * hd * BF16 * n
    return kv, q_and_out


def window_q_and_out_bytes(cfg: dict) -> int:
    """q + out bytes a slot and step over the WINDOW layers."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * BF16 \
        * counts(cfg)["W"]


def walk_page_bytes(cfg: dict, page: int) -> int:
    """Bytes `window_walk` reads for ONE page of one KV head in one layer:
    the page's K and V rows of that head's lanes (32,768 B at 64 x 128)."""
    return 2 * page * cfg["head_dim"] * BF16


def expert_bytes(cfg: dict) -> int:
    """Bytes of ONE routed expert's three matrices (bf16): what
    `moe_gmm_glu` streams for every expert that has a row (11,796,480 B)."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"] * BF16


def keys_attended(cfg: dict, first: int, count: int) -> int:
    """Keys the queries at positions [first, first + count) attend, summed
    over ALL the layers as run: min(t + 1, window) in a window layer, t + 1
    in a global one."""
    c, w = counts(cfg), cfg["sliding_window_size"]
    return sum(c["W"] * min(t + 1, w) + c["*"] * (t + 1)
               for t in range(first, first + count))


def prefill_flops(cfg: dict, first: int, count: int) -> float:
    """Multiply-adds x 2 of `window_prefill` over all the layers for the
    real tokens of a chunk at [first, first + count): q k and p v over the
    keys each row attends, every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * keys_attended(cfg, first, count)


def prefill_bytes(cfg: dict, first: int, count: int) -> float:
    """Least HBM bytes of `window_prefill` over all the layers for that
    chunk: q read and the output written once a layer, the K and V some row
    attends read once (the whole context in a global layer, the window
    behind the chunk's first row and the chunk in a window layer)."""
    c, hd = counts(cfg), cfg["head_dim"]
    end = first + count
    near = end - max(0, first - cfg["sliding_window_size"] + 1)
    return BF16 * hd * (
        2 * count * cfg["num_attention_heads"] * (c["*"] + c["W"])
        + 2 * cfg["num_key_value_heads"] * (c["*"] * end + c["W"] * near))


def param_count(cfg: dict) -> int:
    """Parameters held here, from the shapes: what the deployment states."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * d * heads * hd + 2 * d * kvh * hd
    moe = d * cfg["moe_num_primary_experts"] \
        + cfg["moe_num_primary_experts"] * 3 * d * cfg["moe_ffn_hidden_size"]
    norms = (2 * cfg["layers_run"] + 1) * d
    return cfg["layers_run"] * (attn + moe) + norms \
        + 2 * cfg["vocab_size"] * d

