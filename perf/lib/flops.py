"""Operations and bytes the algorithms need, from shapes alone.

The yardstick's own arithmetic (the program has a copy in
`ddp_practice_tpu/utils/flops.py`; a PR to the program cannot move these).
Matrix multiplications only, 2*M*N*K each; elementwise work and
normalisations are left out (under 2% at these sizes), so utilisations read
slightly low. Training = 3 x forward (backward is two matmuls per forward
matmul); recomputed operations are not counted.
"""

from __future__ import annotations


def vit_forward_flops_per_image(cfg: dict) -> float:
    """ViT as run: patch-embed conv as a matmul, `depth` pre-LN blocks
    (qkv + out projections 8 s d^2, scores + weighted sum 4 s^2 d, MLP
    4 s d m), mean-pool, dense head. `s` is the number of patches: the
    program pools instead of using a class token."""
    side = cfg["image_size"] // cfg["patch_size"]
    s = side * side
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    embed = 2.0 * s * d * cfg["patch_size"] ** 2 * cfg["num_channels"]
    block = 8.0 * s * d * d + 4.0 * s * s * d + 4.0 * s * d * m
    head = 2.0 * d * cfg["num_labels"]
    return embed + cfg["num_hidden_layers"] * block + head


def lm_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Decoder LM as run, per token of a `seq_len` sequence: per layer
    8 d^2 (qkv + out) + 4 d m (MLP) + causal attention 4 s d / 2 (a query
    sees s/2 keys on average), plus the 2 d V head. The embedding lookup is
    a gather."""
    d, m = cfg["n_embd"], cfg["n_inner"]
    attn = 4.0 * seq_len * d * 0.5
    return cfg["n_layer"] * (8.0 * d * d + 4.0 * d * m + attn) \
        + 2.0 * d * cfg["vocab_size"]


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """K and V of one token over all layers, in the served type (bf16)."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_el


def paged_decode_least_bytes(kv_bytes_a_token: float,
                             qo_bytes_a_slot_step: float,
                             live_tokens: float, slot_steps: float) -> float:
    """Bytes the paged decode attention of ALL layers has to move for
    `slot_steps` (slot, step) pairs that together attend `live_tokens`
    cached tokens: K and V of every live token once per step, plus one q
    row in and one out row back per slot-step per layer (the two sizes come
    from the configuration's family). Live tokens, not allocated pages, so
    a kernel cannot read over 100% of its roofline by walking fewer bytes
    than this."""
    return live_tokens * kv_bytes_a_token \
        + slot_steps * qo_bytes_a_slot_step
