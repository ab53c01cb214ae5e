"""The least bytes and operations of the three Kimi Delta Attention kernels
(`kda_step`, `kda_terms`, `kda_scan`: the program's ops/kda.py), for the
readers `flood_kda_*`; the sizes come from the cell's family
(`kda_sizes(cfg)` = (heads, key_dim, value_dim), `counts(cfg)["K"]` layers).
Counted here, with the benchmark: it does not import what it measures.

Where the family has no `kda_sizes` (another model's cell) or the program no
such op (the parent of the PR that brought them), the readers read nothing
and the line leaves their metrics out.
"""

from __future__ import annotations

F32 = 4
# positions a chunk of the program's scan holds (ops/kda.py CHUNK)
SCAN_CHUNK = 64


def step_bytes(cfg: dict, family) -> int:
    """Least HBM bytes `kda_step` moves for ONE decoding slot and step over
    the model's Kimi Delta Attention layers: the float32 state read and
    written. (q, k, v, the decay and beta are under 1% of it.)"""
    return family.counts(cfg)["K"] * 2 * family.ssm_state_bytes(cfg)


def scan_flops_per_token(cfg: dict, family) -> float:
    """Multiply-adds x 2 a real token of the chunk terms and the carry in
    ONE layer, as the mathematics needs them (float32; the MXU's passes are
    the kernel's to pay). Terms, a head and chunk of C rows: the two (C, C)
    sums over the lower triangle (C^2 / 2 pairs of 2 x key_dim each, q k
    and k k), the unit-lower inverse as a triangular solve (C^3 / 3), T
    against beta exp(G) K and beta V (C^2 / 2 x (key_dim + value_dim) each
    way). Carry: w S, (q exp(G)) S and (k exp(G_C - G))^T U against the
    (key_dim, value_dim) state, and the chunk's lower triangle against U."""
    heads, dk, dv = family.kda_sizes(cfg)
    c = SCAN_CHUNK
    terms = 2 * c * dk + c * c / 3 + c * (dk + dv)
    carry = 3 * dk * dv + c * dv / 2
    return 2.0 * heads * (terms + carry)


def scan_bytes_per_token(cfg: dict, family) -> int:
    """Least HBM bytes a real token of `kda_terms` + `kda_scan` in ONE
    layer: q, k, v, g read once (float32, as the kernels take them) and the
    output row written once; the six terms between the two kernels are the
    program's choice and not counted."""
    heads, dk, dv = family.kda_sizes(cfg)
    return F32 * heads * (3 * dk + 2 * dv)
