"""Mixture-of-Experts: top-k routed MLP with expert parallelism.

Absent from the reference ("Expert parallel (EP/MoE) — No", SURVEY §2.3).
TPU-first construction (the GShard/Switch recipe, which was designed FOR
TPUs): routing is dense one-hot linear algebra — no gather/scatter, no
dynamic shapes, everything lands on the MXU as batched einsums —

    logits  (G,T,E) -> top-k assignment + position-in-expert via cumsum
    dispatch (G,T,E,C) one-hot   combine (G,T,E,C) gate-weighted
    expert_in  = einsum(dispatch, x)      -> (E, G, C, D)
    expert_out = batched expert MLP       -> (E, G, C, D)
    y          = einsum(combine, expert_out) -> (G, T, D)

Expert weights are stacked on a leading E dim sharded over the 'expert'
mesh axis, and the (E, ...) activation tensors carry a
`with_sharding_constraint` to the same axis — XLA lowers the layout switch
(tokens grouped-by-expert <-> experts-by-token) into all-to-alls over ICI,
which is exactly the manual NCCL a2a pattern of GPU MoE frameworks, here
derived from shardings. Capacity overflow drops tokens (residual passes
them through untouched); a Switch-style load-balance auxiliary loss keeps
routing uniform.
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.utils import backend


def _constrain(x, spec):
    """Pin a layout on the current framework mesh (no-op without a mesh —
    e.g. plain single-device unit tests). Uses NamedSharding, which binds
    under jit without a jax context mesh."""
    from ddp_practice_tpu.parallel.ring import get_current_mesh

    mesh = get_current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))
    )


def topk_choices(
    router_logits: jnp.ndarray,  # (..., E) fp32
    *,
    k: int,
    routing_bias: Optional[jnp.ndarray] = None,  # (E,) selection-only
):
    """Top-k expert selection without the capacity machinery.

    Returns (choices (..., k) int32, combine gates (..., k) fp32
    renormalized over each token's k picks, aux_loss, demand (E,)).
    The dropless sorted path (below) consumes this directly; the
    capacity-dropping einsum path keeps `top_k_gating`, whose combine
    weights renormalize over the KEPT experts instead. `routing_bias`
    biases selection only, never the combine weights (the DeepSeek-V3
    aux-free balancing scheme, same contract as top_k_gating)."""
    e = router_logits.shape[-1]
    logits = router_logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    sel = logits if routing_bias is None else (
        logits + routing_bias.astype(jnp.float32)
    )
    _, choices = jax.lax.top_k(sel, k)                    # (..., k)
    cgates = jnp.take_along_axis(gates, choices, axis=-1)  # (..., k)
    cgates = cgates / jnp.maximum(
        jnp.sum(cgates, axis=-1, keepdims=True), 1e-9
    )
    lead = tuple(range(router_logits.ndim - 1))
    onehot = jax.nn.one_hot(choices, e, dtype=jnp.float32)  # (..., k, E)
    demand = jnp.mean(jnp.sum(onehot, axis=-2), axis=lead) / k
    # Switch-style load-balance loss (eq. 4): pre-drop first-choice
    # fractions x mean router mass — identical to top_k_gating's
    frac = jnp.mean(onehot[..., 0, :], axis=lead)
    prob = jnp.mean(gates, axis=lead)
    aux = e * jnp.sum(frac * prob)
    return choices, cgates, aux, demand


def top_k_routing(
    router_logits: jnp.ndarray,  # (G, T, E) fp32
    *,
    k: int,
    capacity: int,
    routing_bias: Optional[jnp.ndarray] = None,  # (E,) selection-only
):
    """Capacity-constrained top-k routing as INDEX tensors.

    Returns (choices (G,T,k) int32, positions (G,T,k) int32 — each
    token's buffer position within its chosen expert, keeps (G,T,k)
    fp32 — 0 where the token overflowed capacity, gsel (G,T,E) fp32 —
    router gates renormalized over each token's kept experts, aux_loss,
    demand (E,)).

    Iterative top-k: pick the best expert per token, compute each
    token's position within that expert's buffer by a cumsum over the
    token dim, drop tokens past `capacity`, mask the chosen expert out,
    repeat. All dense ops — compiles to static-shape TPU code. Both
    expert-compute layouts derive from these indices: the einsum path
    expands them to one-hot dispatch/combine tensors (top_k_gating),
    the gather path consumes them directly.

    `routing_bias` biases SELECTION only (which experts a token goes
    to), never the combine weights — the aux-free online balancing
    signal (MoEMlp maintains it; the DeepSeek-V3 scheme). `demand` is
    the (E,) pre-drop share of the k*T assignment slots each expert
    attracted — the overload signal the bias update consumes.
    """
    g, t, e = router_logits.shape
    gates = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    if routing_bias is not None:
        sel = jax.nn.softmax(
            router_logits.astype(jnp.float32)
            + routing_bias.astype(jnp.float32), axis=-1
        )
    else:
        sel = gates

    remaining = sel
    fill = jnp.zeros((g, e), jnp.float32)  # tokens already claimed per expert
    first_choice = None
    demand = jnp.zeros((e,), jnp.float32)
    kept_expert = jnp.zeros((g, t, e), jnp.float32)
    choices, positions, keeps = [], [], []
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)              # (G, T)
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # (G, T, E)
        demand = demand + jnp.mean(onehot, axis=(0, 1)) / k
        if first_choice is None:
            first_choice = onehot
        pos = (
            jnp.cumsum(onehot, axis=1) - onehot + fill[:, None, :]
        )  # (G, T, E): position within expert buffer
        pos_tok = jnp.sum(pos * onehot, axis=-1)             # (G, T)
        keep = (pos_tok < capacity).astype(jnp.float32)      # (G, T)
        choices.append(choice.astype(jnp.int32))
        positions.append(jnp.minimum(pos_tok, capacity - 1).astype(jnp.int32))
        keeps.append(keep)
        kept_expert = kept_expert + onehot * keep[..., None]
        fill = fill + jnp.sum(onehot * keep[..., None], axis=1)
        remaining = remaining * (1.0 - onehot)

    # per-slot combine weight: router gates renormalized over each token's
    # kept experts (tokens dropped everywhere get an all-zero combine row —
    # the residual connection carries them through unchanged)
    gsel = gates * kept_expert
    gsel = gsel / jnp.maximum(jnp.sum(gsel, axis=-1, keepdims=True), 1e-9)

    # Switch-style load-balance loss: E * sum_e fraction_e * prob_e, with
    # frac from the PRE-DROP first-choice assignments (Switch eq. 4). An
    # earlier version used the post-drop dispatched counts — self-
    # defeating: an over-capacity expert's fraction saturates at
    # capacity, so the loss could not see (or penalize) overload beyond
    # it, and raising the aux weight made balance WORSE (measured,
    # BENCHMARKS.md round-4 MoE section).
    frac = jnp.mean(first_choice, axis=(0, 1))               # (E,) demand
    prob = jnp.mean(gates, axis=(0, 1))                      # (E,) router mass
    aux = e * jnp.sum(frac * prob)
    return (
        jnp.stack(choices, axis=-1), jnp.stack(positions, axis=-1),
        jnp.stack(keeps, axis=-1), gsel, aux, demand,
    )


def expert_choice_gating(
    router_logits: jnp.ndarray,  # (G, T, E) fp32
    *,
    capacity: int,
):
    """Expert-choice routing (Zhou et al. 2022): experts pick tokens.

    Each expert takes the top-`capacity` tokens of its softmax column,
    so every buffer slot is filled — perfect load balance, zero drops,
    and zero capacity padding BY CONSTRUCTION (executed expert FLOPs ==
    active FLOPs; with capacity k*T/E the compute matches top-k routing
    exactly). No auxiliary loss and no balancing bias are needed; the
    machinery that token-choice requires to fight imbalance simply has
    nothing to do. Combine weights are the raw router gates at the
    picked (token, expert) pairs (the paper's formulation — tokens
    chosen by several experts sum their contributions; tokens chosen by
    none ride the residual).

    Returns (dispatch (G,T,E,C), combine (G,T,E,C), uncovered — the
    fraction of tokens no expert picked, the quality-relevant analogue
    of token-choice's drop rate).

    Caveat (documented, inherent to EC): a token's routing depends on
    which OTHER tokens in its routing group compete for the same
    experts — for causal LMs that lets training-time routing (only
    routing, never attention) see the future. Mixture-of-Depths
    (Raposo et al. 2024) discusses the same property and its inference
    predictors; scope the competition with routing groups and prefer
    token-choice when strict train-time causality matters.
    """
    g, t, e = router_logits.shape
    gates = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    scores = jnp.swapaxes(gates, 1, 2)                    # (G, E, T)
    _, idx = jax.lax.top_k(scores, capacity)              # (G, E, C)
    onehot = jax.nn.one_hot(idx, t, dtype=jnp.float32)    # (G, E, C, T)
    dispatch = jnp.transpose(onehot, (0, 3, 1, 2))        # (G, T, E, C)
    combine = dispatch * gates[..., None]
    covered = jnp.clip(jnp.sum(dispatch, axis=(2, 3)), 0.0, 1.0)
    uncovered = 1.0 - jnp.mean(covered)
    return dispatch, combine, uncovered


def top_k_gating(
    router_logits: jnp.ndarray,  # (G, T, E) fp32
    *,
    k: int,
    capacity: int,
    routing_bias: Optional[jnp.ndarray] = None,  # (E,) selection-only
):
    """Return (dispatch (G,T,E,C), combine (G,T,E,C), aux_loss, demand).

    The one-hot expansion of top_k_routing — the GShard layout the
    einsum path and its expert-sharded all-to-alls contract over."""
    choices, positions, keeps, gsel, aux, demand = top_k_routing(
        router_logits, k=k, capacity=capacity, routing_bias=routing_bias,
    )
    e = router_logits.shape[-1]
    dispatch = jnp.zeros(
        router_logits.shape[:2] + (e, capacity), jnp.float32
    )
    for j in range(choices.shape[-1]):
        onehot = jax.nn.one_hot(choices[..., j], e, dtype=jnp.float32)
        pos_oh = jax.nn.one_hot(
            positions[..., j], capacity, dtype=jnp.float32
        )
        dispatch = dispatch + jnp.einsum(
            "gte,gtc->gtec", onehot * keeps[..., j, None], pos_oh
        )
    combine = dispatch * gsel[..., None]
    return dispatch, combine, aux, demand


def _assignment_permutation(choices_flat: jnp.ndarray, e: int):
    """Static-shape counting sort of the (N*k,) expert assignments.

    Returns (counts (E,) int32, dest (N*k,) int32, inv (N*k,) int32):
    assignment a lands at row dest[a] of the expert-sorted buffer, and
    sorted row r holds assignment inv[r]. Pure cumsum arithmetic — no
    lax.sort, no scatter with duplicate indices (inv's scatter writes a
    permutation, which XLA lowers as a gather of the inverse)."""
    nk = choices_flat.shape[0]
    onehot = jax.nn.one_hot(choices_flat, e, dtype=jnp.int32)   # (Nk, E)
    counts = jnp.sum(onehot, axis=0)                            # (E,)
    offsets = jnp.cumsum(counts) - counts                       # exclusive
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot         # (Nk, E)
    dest = (
        jnp.sum(pos_in_expert * onehot, axis=-1) + offsets[choices_flat]
    ).astype(jnp.int32)
    # inv from ONE stable sort: counting-sort order IS (expert, arrival)
    # order, which a stable sort by expert id reproduces exactly
    _, inv = jax.lax.sort_key_val(
        choices_flat, jnp.arange(nk, dtype=jnp.int32)
    )
    return counts, dest, inv


def _slot_tables(choices, positions, keeps, e: int, capacity: int):
    """Invert the (token -> slot) routing into per-slot lookup tables.

    Returns (slot_token (G, E*C) int32, slot_round (G, E*C) int32,
    slot_mask (G, E*C) fp32, dest (G, T, k) int32 — each assignment's
    flat slot, E*C for dropped). Dropped assignments scatter into a
    spare trailing column so they can never collide with a live slot.
    The scatters move 3*k*T int32-sized elements per group — index
    metadata, not rows; the row traffic all rides gathers (the point
    of this path)."""
    g, t, k = choices.shape
    ec = e * capacity
    dest = choices * capacity + positions                  # (G, T, k)
    dest = jnp.where(keeps > 0, dest, ec).astype(jnp.int32)
    gi = jnp.arange(g, dtype=jnp.int32)[:, None, None]
    ti = jnp.broadcast_to(
        jnp.arange(t, dtype=jnp.int32)[None, :, None], (g, t, k)
    )
    ri = jnp.broadcast_to(
        jnp.arange(k, dtype=jnp.int32)[None, None, :], (g, t, k)
    )
    gi = jnp.broadcast_to(gi, (g, t, k))
    slot_token = jnp.zeros((g, ec + 1), jnp.int32).at[gi, dest].set(
        ti, mode="drop"
    )[:, :ec]
    slot_round = jnp.zeros((g, ec + 1), jnp.int32).at[gi, dest].set(
        ri, mode="drop"
    )[:, :ec]
    slot_mask = jnp.zeros((g, ec + 1), jnp.float32).at[gi, dest].set(
        1.0, mode="drop"
    )[:, :ec]
    return slot_token, slot_round, slot_mask, dest


@jax.custom_vjp
def _dispatch_gather(x, slot_token, slot_mask, dest):
    """xin[g, s] = x[g, slot_token[g, s]] * slot_mask[g, s].

    Forward is one batched row gather over the token dim; the custom
    backward is k row gathers (dx[g, t] = sum_j dxin[g, dest[g, t, j]],
    with dropped assignments pointing at the masked spare slot) instead
    of the scatter-add autodiff would emit."""
    del dest
    xin = jnp.take_along_axis(x, slot_token[..., None], axis=1)
    return xin * slot_mask[..., None].astype(xin.dtype)


def _dispatch_gather_fwd(x, slot_token, slot_mask, dest):
    return _dispatch_gather(x, slot_token, slot_mask, dest), dest


def _dispatch_gather_bwd(dest, g_out):
    # pad a zero spare slot so dropped assignments (dest == E*C) read 0
    gz = jnp.pad(g_out, ((0, 0), (0, 1), (0, 0)))
    k = dest.shape[-1]
    dx = jnp.take_along_axis(gz, dest[..., 0, None], axis=1)
    for j in range(1, k):
        dx = dx + jnp.take_along_axis(gz, dest[..., j, None], axis=1)
    return dx, None, None, None


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def _combine_gather(out, w, dest, slot_token, slot_round, slot_mask):
    """y[g, t] = sum_j w[g, t, j] * out[g, dest[g, t, j]].

    Gather-only in both directions: the backward for `out` reads
    gy rows back through the slot tables (d out[g, s] =
    gy[g, slot_token[g, s]] * w[g, slot_token, slot_round] * mask) and
    the backward for `w` is k gathers + row dots."""
    del slot_token, slot_round, slot_mask
    k = dest.shape[-1]
    oz = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))
    y = jnp.take_along_axis(oz, dest[..., 0, None], axis=1) * (
        w[..., 0, None].astype(out.dtype)
    )
    for j in range(1, k):
        y = y + jnp.take_along_axis(oz, dest[..., j, None], axis=1) * (
            w[..., j, None].astype(out.dtype)
        )
    return y


def _combine_gather_fwd(out, w, dest, slot_token, slot_round, slot_mask):
    y = _combine_gather(out, w, dest, slot_token, slot_round, slot_mask)
    return y, (out, w, dest, slot_token, slot_round, slot_mask)


def _combine_gather_bwd(res, gy):
    out, w, dest, slot_token, slot_round, slot_mask = res
    k = dest.shape[-1]
    # d out: route each slot back to its token's cotangent row, scaled
    # by that slot's combine weight (pure indexing of residuals)
    w_slot = jnp.take_along_axis(
        w.reshape(w.shape[0], -1),
        (slot_token * k + slot_round), axis=1,
    ) * slot_mask                                           # (G, E*C)
    dout = jnp.take_along_axis(gy, slot_token[..., None], axis=1) * (
        w_slot[..., None].astype(gy.dtype)
    )
    oz = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))
    dw = jnp.stack(
        [
            jnp.sum(
                gy * jnp.take_along_axis(oz, dest[..., j, None], axis=1),
                axis=-1,
            )
            for j in range(k)
        ],
        axis=-1,
    ).astype(w.dtype)
    return dout, dw, None, None, None, None


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


# largest tile <= target that divides dim exactly — megablox rejects
# non-dividing m tiles, and small test shapes would otherwise reject the
# tuned production tiles (one definition, shared with the fused encoder)
from ddp_practice_tpu.ops.fused_encoder import _fit_tile  # noqa: E402


def _gmm_tiling(m: int, k: int, n: int):
    """v5e-tuned megablox tiling for the sorted path's grouped matmuls.

    The megablox default (128, 128, 128) ran the lm_moe shapes at ~11
    TFLOP/s — each tiny k-tile re-streams operands. Full-contraction k
    tiles with 512-wide m/n tiles measured 4-6x faster
    (experiments/gmm_tune.py: (m=32k, k=768, n=3072) 70 TF/s at
    (512, 768, 512); (m=32k, k=3072, n=768) 42 TF/s at
    (512, 3072, 768) — both within ~2% of the dense-matmul rate of the
    same FLOPs). Keyed by each CALL's effective dims, so forward and
    the two backward directions each get their own shape's optimum.

    The n tile then halves until the kernel's working set — lhs, rhs and
    out tiles double-buffered in bf16 plus the fp32 accumulator — fits
    the 16 MB scoped-VMEM default with room for the compiler's own
    buffers: jax 0.9's compiler refuses (512, 3072, 768) at 18.58 MB
    (tests/test_tpu_compile.py; the 42 TF/s figure above predates it and
    is not re-measured). The full-contraction k tile is what the tuning
    found to matter, so it is the last to go."""
    tm, tk = _fit_tile(m, 512), min(k, 3072)
    tn = _fit_tile(n, n if n <= 768 else 512)
    while (4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn > 14 * 2**20
           and tn % 256 == 0):
        tn //= 2
    return tm, tk, tn


def _mb_gmm(lhs, rhs, gs, *, transpose_rhs: bool, interpret: bool):
    # from-import of the SUBMODULE path: the package __init__ exports a
    # custom_vjp FUNCTION named gmm that shadows the gmm submodule, so
    # `megablox.gmm` attribute access raises — and tgmm is not
    # re-exported at all
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as raw_gmm

    k_dim = rhs.shape[2] if transpose_rhs else rhs.shape[1]
    n_dim = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = _gmm_tiling(lhs.shape[0], k_dim, n_dim)
    return raw_gmm(
        lhs, rhs, gs, lhs.dtype, tiling, None, None, transpose_rhs,
        interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, group_sizes, interpret):
    """Differentiable grouped matmul over expert-sorted rows.

    A thin re-wrap of megablox gmm/tgmm (jax.experimental.pallas)
    ONLY so each autodiff direction picks its own tuned tiling — the
    stock jax wrapper threads one tiling through forward, grad-lhs,
    and tgmm, and no single tuple is good for all three shapes (the
    measured spread is 4x; _gmm_tiling)."""
    return _mb_gmm(lhs, rhs, group_sizes, transpose_rhs=False,
                   interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, interpret):
    out = _mb_gmm(lhs, rhs, group_sizes, transpose_rhs=False,
                  interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    lhs, rhs, gs = res
    dlhs = _mb_gmm(g, rhs, gs, transpose_rhs=True, interpret=interpret)
    # dW: tgmm((k, m), (m, n)) -> (e, k, n). tgmm's tiling is
    # (contraction m, k, n). Measured (experiments/gmm_tune.py): small-k
    # dW (w_in-like) peaks at (512, k, 512) = 51 TF/s and larger
    # contraction tiles fail to compile there; wide-k dW (w_out-like)
    # peaks at (2048, 1024, n) = 39 TF/s
    m_dim = lhs.shape[0]
    if lhs.shape[1] <= 1024:
        tiling = (
            _fit_tile(m_dim, 512), lhs.shape[1],
            _fit_tile(g.shape[1], 512),
        )
    else:
        tiling = (
            _fit_tile(m_dim, 2048), 1024, _fit_tile(g.shape[1], 768),
        )
    drhs = tgmm(
        lhs.swapaxes(0, 1), g, gs, rhs.dtype, tiling, None,
        rhs.shape[0], interpret=interpret,
    )
    return dlhs, drhs, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.custom_vjp
def _dispatch_rows(xf, tok, dest_nk):
    """Expert-sort gather: row r of the output is token tok[r]'s vector.

    Custom VJP so NEITHER direction is a TPU scatter: the forward is a
    row gather, and the cotangent of token n is the sum of its k sorted
    rows — dest_nk (N, k) holds exactly those row ids, so the backward
    is k gathers + adds instead of a 2N-way scatter-add."""
    del dest_nk
    return xf[tok]


def _dispatch_rows_fwd(xf, tok, dest_nk):
    return xf[tok], (tok, dest_nk)


def _dispatch_rows_bwd(res, g):
    tok, dest_nk = res
    k = dest_nk.shape[1]
    dxf = g[dest_nk[:, 0]]
    for j in range(1, k):
        dxf = dxf + g[dest_nk[:, j]]
    return dxf, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(out, cgates, tok, dest_nk, inv):
    """Weighted un-sort: y[n] = sum_j cgates[n, j] * out[dest_nk[n, j]].

    Forward is k row gathers + fma. Backward stays gather-only too:
    d out[r] = gy[tok[r]] * cgates.flat[inv[r]] (row gather x scalar),
    d cgates[n, j] = <gy[n], out[dest_nk[n, j]]> (gather + rowwise dot).
    """
    del tok, inv
    k = dest_nk.shape[1]
    y = out[dest_nk[:, 0]] * cgates[:, 0, None]
    for j in range(1, k):
        y = y + out[dest_nk[:, j]] * cgates[:, j, None]
    return y


def _combine_rows_fwd(out, cgates, tok, dest_nk, inv):
    return _combine_rows(out, cgates, tok, dest_nk, inv), (
        out, cgates, tok, dest_nk, inv,
    )


def _combine_rows_bwd(res, gy):
    out, cgates, tok, dest_nk, inv = res
    gate_sorted = cgates.reshape(-1)[inv]                       # (Nk,)
    dout = gy[tok] * gate_sorted[:, None].astype(gy.dtype)
    dc = [
        jnp.sum(gy * out[dest_nk[:, j]], axis=-1)
        for j in range(dest_nk.shape[1])
    ]
    dcgates = jnp.stack(dc, axis=-1).astype(cgates.dtype)
    return dout, dcgates, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@jax.custom_vjp
def _bias_rows(b, sorted_expert, onehot_sorted):
    """Per-row expert bias gather b[sorted_expert] with a dense-matmul
    backward: db = onehot_sorted^T @ g — an (E, rows) x (rows, F) dot on
    the MXU instead of a rows->E scatter-add."""
    del onehot_sorted
    return b[sorted_expert]


def _bias_rows_fwd(b, sorted_expert, onehot_sorted):
    # zero-size dtype token: custom_vjp residuals must be JAX types
    return b[sorted_expert], (onehot_sorted, jnp.zeros((0,), b.dtype))


def _bias_rows_bwd(res, g):
    onehot_sorted, dtype_token = res
    db = jax.lax.dot_general(
        onehot_sorted.astype(jnp.float32), g.astype(jnp.float32),
        (((0,), (0,)), ((), ())),
    )
    return db.astype(dtype_token.dtype), None, None


_bias_rows.defvjp(_bias_rows_fwd, _bias_rows_bwd)


class MoEMlp(nn.Module):
    """Expert-parallel MLP: drop-in for a dense transformer MLP block."""

    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    mlp_dim: int = 768
    aux_loss_weight: float = 0.01
    # aux-free online balancing (the DeepSeek-V3 scheme): a NON-LEARNED
    # per-expert bias nudges SELECTION (never combine weights) against
    # measured overload each training step: b -= rate * sign(demand -
    # 1/E). Unlike the gradient aux loss, it acts on the argmax directly,
    # so it balances even when hidden states share a dominant common-mode
    # direction (measured: the aux loss alone plateaued at ~10% drops and
    # OSCILLATED when strengthened — BENCHMARKS.md round-4 MoE section).
    # Lives in "batch_stats" so it rides the existing non-param state
    # plumbing (train/steps.py, checkpointing). 0 disables.
    bias_update_rate: float = 0.02
    # tokens per routing group. 0 = one group per leading-dim row (the
    # whole sequence — the GShard default). Smaller groups cut the
    # dispatch/combine einsum cost, which is O(group_size) PER TOKEN
    # (the one-hot contracts t x (E*C) with C ∝ group_size): at lm_moe
    # shape, group 2048 -> 256 is ~8x less dispatch matmul. The price is
    # capacity granularity: per-group demand varies more, so pair small
    # groups with the strided interleave below and a measured capacity
    # factor (BENCHMARKS.md round-4 MoE section).
    group_size: int = 0
    # interleave-stride the sequence into groups (with n_sub = seq /
    # group_size groups per sequence, group j takes tokens {j, j+n_sub,
    # j+2*n_sub, ...}): adjacent tokens — which share local context
    # and crowd the same experts — land in DIFFERENT groups, so
    # per-group demand concentrates less than contiguous chunks at the
    # same size. Shard-safe: the transpose is within one sequence
    # (leading dim untouched), so dp sharding never moves.
    group_stride: bool = True
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    expert_axis: Optional[str] = MeshConfig.AXIS_EXPERT
    # expert-compute implementation:
    #   "einsum" — the GShard dense one-hot dispatch/combine einsums with
    #     capacity dropping: shardable over the 'expert' mesh axis (the
    #     sharding constraints lower to all-to-alls), the multichip path.
    #   "gather" — same capacity/grouping semantics, but dispatch and
    #     combine are index GATHERS through per-slot lookup tables
    #     (custom VJPs keep the backward gather-only too) while the
    #     expert MLP stays the dense batched einsum. Measured SLOWER
    #     than einsum at the lm_moe bench shape (31.9% vs 37.7% MFU):
    #     XLA lowers a TPU row gather at ~0.25-0.5 ms per (32k, 768)
    #     pass and this path needs ~8 per layer, while the one-hot
    #     dispatch matmuls it replaces cost ~1 ms/layer once routing
    #     groups shrink them. Kept for the regime that inverts the
    #     tradeoff (capacity >> group_size, where one-hot tensors
    #     explode quadratically but gathers stay linear).
    #   "sorted" — dropless counting-sort + grouped matmul (megablox gmm
    #     Pallas kernels, v5e-tuned tilings): no capacity padding at
    #     all (exactly k*N expert rows). Also measured BELOW einsum —
    #     XLA's dense batched expert einsum reaches ~103-139 TF/s where
    #     gmm peaks at ~70/42 (experiments/gmm_tune.py) — but it is the
    #     only drop-free top-k path, and wins when capacity waste
    #     dominates (high cf or skewed loads).
    #   "auto" (default) — einsum everywhere, by measurement: the
    #     GShard dense-linear-algebra design IS the TPU-native answer
    #     at production shapes (BENCHMARKS.md round-5 MoE section
    #     records the full gather/sorted shootout).
    impl: str = "auto"
    # routing scheme:
    #   "topk" — tokens choose experts (GShard/Switch): the default;
    #     needs the aux loss + balancing bias, pays capacity padding
    #     (cf x active FLOPs executed) and drops overflow tokens.
    #   "expert_choice" — experts choose tokens (expert_choice_gating):
    #     perfect balance, zero drops, zero padding by construction —
    #     executed == active FLOPs at cf 1.0, the TPU-efficiency
    #     choice. Training-time routing sees the whole routing group
    #     (causality caveat in the gating docstring).
    router: str = "topk"

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, decode: bool = False
                 ) -> jnp.ndarray:  # (G, T, D)
        impl = self.impl
        if impl == "auto":
            impl = "einsum"
        elif impl not in ("einsum", "gather", "sorted"):
            raise ValueError(
                f"moe impl {impl!r} (want 'auto'|'einsum'|'gather'|"
                "'sorted')"
            )
        if self.router not in ("topk", "expert_choice"):
            raise ValueError(
                f"moe router {self.router!r} (want 'topk'|'expert_choice')"
            )
        if self.router == "expert_choice" and impl != "einsum":
            raise ValueError(
                "expert_choice routing runs on the einsum path (its "
                "dispatch is already dense and padding-free); pass "
                "impl='auto'/'einsum'"
            )
        if impl == "sorted" and not self.is_initializing():
            return self._sorted(x)
        if impl == "gather" and not self.is_initializing():
            return self._gather(x)
        return self._einsum(x, decode=decode)

    def _group(self, x):
        """Apply the routing-group reshape (see group_size/group_stride);
        returns (grouped x, n_sub)."""
        g0, t0, d = x.shape
        if not 0 < self.group_size < t0:
            return x, 1
        if t0 % self.group_size:
            raise ValueError(
                f"moe group_size {self.group_size} must divide the "
                f"sequence length {t0}"
            )
        n_sub = t0 // self.group_size
        if self.group_stride:
            # (g0, t0, d) -> (g0 * n_sub, group_size, d), group j of
            # a sequence = tokens {j, j + n_sub, ...}
            x = x.reshape(g0, self.group_size, n_sub, d)
            x = jnp.swapaxes(x, 1, 2)
        return x.reshape(g0 * n_sub, self.group_size, d), n_sub

    def _ungroup(self, y, g0, t0, n_sub):
        if n_sub <= 1:
            return y
        d = y.shape[-1]
        if self.group_stride:
            y = y.reshape(g0, n_sub, self.group_size, d)
            y = jnp.swapaxes(y, 1, 2)
        return y.reshape(g0, t0, d)

    def _gather(self, x: jnp.ndarray) -> jnp.ndarray:
        """Capacity-layout expert compute with index-gather glue.

        Identical routing semantics to the einsum path (same groups,
        same capacity drops, same combine weights — pinned by
        tests/test_moe.py equality tests) but the (G,T,E,C) one-hot
        dispatch/combine tensors never exist: per-slot lookup tables
        (_slot_tables) drive row gathers into the (G,E,C,D) buffer and
        back, with custom VJPs that stay gather-only. The expert MLP
        keeps the dense batched einsum — measured ~139 TF/s on v5e,
        2-3x any grouped-matmul kernel at this shape."""
        g0, t0, d = x.shape
        self._warn_oversized_group(t0)
        x, n_sub = self._group(x)
        g, t, _ = x.shape
        e, f, k = self.num_experts, self.mlp_dim, self.top_k
        capacity = max(1, int(self.capacity_factor * k * t / e))
        router = nn.Dense(
            e,
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
            use_bias=False,
            name="router",
        )
        logits = router(x.astype(jnp.float32))               # (G, T, E)
        bias = self._router_bias(e)
        choices, positions, keeps, gsel, aux, demand = top_k_routing(
            logits, k=k, capacity=capacity,
            routing_bias=None if bias is None else bias.value,
        )
        self._update_bias(bias, demand, e)
        self.sow("intermediates", "moe_aux_loss", self.aux_loss_weight * aux)
        routed = jnp.sum(keeps)
        load = jnp.sum(
            jax.nn.one_hot(choices, e, dtype=jnp.float32)
            * keeps[..., None],
            axis=(0, 1, 2),
        )
        self.sow(
            "intermediates", "moe_load_frac",
            load / jnp.maximum(routed, 1.0),
        )
        self.sow(
            "intermediates", "moe_drop_rate",
            1.0 - routed / (k * g * t),
        )

        slot_token, slot_round, slot_mask, dest = _slot_tables(
            choices, positions, keeps, e, capacity
        )
        w_in, b_in, w_out, b_out = self._expert_params(d, e, f)
        cd = self.dtype
        xin = _dispatch_gather(
            x.astype(cd), slot_token, slot_mask.astype(cd), dest
        )                                                    # (G, E*C, D)
        xin = xin.reshape(g, e, capacity, d)
        h = jnp.einsum("gecd,edf->gecf", xin, w_in.astype(cd))
        h = nn.gelu(h + b_in.astype(cd)[None, :, None, :])
        out = jnp.einsum("gecf,efd->gecd", h, w_out.astype(cd))
        out = out + b_out.astype(cd)[None, :, None, :]
        w = jnp.take_along_axis(gsel, choices, axis=-1) * keeps  # (G,T,k)
        y = _combine_gather(
            out.reshape(g, e * capacity, d), w.astype(cd), dest,
            slot_token, slot_round, slot_mask,
        )
        return self._ungroup(y, g0, t0, n_sub).astype(x.dtype)

    def _router_bias(self, e: int):
        """The aux-free balancing bias variable, shared by both paths.

        decode/eval paths may apply without the batch_stats collection
        (generate.py builds variables from params + cache only): route
        with no bias there — selection then follows the raw gates,
        which the aux loss keeps roughly balanced."""
        if self.is_initializing() or self.has_variable(
            "batch_stats", "router_bias"
        ):
            return self.variable(
                "batch_stats", "router_bias",
                lambda: jnp.zeros((e,), jnp.float32),
            )
        return None

    def _update_bias(self, bias, demand, e: int):
        if bias is not None and self.is_mutable_collection(
            "batch_stats"
        ) and self.bias_update_rate > 0.0:
            bias.value = jax.lax.stop_gradient(
                bias.value - self.bias_update_rate
                * jnp.sign(demand - 1.0 / e)
            )

    def _expert_params(self, d: int, e: int, f: int):
        w_in = self.param(
            "expert_w_in",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, d, f),
            self.param_dtype,
        )
        b_in = self.param(
            "expert_b_in", nn.initializers.zeros, (e, f), self.param_dtype
        )
        w_out = self.param(
            "expert_w_out",
            nn.initializers.lecun_normal(batch_axis=(0,)),
            (e, f, d),
            self.param_dtype,
        )
        b_out = self.param(
            "expert_b_out", nn.initializers.zeros, (e, d), self.param_dtype
        )
        return w_in, b_in, w_out, b_out

    def _sorted(self, x: jnp.ndarray) -> jnp.ndarray:
        """Dropless sorted expert compute (single device).

        Tokens flatten to (N, D); the k assignments counting-sort by
        expert (dest by cumsum arithmetic, inv by one stable
        lax.sort_key_val — no scatters); the expert MLP runs as TWO
        grouped matmuls over the ragged (N*k, ·) buffer (megablox gmm —
        jax.experimental.pallas.ops.tpu.megablox, fp32 accumulation);
        combine gathers each token's k rows back with renormalized
        gates. Router health/aux/bias machinery is shared with the
        einsum path; drop rate is exactly 0 by construction.

        group_size/group_stride are deliberately NOT applied here:
        routing groups exist to scope CAPACITY competition (which
        tokens crowd each other out of an expert's buffer), and the
        dropless path has no capacity — per-token top-k choices, and
        therefore the output, demand statistics, and balance-bias
        updates, are identical with or without the group reshape, so
        applying it would only pay the strided transpose's HBM
        traffic for nothing."""
        g0, t0, d = x.shape
        e, f, k = self.num_experts, self.mlp_dim, self.top_k
        n = g0 * t0
        xf = x.reshape(n, d)
        router = nn.Dense(
            e,
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
            use_bias=False,
            name="router",
        )
        logits = router(xf.astype(jnp.float32))              # (N, E)
        bias = self._router_bias(e)
        choices, cgates, aux, demand = topk_choices(
            logits, k=k, routing_bias=None if bias is None else bias.value,
        )
        self._update_bias(bias, demand, e)
        self.sow("intermediates", "moe_aux_loss", self.aux_loss_weight * aux)

        cf = choices.reshape(n * k)
        counts, dest, inv = _assignment_permutation(cf, e)
        dest_nk = dest.reshape(n, k)
        tok = inv // k
        self.sow(
            "intermediates", "moe_load_frac",
            counts.astype(jnp.float32) / (k * n),
        )
        self.sow(
            "intermediates", "moe_drop_rate", jnp.zeros((), jnp.float32)
        )

        w_in, b_in, w_out, b_out = self._expert_params(d, e, f)
        cd = self.dtype
        interpret = not backend.on_tpu()
        sorted_expert = cf[inv]
        onehot_sorted = jax.nn.one_hot(sorted_expert, e, dtype=cd)
        x_sorted = _dispatch_rows(xf.astype(cd), tok, dest_nk)
        h = _grouped_matmul(x_sorted, w_in.astype(cd), counts, interpret)
        h = nn.gelu(h + _bias_rows(b_in.astype(cd), sorted_expert,
                                   onehot_sorted))
        out = _grouped_matmul(h, w_out.astype(cd), counts, interpret)
        out = out + _bias_rows(b_out.astype(cd), sorted_expert,
                               onehot_sorted)
        y = _combine_rows(out, cgates.astype(cd), tok, dest_nk, inv)
        return y.reshape(g0, t0, d).astype(x.dtype)

    def _warn_oversized_group(self, t0: int) -> None:
        """A group larger than the sequence cannot exist; routing falls
        back to whole-sequence, whose capacity behavior differs from
        what the group-tuned capacity factor was calibrated for
        (advisor round 4). Warn, don't raise — and only on the
        TRAINING path (mutable batch_stats, like the router-bias
        update): short inputs are NORMAL in decode/prefill (t0 =
        prompt length or 1 — inference.py drives this module with
        the training group_size) and must stay silent. The training
        signal is a mutable "intermediates" collection (the metric
        sows) — NOT batch_stats, which expert-choice models don't
        create at all."""
        if (self.group_size > t0 and not self.is_initializing()
                and self.is_mutable_collection("intermediates")):
            import warnings

            warnings.warn(
                f"moe group_size {self.group_size} exceeds the sequence "
                f"length {t0}: routing whole-sequence — pass 0 or a "
                "divisor of the sequence length",
                stacklevel=2,
            )

    def _einsum(self, x: jnp.ndarray, *, decode: bool = False
                ) -> jnp.ndarray:
        g0, t0, d = x.shape
        self._warn_oversized_group(t0)
        x, n_sub = self._group(x)
        g, t, d = x.shape
        e, f = self.num_experts, self.mlp_dim
        capacity = max(
            1, int(self.capacity_factor * self.top_k * t / e)
        )

        router = nn.Dense(
            e,
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
            use_bias=False,
            name="router",
        )
        logits = router(x.astype(jnp.float32))               # (G, T, E)
        if self.router == "expert_choice" and not decode:
            # experts pick tokens: full buffers, no aux loss, no
            # balancing bias — the imbalance-fighting machinery has
            # nothing to do (expert_choice_gating docstring). Capacity
            # clamps to the group token count: small groups / few
            # experts make cf*k*T/E exceed T, and an expert cannot
            # pick more tokens than exist.
            dispatch, combine, uncovered = expert_choice_gating(
                logits, capacity=min(capacity, t)
            )
            self.sow(
                "intermediates", "moe_aux_loss", jnp.zeros((), jnp.float32)
            )
            # the quality-relevant analogue of the drop rate: tokens no
            # expert picked (they ride the residual unchanged). Capacity
            # drops are zero by construction; this reports coverage.
            self.sow("intermediates", "moe_drop_rate", uncovered)
        else:
            if self.router == "expert_choice":
                # KV-cache decode: expert choice has no serving story of
                # its own (with T=1 every expert would pick the lone
                # token — E/k the trained compute, different function).
                # Use the standard EC serving approximation: per-token
                # top-k over the gates, capacity = t so nothing drops.
                # Combine with the RAW gates at the picked experts —
                # EC training combines with raw gates, so reusing
                # top_k_gating's renormalized weights would rescale
                # every MoE branch by ~1/(sum of picked gates) at
                # serve time. A train/infer expert-selection mismatch
                # is inherent to EC (Zhou et al. 2022 §3.2 /
                # Mixture-of-Depths §inference discuss predictors);
                # token-choice routing is the option without it.
                dispatch, _combine, _aux, _demand = top_k_gating(
                    logits, k=self.top_k, capacity=t, routing_bias=None,
                )
                gates = jax.nn.softmax(logits.astype(jnp.float32), -1)
                combine = dispatch * gates[..., None]
            else:
                bias = self._router_bias(e)
                dispatch, combine, aux, demand = top_k_gating(
                    logits, k=self.top_k, capacity=capacity,
                    routing_bias=None if bias is None else bias.value,
                )
                self._update_bias(bias, demand, e)
                self.sow(
                    "intermediates", "moe_aux_loss",
                    self.aux_loss_weight * aux,
                )
                # the fraction of the k*T slots lost to capacity drops
                # (diagnostic sows — no "aux_loss" in the name, so they
                # never join the objective; train/steps.py surfaces
                # them as moe_* metrics)
                self.sow(
                    "intermediates", "moe_drop_rate",
                    1.0 - jnp.sum(dispatch) / (self.top_k * g * t),
                )
        # per-expert share of ROUTED tokens — shared router-health sow
        routed = jnp.sum(dispatch)
        self.sow(
            "intermediates", "moe_load_frac",
            jnp.sum(dispatch, axis=(0, 1, 3)) / jnp.maximum(routed, 1.0),
        )

        w_in, b_in, w_out, b_out = self._expert_params(d, e, f)

        ax = self.expert_axis
        cdtype = self.dtype
        xin = jnp.einsum(
            "gtec,gtd->egcd", dispatch.astype(cdtype), x.astype(cdtype)
        )
        xin = _constrain(xin, (ax, MeshConfig.AXIS_DATA, None, None))
        h = jnp.einsum("egcd,edf->egcf", xin, w_in.astype(cdtype))
        h = nn.gelu(h + b_in.astype(cdtype)[:, None, None, :])
        out = jnp.einsum("egcf,efd->egcd", h, w_out.astype(cdtype))
        out = out + b_out.astype(cdtype)[:, None, None, :]
        out = _constrain(out, (ax, MeshConfig.AXIS_DATA, None, None))
        y = jnp.einsum("gtec,egcd->gtd", combine.astype(cdtype), out)
        return self._ungroup(y, g0, t0, n_sub).astype(x.dtype)


# ------------------------------------------------- a chip's share of experts
# Token-choice experts of which THIS chip holds a contiguous range (one of
# the chips that share each layer in an expert-parallel deployment). The
# router scores and picks over ALL experts; only the picks that land in the
# held range are computed and summed here, and what the absent experts
# would have added is left out (their chips add it; on one chip the layer
# runs without that exchange). The dropless sorted path above, with a held
# range: the picks sort by held expert (`_assignment_permutation`, absent
# picks last), each expert's rows are laid out as whole row tiles, and ONE
# kernel, `moe_gmm`, walks the tiles with the tile's expert's weights. Two
# more move the rows: `moe_rows_fill` writes the used tiles from the tokens'
# rows and `moe_rows_sum` adds each valid row, times its weight, to its
# token's sum: one row move a HELD pick each way, whatever the router did.


def route_sigmoid_topk(scores_logits, select_bias, *, k: int,
                       scaling: float, n_group: int = 1,
                       topk_group: int = 1):
    """Sigmoid router with a selection-only bias. scores_logits (N, E)
    float32. The k picks are the top of `sigmoid + bias`; the weights are
    the picks' own sigmoids (no bias), normalised over the picks, times
    `scaling`. With `n_group` > 1 the pick is group-limited: the experts
    are `n_group` groups of E / n_group consecutive ones, a group's score
    the sum of its two largest `sigmoid + bias`, the best `topk_group`
    groups kept and the k picks taken among their experts alone. Returns
    (choices (N, k) int32, weights (N, k) float32)."""
    s = jax.nn.sigmoid(scores_logits.astype(jnp.float32))
    biased = s + select_bias.astype(jnp.float32)
    if n_group > 1:
        n, e = biased.shape
        if e % n_group or not 0 < topk_group <= n_group \
                or k > topk_group * (e // n_group):
            raise ValueError(
                f"{e} experts in {n_group} groups, {topk_group} kept, "
                f"{k} picked")
        grouped = biased.reshape(n, n_group, e // n_group)
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(score, topk_group)
        keep = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        biased = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(n, e)
    _, choices = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(s, choices, axis=-1)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return choices.astype(jnp.int32), w * scaling


def route_softmax_topk(scores_logits, *, k: int, scaling: float):
    """Softmax router: the k largest of softmax(logits) over ALL experts,
    renormalised to sum 1 over the picks, times `scaling`. Returns
    (choices (N, k) int32, weights (N, k) float32)."""
    p = jax.nn.softmax(scores_logits.astype(jnp.float32), axis=-1)
    w, choices = jax.lax.top_k(p, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return choices.astype(jnp.int32), w * scaling


@functools.partial(jax.jit, static_argnames=("offset", "held", "tile"))
def held_tile_layout(choices, *, offset: int, held: int, tile: int):
    """Where each pick's row goes. choices (N, k) over ALL the experts the
    router scores; experts `offset` .. `offset + held` are here.

    The held picks' rows form a padded buffer of `n_tiles * tile` rows in
    which expert e's rows start at a tile boundary (so one row tile has
    one expert). `n_tiles = ceil(N k / tile) + held` is the buffer's SIZE,
    enough for any routing; what is moved into it and out of it is what
    THIS routing holds: the first `tiles_used` tiles, and in them the
    valid rows alone (`held_rows_fill`, `held_rows_sum`). Returns a dict.
    By tile: `tile_expert` (n_tiles,) local expert of each tile (idle tiles
    repeat the last used one: no new weights are fetched for them),
    `tiles_used` (1,), `tile_rows` (n_tiles,) valid rows of a tile (0 past
    the used ones), `tile_first_pick` (n_tiles,) where a tile's rows start
    in the expert-sorted order of the picks, of which `sorted_pick` (N k,)
    is the flat pick and `sorted_token` its token (a tile's valid rows are
    `tile_rows` consecutive sorted picks: all the kernels read). By row,
    for the gathers off the TPU: `row_token` (rows,) the token whose latent
    a row holds, `row_valid` (rows,). By pick: `pick_row` (N, k) the row of
    a held pick (0 otherwise), `pick_held` (N, k) bool. `counts` (held,).
    The by-row and by-pick keys cost more than the rows they index (an
    index gather a row of the whole buffer, a one-hot cumsum a pick:
    PERF.md section 6, PR 39); a program that does not read them does not
    compute them. Jitted on its own, as the two kernels' calls are: a
    model's expert layers ask for the same layout, and a function traced
    and lowered once a program, not once a layer, is host time off every
    program's set-up."""
    n, k = choices.shape
    local = choices - offset
    is_held = (local >= 0) & (local < held)
    local = jnp.where(is_held, local, held).reshape(n * k)
    counts, dest, inv = _assignment_permutation(local, held + 1)
    counts = counts[:held]
    first = jnp.cumsum(counts) - counts           # sorted row of rank 0
    tiles = -(-counts // tile)
    tile_end = jnp.cumsum(tiles)
    tile_first = tile_end - tiles
    used = tile_end[-1]
    n_tiles = -(-(n * k) // tile) + held
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right"), held - 1
    ).astype(jnp.int32)
    rows = jnp.arange(n_tiles * tile, dtype=jnp.int32)
    row_e = tile_expert[rows // tile]
    rank = rows - tile_first[row_e] * tile
    row_valid = (rows // tile < used) & (rank < counts[row_e])
    src = inv[jnp.where(row_valid, first[row_e] + rank, 0)]
    held_local = jnp.minimum(local, held - 1)
    pick_row = tile_first[held_local] * tile + dest - first[held_local]
    tiles_all = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_rank = (tiles_all - tile_first[tile_expert]) * tile
    return {
        "row_token": src // k, "row_valid": row_valid,
        "sorted_pick": inv, "sorted_token": inv // k,
        "tile_first_pick": (first[tile_expert] + tile_rank
                            ).astype(jnp.int32),
        "tile_rows": jnp.where(
            tiles_all < used,
            jnp.clip(counts[tile_expert] - tile_rank, 0, tile), 0
        ).astype(jnp.int32),
        "tile_expert": tile_expert,
        "tiles_used": used.astype(jnp.int32)[None],
        "pick_row": jnp.where(is_held.reshape(-1), pick_row, 0
                              ).reshape(n, k),
        "pick_held": is_held, "counts": counts,
    }


def _relu2_body(x_ref, w1_ref, w2_ref):
    """relu(x W1)^2 W2 of one row tile, float32 sums."""
    h = jnp.dot(x_ref[...], w1_ref[...], preferred_element_type=jnp.float32)
    h = jnp.square(jnp.maximum(h, 0.0)).astype(x_ref.dtype)
    return jnp.dot(h, w2_ref[...], preferred_element_type=jnp.float32)


def _glu_act(g, activation: str):
    """The gate's activation on float32 `g`: "silu" (SwiGLU) or "relu"
    (ReGLU). A static choice of the layer: the kernel's body holds the one
    it was built with and no branch."""
    if activation == "silu":
        return g * jax.nn.sigmoid(g)
    if activation == "relu":
        return jnp.maximum(g, 0.0)
    raise ValueError(f"activation {activation!r}: want 'silu' or 'relu'")


def _glu_body(x_ref, wg_ref, wu_ref, wd_ref, *, activation: str = "silu"):
    """(act(x W_gate) * x W_up) W_down of one row tile, float32 sums."""
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (_glu_act(g, activation) * u).astype(x.dtype)
    return jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)


def _expert_tiles_kernel(te_ref, used_ref, x_ref, *refs, body):
    """One row tile through its expert (`body` over the expert's
    matrices). Tiles past the used ones write zeros and fetch nothing
    new."""
    del te_ref
    *w_refs, o_ref = refs
    t = pl.program_id(0)

    @pl.when(t < used_ref[0])
    def _():
        o_ref[...] = body(x_ref, *w_refs).astype(o_ref.dtype)

    @pl.when(t >= used_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _expert_tiles_call(body, name, x_rows, mats, tile_expert, tiles_used,
                       tile: int):
    """ONE device op called `name` (interpret mode off the TPU, where only
    the tests call it): grid over the tiles, the weights' block index is
    the tile's expert, so a run of tiles of one expert fetches its
    matrices once and every held expert that has a row is streamed exactly
    once."""
    rows, d = x_rows.shape
    n_tiles = rows // tile
    widest = max(max(m.shape[1:]) for m in mats)
    itemsize = jnp.dtype(mats[0].dtype).itemsize
    # an expert's matrices, two deep, and the row tiles
    vmem = 2 * sum(m.shape[1] * m.shape[2] for m in mats) * itemsize \
        + 8 * tile * widest * 4 + (4 << 20)
    return pl.pallas_call(
        functools.partial(_expert_tiles_kernel, body=body),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tile, d), lambda t, te, used: (t, 0))] + [
                pl.BlockSpec((None,) + m.shape[1:],
                             lambda t, te, used: (te[t], 0, 0))
                for m in mats],
            out_specs=pl.BlockSpec((tile, d), lambda t, te, used: (t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem)),
        interpret=not backend.on_tpu(),
        name=name,
    )(tile_expert, tiles_used, x_rows, *mats)


def _live_tiles(out, n_tiles: int, tiles_used, like):
    live = jnp.arange(n_tiles)[:, None, None] < tiles_used[0]
    return jnp.where(live, out, 0.0).astype(like.dtype).reshape(like.shape)


def expert_mlp_tiles(x_rows, w1, w2, tile_expert, tiles_used, *, tile: int):
    """relu(x W1_e)^2 W2_e for every row tile, e the tile's expert.
    x_rows (n_tiles * tile, d); w1 (held, d, f); w2 (held, f, d): the
    kernel on the TPU, a gather + einsum elsewhere."""
    if not backend.on_tpu():
        with jax.named_scope("moe_gmm"):
            return expert_mlp_tiles_reference(
                x_rows, w1, w2, tile_expert, tiles_used, tile=tile)
    return expert_mlp_tiles_kernel(
        x_rows, w1, w2, tile_expert, tiles_used, tile=tile)


def expert_mlp_tiles_reference(x_rows, w1, w2, tile_expert, tiles_used, *,
                               tile: int):
    rows, d = x_rows.shape
    n_tiles = rows // tile
    xt = x_rows.reshape(n_tiles, tile, d)
    h = jnp.einsum("tmd,tdf->tmf", xt, w1[tile_expert],
                   preferred_element_type=jnp.float32)
    h = jnp.square(jnp.maximum(h, 0.0)).astype(x_rows.dtype)
    out = jnp.einsum("tmf,tfd->tmd", h, w2[tile_expert],
                     preferred_element_type=jnp.float32)
    return _live_tiles(out, n_tiles, tiles_used, x_rows)


def expert_mlp_tiles_kernel(x_rows, w1, w2, tile_expert, tiles_used, *,
                            tile: int):
    """The device op `moe_gmm`: both matrices of an expert in VMEM."""
    return _expert_tiles_call(_relu2_body, "moe_gmm", x_rows, (w1, w2),
                              tile_expert, tiles_used, tile)


def expert_glu_tiles(x_rows, w_gate, w_up, w_down, tile_expert, tiles_used,
                     *, tile: int, activation: str = "silu"):
    """(act(x Wg_e) * x Wu_e) Wd_e for every row tile, e the tile's
    expert: gated experts, `activation` "silu" or "relu". w_gate, w_up
    (held, d, f); w_down (held, f, d)."""
    if not backend.on_tpu():
        with jax.named_scope("moe_gmm_glu"):
            return expert_glu_tiles_reference(
                x_rows, w_gate, w_up, w_down, tile_expert, tiles_used,
                tile=tile, activation=activation)
    return expert_glu_tiles_kernel(
        x_rows, w_gate, w_up, w_down, tile_expert, tiles_used, tile=tile,
        activation=activation)


def expert_glu_tiles_reference(x_rows, w_gate, w_up, w_down, tile_expert,
                               tiles_used, *, tile: int,
                               activation: str = "silu"):
    rows, d = x_rows.shape
    n_tiles = rows // tile
    xt = x_rows.reshape(n_tiles, tile, d)
    g = jnp.einsum("tmd,tdf->tmf", xt, w_gate[tile_expert],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("tmd,tdf->tmf", xt, w_up[tile_expert],
                   preferred_element_type=jnp.float32)
    h = (_glu_act(g, activation) * u).astype(x_rows.dtype)
    out = jnp.einsum("tmf,tfd->tmd", h, w_down[tile_expert],
                     preferred_element_type=jnp.float32)
    return _live_tiles(out, n_tiles, tiles_used, x_rows)


def expert_glu_tiles_kernel(x_rows, w_gate, w_up, w_down, tile_expert,
                            tiles_used, *, tile: int,
                            activation: str = "silu"):
    """The device op `moe_gmm_glu`: the three matrices of an expert in
    VMEM, two deep (2 x 9.4 MB at 2048 -> 768 -> 2048 in bf16, 2 x 11.8 MB
    at 2560 -> 768 -> 2560)."""
    # "silu" hands over `_glu_body` itself, as before the option came
    body = _glu_body if activation == "silu" else functools.partial(
        _glu_body, activation=activation)
    return _expert_tiles_call(
        body, "moe_gmm_glu", x_rows, (w_gate, w_up, w_down),
        tile_expert, tiles_used, tile)


def _row_tile(rows_per_expert: float) -> int:
    """Rows a tile holds: the power of two from 16 (a bf16 sublane tile)
    to 128 (the MXU's height) that covers what an expert expects."""
    tile = 16
    while tile < 128 and tile < rows_per_expert:
        tile *= 2
    return tile


_TOKEN_BLOCK = 4096   # tokens whose sums `moe_rows_sum` holds in VMEM
_FILL_BYTES = 48 << 20  # the most of src that `moe_rows_fill` holds there


def _row_of_words(x_ref, row, paired: bool):
    """Row `row` of a ref as (1, d) float32. A 16-bit ref is read through
    its view as (rows / 2, d) 32-bit words, two rows a word: a bf16's bits
    are the upper half of its float32's, so the even row is the word
    shifted up and the odd row the word's upper half."""
    if not paired:
        return x_ref[pl.ds(row, 1), :].astype(jnp.float32)
    word = x_ref.bitcast(jnp.uint32)[pl.ds(row // 2, 1), :]
    up = (16 * (1 - row % 2)).astype(jnp.uint32)
    return pltpu.bitcast((word << up) & jnp.uint32(0xFFFF0000), jnp.float32)


def _rows_fill_kernel(tok_ref, first_ref, n_ref, used_ref, x_ref, o_ref,
                      tile_scr, *, paired: bool):
    """Row tile t of the buffer from its picks' tokens' rows of x, which is
    whole in VMEM. A tile's valid rows are `n_ref[t]` consecutive picks of
    the expert-sorted order from `first_ref[t]` (`tok_ref`: a sorted pick's
    token): one row move each, zeros after them. Tiles past the used ones
    are not written."""
    t = pl.program_id(0)

    @pl.when(t < used_ref[0])
    def _():
        tile_scr[...] = jnp.zeros(tile_scr.shape, tile_scr.dtype)

        def one(i, _):
            tok = tok_ref[first_ref[t] + i]
            tile_scr[pl.ds(i, 1), :] = _row_of_words(x_ref, tok, paired)
        jax.lax.fori_loop(0, n_ref[t], one, None)
        o_ref[...] = tile_scr[...].astype(o_ref.dtype)


def _paired(dtype) -> bool:
    """True for bfloat16 rows (`_row_of_words` reads them as halves of
    words), False for 32-bit ones; no other width is moved."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return True
    if jnp.dtype(dtype).itemsize != 4:
        raise ValueError(f"rows of {dtype} cannot be moved as words")
    return False


def _last_used(t, used):
    return jnp.minimum(t, jnp.maximum(used[0] - 1, 0))


def _interpret(interpret) -> bool:
    return (not backend.on_tpu()) if interpret is None else interpret


def held_rows_fill_kernel(src, lay, *, tile: int, interpret=None):
    """The device op `moe_rows_fill`: the row buffer of `held_tile_layout`
    from src (n, d), one row move a held pick."""
    return _rows_fill_call(
        src, lay["sorted_token"], lay["tile_first_pick"], lay["tile_rows"],
        lay["tiles_used"], tile=tile, interpret=_interpret(interpret))


# jitted on their own, as `held_tile_layout` is
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _rows_fill_call(src, sorted_token, tile_first_pick, tile_rows,
                    tiles_used, *, tile: int, interpret: bool):
    n_tiles = tile_rows.shape[0]
    n, d = src.shape
    paired = _paired(src.dtype)
    if paired and n % 16:         # whole sublane tiles of 16-bit rows
        src = jnp.pad(src, ((0, -n % 16), (0, 0)))
    return pl.pallas_call(
        functools.partial(_rows_fill_kernel, paired=paired),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(
                (tile, d), lambda t, tok, first, n, used: (
                    _last_used(t, used), 0)),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, d), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(src.size * src.dtype.itemsize
                                 + 8 * tile * d * 4 + (4 << 20))),
        interpret=interpret,
        name="moe_rows_fill",
    )(sorted_token, tile_first_pick, tile_rows, tiles_used, src)


def held_rows_fill(src, lay, *, tile: int):
    """The row buffer of `held_tile_layout` from src (n, d): the kernel on
    the TPU where src fits its VMEM (`_FILL_BYTES`: every call the engines
    make; 16 MB at 4,096 tokens of width 2,048), gathers elsewhere."""
    if backend.on_tpu() and src.size * src.dtype.itemsize <= _FILL_BYTES:
        return held_rows_fill_kernel(src, lay, tile=tile)
    return held_rows_fill_reference(src, lay)


def held_rows_fill_reference(src, lay):
    """Every row of the buffer gathered (idle rows gather token 0 and are
    zeroed after): what the kernel stands in for, off the TPU."""
    return jnp.where(lay["row_valid"][:, None], src[lay["row_token"]], 0)


def _rows_sum_kernel(tok_ref, first_ref, n_ref, used_ref, w_ref, out_ref,
                     y_ref, acc, tile_scr, *, block: int):
    """Cell (b, t): the rows of tile t whose tokens lie in token block b,
    each times its pick's float32 weight, added to their tokens' float32
    sums; the block's sums are written as the last tile leaves. Only a
    tile's valid rows are read, so what else the buffer holds reaches no
    token, and a pick that is not held has no row."""
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(t < used_ref[0])
    def _():
        tile_scr[...] = out_ref[...].astype(jnp.float32)

        def one(i, _):
            at = first_ref[t] + i
            tok = tok_ref[at] - b * block

            @pl.when((tok >= 0) & (tok < block))
            def _():
                acc[pl.ds(tok, 1), :] += w_ref[at] * tile_scr[pl.ds(i, 1), :]
        jax.lax.fori_loop(0, n_ref[t], one, None)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        y_ref[...] = acc[...].astype(y_ref.dtype)


def held_rows_sum_kernel(out, lay, weights, dtype, *, tile: int,
                         interpret=None):
    """The device op `moe_rows_sum`: each token's weighted sum over its
    held picks' rows of `out`, one row move a held pick."""
    return _rows_sum_call(
        out, weights, lay["sorted_pick"], lay["tile_first_pick"],
        lay["tile_rows"], lay["tiles_used"], dtype=jnp.dtype(dtype),
        tile=tile, interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("dtype", "tile", "interpret"))
def _rows_sum_call(out, weights, sorted_pick, tile_first_pick, tile_rows,
                   tiles_used, *, dtype, tile: int, interpret: bool):
    n, k = weights.shape
    n_tiles, d = tile_rows.shape[0], out.shape[1]
    block = min(_TOKEN_BLOCK, -(-n // 16) * 16)
    blocks = -(-n // block)
    y = pl.pallas_call(
        functools.partial(_rows_sum_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(blocks, n_tiles),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, d), lambda b, t, tok, first, n, used: (
                    _last_used(t, used), 0))],
            out_specs=pl.BlockSpec(
                (block, d), lambda b, t, tok, first, n, used: (b, 0)),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                            pltpu.VMEM((tile, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((blocks * block, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(
                block * d * (4 + 2 * dtype.itemsize)
                + 8 * tile * d * 4 + (4 << 20))),
        interpret=interpret,
        name="moe_rows_sum",
    )(sorted_pick // k, tile_first_pick, tile_rows, tiles_used,
      weights.astype(jnp.float32).reshape(-1)[sorted_pick], out)
    return y[:n] if blocks * block != n else y


def held_rows_sum(out, lay, weights, dtype, *, tile: int):
    """Each token's sum over its held picks' rows of `out` (the buffer's
    shape), times their weights (n, k) float32, in float32: the kernel on
    the TPU, gathers elsewhere."""
    if backend.on_tpu():
        return held_rows_sum_kernel(out, lay, weights, dtype, tile=tile)
    return held_rows_sum_reference(out, lay, weights, dtype)


def held_rows_sum_reference(out, lay, weights, dtype):
    """A gather of every pick's row (row 0 for a pick that is not held,
    dropped by the select before it meets a weight)."""
    held = lay["pick_held"]
    picked = jnp.where(held[..., None], out[lay["pick_row"]], 0)
    return jnp.einsum("nk,nkd->nd", jnp.where(held, weights, 0.0),
                      picked.astype(jnp.float32)).astype(dtype)


def _held_rows(layer, xf, src):
    """The router and the row layout of a layer that holds a range of the
    experts (`LatentMoE`, `GatedMoE`; `layer` gives `num_experts`, `top_k`,
    `experts_held`, `expert_offset`, `routed_scaling`, the parameters'
    scope and, where it has them, `router`: "sigmoid" with a selection bias,
    or "softmax", and the sigmoid router's `n_group`, `topk_group`): scores xf (n, d) over ALL experts in float32, lays the held
    picks out as whole row tiles and fills them from `src` (n, width).
    Returns (rows, layout, weights (n, k) float32, tile)."""
    n, k, held = xf.shape[0], layer.top_k, layer.experts_held
    if not 0 <= layer.expert_offset <= layer.num_experts - held:
        raise ValueError(
            f"held experts [{layer.expert_offset}, "
            f"{layer.expert_offset + held}) lie outside the "
            f"{layer.num_experts} the router scores")
    softmax = getattr(layer, "router", "sigmoid") == "softmax"
    if not softmax:
        bias = layer.param("e_score_correction_bias", nn.initializers.zeros,
                           (layer.num_experts,), layer.param_dtype)
    with jax.named_scope("moe_route"):
        logits = nn.Dense(
            layer.num_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=layer.param_dtype, name="router",
        )(xf.astype(jnp.float32))
        if softmax:
            choices, weights = route_softmax_topk(
                logits, k=k, scaling=layer.routed_scaling)
        else:
            choices, weights = route_sigmoid_topk(
                logits, bias, k=k, scaling=layer.routed_scaling,
                n_group=getattr(layer, "n_group", 1),
                topk_group=getattr(layer, "topk_group", 1))
        tile = _row_tile(n * k / layer.num_experts)
        lay = held_tile_layout(choices, offset=layer.expert_offset,
                               held=held, tile=tile)
        rows = held_rows_fill(src.astype(layer.dtype), lay, tile=tile)
    return rows, lay, weights, tile


def _held_combine(out, lay, weights, dtype, tile: int):
    """Each token's weighted sum over its held picks' rows of `out`."""
    with jax.named_scope("moe_combine"):
        return held_rows_sum(out, lay, weights, dtype, tile=tile)


def _held_count(layer, lay, decode: bool, tile: int) -> None:
    """Decode mode: add this call's counts to the cache collection's
    `moe_stats` (rows that landed on held experts, held experts touched,
    most rows on one expert; summed over the calls since the engine last
    zeroed it), what `PagedEngine` hands its tracer; and to `moe_rows` the
    rows this call moved beside the rows of its whole layout
    (`held_rows_moved`)."""
    if not decode:
        return
    stats = layer.variable("cache", "moe_stats", jnp.zeros, (3,), jnp.int32)
    if not layer.is_initializing():
        c, old = lay["counts"], stats.value
        stats.value = jnp.stack([
            old[0] + c.sum(), old[1] + (c > 0).sum(),
            jnp.maximum(old[2], c.max())]).astype(jnp.int32)
    rows = layer.variable("cache", "moe_rows", jnp.zeros, (2,), jnp.int32)
    if not layer.is_initializing():
        rows.value = rows.value + held_rows_moved(lay, tile)


def held_rows_moved(lay, tile: int):
    """(2,) int32: the rows a call moves into its tile buffer and out of
    it, and the rows of the whole layout, which covers any routing and
    which the gathers move (`held_rows_fill_reference`,
    `held_rows_sum_reference`: they stand in for the kernels off the TPU,
    where the count is still the kernels'). Into the buffer go the used
    tiles' rows, the held picks rounded up to whole tiles an expert; out of
    it one row a held pick."""
    n, k = lay["pick_held"].shape
    moved = lay["tiles_used"][0] * tile + lay["counts"].sum()
    layout = lay["tile_expert"].shape[0] * tile + n * k
    return jnp.stack([moved, layout]).astype(jnp.int32)


class LatentMoE(nn.Module):
    """Experts in a latent space (LatentMoE), a chip's share of them held.

        s = sigmoid(x W_r)                      float32, all `num_experts`
        picks = top_k(s + selection bias);  w_e = s_e / sum_picks s * scaling
        u = x W_down                            d -> latent
        routed = (sum_{picks held here} w_e relu(u W1_e)^2 W2_e) W_up
        out = routed + relu(x V1)^2 V2          the shared expert, whole

    `experts_held` experts from `expert_offset` are here; `W_down`, `W_up`,
    the router and the shared expert are whole on every chip. Router, row
    layout, combine and the decode-mode counters (`moe_stats`) are
    `GatedMoE`'s too (`_held_rows`, `_held_combine`, `_held_count`)."""

    num_experts: int
    top_k: int
    latent_dim: int
    expert_dim: int
    shared_dim: int
    experts_held: int
    expert_offset: int = 0
    routed_scaling: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, decode: bool = False):
        lead, d = x.shape[:-1], x.shape[-1]
        cd = self.dtype
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cd, param_dtype=self.param_dtype)
        xf = x.reshape(-1, d).astype(cd)
        held = self.experts_held
        u = dense(self.latent_dim, name="down")(xf)
        rows, lay, weights, tile = _held_rows(self, xf, u)
        w1 = self.param("expert_w1", nn.initializers.normal(0.02),
                        (held, self.latent_dim, self.expert_dim),
                        self.param_dtype)
        w2 = self.param("expert_w2", nn.initializers.normal(0.02),
                        (held, self.expert_dim, self.latent_dim),
                        self.param_dtype)
        out = expert_mlp_tiles(
            rows, w1.astype(cd), w2.astype(cd), lay["tile_expert"],
            lay["tiles_used"], tile=tile)
        y = dense(d, name="up")(_held_combine(out, lay, weights, cd, tile))
        shared = dense(self.shared_dim, name="shared_in")(xf)
        shared = jnp.square(nn.relu(shared))
        y = y + dense(d, name="shared_out")(shared)
        _held_count(self, lay, decode, tile)
        return y.reshape(*lead, d).astype(x.dtype)


class GatedMLP(nn.Module):
    """SwiGLU: W_down(silu(W_gate x) * W_up x), no biases."""

    width: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype)
        h = nn.silu(dense(self.width, name="gate")(x)) \
            * dense(self.width, name="up")(x)
        return dense(x.shape[-1], name="down")(h)


class GatedMoE(nn.Module):
    """Gated (SwiGLU) experts in the model's own width (DeepSeek-V3 style),
    a chip's share of them held; `LatentMoE`'s router, row layout, combine
    and counters.

        s = sigmoid(x W_r)                      float32, all `num_experts`
        picks = top_k(s + selection bias);  w_e = s_e / sum_picks s * scaling
        routed = sum_{picks held here} w_e Wd_e(silu(Wg_e x) * Wu_e x)
        out = routed + the shared SwiGLU expert of width `shared_dim`

    `router="softmax"`: s = softmax(x W_r), picks = top_k(s), no bias.
    `n_group`, `topk_group`: the sigmoid router's group-limited pick
    (`route_sigmoid_topk`; 1 and 1: every expert a candidate).
    `shared_gate`: the shared expert times sigmoid(x w_s), a gate of its
    own (Qwen's). `shared_dim` 0: no shared expert. `activation`: the
    experts' gate, "silu" or "relu" (ReGLU). `router_input`: what the
    router scores where that is not the rows the experts read (a router
    placed before the layer's mixer). The expert matrices go through ONE
    kernel, `moe_gmm_glu`."""

    num_experts: int
    top_k: int
    expert_dim: int
    shared_dim: int
    experts_held: int
    expert_offset: int = 0
    routed_scaling: float = 1.0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    router: str = "sigmoid"
    shared_gate: bool = False
    activation: str = "silu"
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, x, *, decode: bool = False, router_input=None):
        lead, d = x.shape[:-1], x.shape[-1]
        cd, held, f = self.dtype, self.experts_held, self.expert_dim
        xf = x.reshape(-1, d).astype(cd)
        scored = xf if router_input is None \
            else router_input.reshape(-1, d).astype(cd)
        rows, lay, weights, tile = _held_rows(self, scored, xf)
        mats = [self.param(name, nn.initializers.normal(0.02), shape,
                           self.param_dtype).astype(cd)
                for name, shape in (("expert_gate", (held, d, f)),
                                    ("expert_up", (held, d, f)),
                                    ("expert_down", (held, f, d)))]
        out = expert_glu_tiles(rows, *mats, lay["tile_expert"],
                               lay["tiles_used"], tile=tile,
                               activation=self.activation)
        y = _held_combine(out, lay, weights, cd, tile)
        if self.shared_dim:
            shared = GatedMLP(self.shared_dim, cd, self.param_dtype,
                              name="shared")(xf)
            if self.shared_gate:
                shared = shared * nn.sigmoid(nn.Dense(
                    1, use_bias=False, dtype=cd,
                    param_dtype=self.param_dtype,
                    name="shared_expert_gate")(xf))
            y = y + shared
        _held_count(self, lay, decode, tile)
        return y.reshape(*lead, d).astype(x.dtype)
