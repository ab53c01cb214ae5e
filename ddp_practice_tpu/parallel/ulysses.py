"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head scatter.

Second sequence-parallel scheme next to `parallel.ring` (absent from the
reference, which has no sequence axis at all — SURVEY §5.7). Where ring
attention keeps queries local and rotates K/V blocks around the 'seq' mesh
axis, Ulysses re-shards with two all-to-alls:

    (batch, seq/N, heads, d) --all_to_all--> (batch, seq, heads/N, d)
      ... dense attention over the FULL sequence per (fewer) heads ...
    (batch, seq, heads/N, d) --all_to_all--> (batch, seq/N, heads, d)

Attention itself is then a plain fused softmax-attention over the whole
sequence — maximally MXU-friendly — at the cost of two all-to-alls over ICI
instead of ring ppermutes. Preferable when heads >> seq-axis size and the
sequence fits in HBM once gathered; ring wins for extreme lengths.

Requires local heads divisible by the 'seq' axis size (heads are already
divided by the 'tensor' axis under TP, so: heads % (tp * sp) == 0).
"""

from __future__ import annotations

import functools

import jax
from jax import lax, shard_map

from ddp_practice_tpu.parallel.ring import (
    _axis_bound,
    _island_mesh_and_spec,
    get_current_mesh,
)


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = False,
                      mesh=None, impl: str = "xla"):
    """All-to-all sequence-parallel attention; same signature as ring.

    `impl` picks the local full-sequence attention after the head scatter:
    'xla' (fused dense) or 'flash' (the Pallas tiled kernel — O(seq)
    memory over the gathered sequence)."""
    if _axis_bound(axis_name):
        return _ulysses_local(
            q, k, v, axis_name=axis_name, causal=causal, impl=impl
        )
    mesh = mesh or get_current_mesh()
    if mesh is None:
        raise ValueError(
            "ulysses_attention outside shard_map needs a mesh "
            "(set via parallel.ring.set_current_mesh)"
        )
    mesh, spec = _island_mesh_and_spec(mesh, axis_name)
    fn = shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis_name, causal=causal, impl=impl
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, impl: str = "xla"):
    from ddp_practice_tpu.ops.attention import _attention

    axis_size = lax.psum(1, axis_name)
    heads = q.shape[2]
    if heads % axis_size != 0:
        raise ValueError(
            f"ulysses needs local heads ({heads}) divisible by "
            f"'{axis_name}' axis size ({axis_size})"
        )

    def gather_seq_scatter_heads(x):
        # (b, s/N, h, d) -> (b, s, h/N, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def scatter_seq_gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qg = gather_seq_scatter_heads(q)
    kg = gather_seq_scatter_heads(k)
    vg = gather_seq_scatter_heads(v)
    if impl == "flash":
        from ddp_practice_tpu.ops.flash_attention import flash_attention

        out = flash_attention(qg, kg, vg, causal=causal)
    elif impl == "xla":
        out = _attention(qg, kg, vg, causal=causal)
    else:
        raise ValueError(f"unknown attention impl {impl!r} (want 'xla'|'flash')")
    return scatter_seq_gather_heads(out)
