"""Ring attention: sequence/context parallelism over the 'seq' mesh axis.

Absent from the reference (no attention, no sequence axis — SURVEY §5.7) but
first-class here: the sequence dimension is sharded across devices; each
device computes blockwise attention for its local queries while K/V blocks
rotate around the ring via `lax.ppermute` (ICI neighbor exchange), with an
online-softmax accumulator so the result is exact — the Ring Attention
construction (Liu et al.) on top of XLA collectives.

Works in two modes:
- already inside a `shard_map`/pmap where `axis_name` is bound: computes
  directly on the local blocks.
- under GSPMD `jit`: wraps itself in a `shard_map` island over the current
  mesh (batch dim over 'data', sequence dim over `axis_name`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import AxisType, PartitionSpec as P

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.utils import backend

_NEG_INF = -1e30

# Mesh registry so model code deep inside a jitted function can open a
# shard_map island without threading the Mesh object through every module.
_CURRENT_MESH = None


def set_current_mesh(mesh) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_current_mesh():
    return _CURRENT_MESH


def single_chip_tpu() -> bool:
    """True when the program executes compiled on ONE TPU chip.

    The auto-selection gate for kernel-by-default paths (currently
    models/vit.py EncoderBlock._auto_fuse; MoE's "auto" resolved to the
    einsum path everywhere once the gather/sorted shootout measured it
    fastest, so MoEMlp no longer consults this): Pallas kernels run
    interpret-mode on CPU (never a win) and are not
    validated under multi-chip GSPMD partitioning here, so implicit
    selection stays out of both regimes. "One chip" means the devices
    this program runs on — the framework's current mesh when set
    (a --devices 1 run on a multi-chip host qualifies), the host
    inventory otherwise."""
    if not backend.on_tpu():
        return False
    mesh = get_current_mesh()
    n_dev = mesh.devices.size if mesh is not None else jax.device_count()
    return n_dev == 1


def _axis_bound(axis_name: str) -> bool:
    try:
        lax.axis_index(axis_name)
        return True
    except (NameError, KeyError, ValueError):
        return False


def ring_attention(
    q: jnp.ndarray,  # (batch, seq_local_or_global, heads, head_dim)
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    mesh=None,
    impl: str = "xla",
) -> jnp.ndarray:
    """impl='xla': inline blockwise einsums (online softmax). impl='flash':
    the Pallas kernel (ops.flash_attention) runs each local q x k-block
    attention, returning (out, lse); partials merge across ring steps in
    logsumexp space — O(local seq) memory with the fused kernel's HBM
    profile, composing the two long-context features."""
    if _axis_bound(axis_name):
        return _ring_attention_local(
            q, k, v, axis_name=axis_name, causal=causal, impl=impl
        )
    mesh = mesh or get_current_mesh()
    if mesh is None:
        raise ValueError(
            "ring_attention outside shard_map needs a mesh "
            "(set via parallel.ring.set_current_mesh)"
        )
    # batch over data, sequence over the ring axis, heads stay sharded over
    # tensor (heads are independent in attention, so TP composes with SP)
    mesh, spec = _island_mesh_and_spec(mesh, axis_name)
    fn = shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, causal=causal,
            impl=impl,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _island_context(mesh):
    """(mesh to open an island on, axes an enclosing shard_map already
    made manual).

    Under an OUTER partial-manual shard_map (the GPipe pipeline is manual
    over 'pipe'/'data'), a nested island must (a) pass the context
    AbstractMesh, whose axis_types record which axes are already Manual,
    and (b) name only still-automatic axes in its specs — the manual ones
    are already local dims here. That is what lets sequence parallelism
    run INSIDE a pipeline stage (sp x pp)."""
    ctx = jax.sharding.get_abstract_mesh()
    manual = {
        n for n, t in zip(ctx.axis_names, ctx.axis_types)
        if t == AxisType.Manual
    }
    return (ctx if manual else mesh), manual


def _island_mesh_and_spec(mesh, axis_name: str):
    """Mesh + (batch, seq, heads, None) spec for an SP shard_map island."""
    mesh, manual = _island_context(mesh)
    if axis_name in manual:
        raise ValueError(
            f"sequence axis {axis_name!r} is already manual in the "
            "enclosing shard_map — call the local ring directly"
        )
    spec = P(
        None if MeshConfig.AXIS_DATA in manual else MeshConfig.AXIS_DATA,
        axis_name,
        None if MeshConfig.AXIS_TENSOR in manual else MeshConfig.AXIS_TENSOR,
        None,
    )
    return mesh, spec


def kernel_island(fn, *, in_specs, out_specs):
    """`fn` as it must be called under GSPMD `jit` on a mesh: once per
    device, on that device's own shard, inside a shard_map island.

    A Pallas (Mosaic) kernel is an opaque custom call — the TPU
    partitioner refuses to split one ("Mosaic kernels cannot be
    automatically partitioned"), which interpret mode on CPU never shows
    because there the kernel is ordinary XLA ops. Attention is
    independent per batch row and per head, so the specs shard batch
    over 'data' and heads over 'tensor' and nothing is gathered. Axes an
    enclosing shard_map already made manual are local dims here and are
    dropped from the specs. With no registered mesh, or one device, `fn`
    is returned as it is."""
    mesh = get_current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return fn
    mesh, manual = _island_context(mesh)
    if manual >= set(mesh.axis_names):
        return fn  # every axis is already local: nothing left to split

    def local(spec):
        return P(*(None if a in manual else a for a in spec))

    return shard_map(
        fn, mesh=mesh,
        in_specs=tuple(local(sp) for sp in in_specs),
        out_specs=local(out_specs),
        check_vma=False,
    )


# what a local attention kernel sees under DP x TP (SP aside):
# (batch, seq, heads, head_dim), and the raw (batch, seq, 3, heads,
# head_dim) output of the QKV projection
BSHD_SPEC = P(MeshConfig.AXIS_DATA, None, MeshConfig.AXIS_TENSOR, None)
QKV_SPEC = P(MeshConfig.AXIS_DATA, None, None, MeshConfig.AXIS_TENSOR, None)


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          impl: str = "xla"):
    if impl == "flash":
        return _ring_flash_local(q, k, v, axis_name=axis_name, causal=causal)
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r} (want 'xla'|'flash')")
    return _ring_xla_local(q, k, v, axis_name=axis_name, causal=causal)


def _ring_flash_local(q, k, v, *, axis_name: str, causal: bool):
    """Ring attention with the Pallas flash kernel as the local attention.

    Each ring step computes flash attention of the (resident) local queries
    against the currently-held K/V block, yielding normalized (o_i, lse_i);
    partials merge exactly:

        m = max(lse, lse_i); w = exp(lse - m); w_i = exp(lse_i - m)
        o <- (w*o + w_i*o_i) / (w + w_i);  lse <- m + log(w + w_i)

    Causality across blocks resolves by block index (this device holds
    global q positions [my_idx*sq, ...)): earlier blocks attend fully,
    the diagonal block runs the kernel's causal mask, later blocks are
    skipped (lse = -inf) — gradients flow through the kernel's tiled
    backward plus the (differentiable) merge."""
    from ddp_practice_tpu.ops.flash_attention import flash_attention_with_lse

    in_dtype = q.dtype
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, dh = q.shape

    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], dh)

    qf, kf, vf = fold(q), fold(k), fold(v)
    o0 = jnp.zeros((b * h, sq, dh), jnp.float32)
    lse0 = jnp.full((b * h, sq), _NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def attend(kb, vb, kblock):
        def run(causal_flag):
            def f(args):
                o, lse = flash_attention_with_lse(*args, causal=causal_flag)
                return o.astype(jnp.float32), lse
            return f

        if not causal:
            return run(False)((qf, kb, vb))

        def masked(args):
            return (jnp.zeros((b * h, sq, dh), jnp.float32),
                    jnp.full((b * h, sq), _NEG_INF, jnp.float32))

        idx = jnp.where(kblock == my_idx, 1, jnp.where(kblock < my_idx, 2, 0))
        return lax.switch(idx, [masked, run(True), run(False)], (qf, kb, vb))

    def body(carry, step):
        o, lse, kb, vb = carry
        kblock = (my_idx - step) % axis_size
        oi, lsei = attend(kb, vb, kblock)
        m = jnp.maximum(lse, lsei)
        w1 = jnp.exp(lse - m)
        w2 = jnp.exp(lsei - m)
        denom = w1 + w2
        o = (o * w1[..., None] + oi * w2[..., None]) / denom[..., None]
        lse = m + jnp.log(denom)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o, lse, kb, vb), None

    (o, _, _, _), _ = lax.scan(
        body, (o0, lse0, kf, vf), jnp.arange(axis_size)
    )
    o = jnp.transpose(o.reshape(b, h, sq, dh), (0, 2, 1, 3))
    return o.astype(in_dtype)


def _ring_xla_local(q, k, v, *, axis_name: str, causal: bool):
    """Blockwise attention on local shards; K/V ring-rotated each step."""
    in_dtype = q.dtype
    axis_size = lax.psum(1, axis_name)  # trace-time constant under shard_map
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    qf = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)  # (b,h,sq,d)
    kf = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32)
    vf = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32)

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)

    q_pos = my_idx * sq + jnp.arange(sq)  # global query positions

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(carry, step):
        o, m, l, kb, vb = carry
        kblock = (my_idx - step) % axis_size
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale
        if causal:
            k_pos = kblock * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard exp(-inf - -inf): rows still fully masked keep m at _NEG_INF
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o_new, m_new, l_new, kb, vb), None

    (o, m, l, _, _), _ = lax.scan(
        body, (o0, m0, l0, kf, vf), jnp.arange(axis_size)
    )
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(in_dtype)
