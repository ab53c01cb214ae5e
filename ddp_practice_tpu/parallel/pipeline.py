"""Pipeline parallelism: GPipe microbatch schedule over the 'pipe' mesh axis.

Absent from the reference (single forward per step, no stage partitioning —
SURVEY §2.3 "Pipeline parallel — No"). TPU-first design: no per-stage
processes or send/recv threads (the GPU idiom). Instead the whole pipeline
is ONE jitted SPMD program:

- the block stack's parameters carry a leading stage dimension sharded over
  the 'pipe' mesh axis — each device holds depth/P blocks;
- a `lax.scan` over M + P - 1 ticks runs the GPipe schedule: stage 0
  ingests a fresh microbatch each tick, every stage applies its local
  blocks, and activations hop stage→stage via `lax.ppermute` (one ICI
  neighbor exchange per tick);
- the last stage's emitted microbatches are re-broadcast with a masked
  `psum`, so downstream (GSPMD) code sees the output replicated over
  'pipe'.

The backward pass is just XLA differentiating the scan: reversed ppermutes,
exactly the 1F1B-style reverse hops, with the latency-hiding scheduler
overlapping compute and ICI traffic. Composes with the 'data' axis (batch
dim stays sharded over 'data' inside the shard_map).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.parallel.ring import get_current_mesh


def pipeline_apply(
    block_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    *,
    num_microbatches: int,
    axis_name: str = MeshConfig.AXIS_PIPE,
    mesh=None,
    remat: bool = True,
):
    """Run `x` through a stage-sharded block stack with a GPipe schedule.

    block_fn(stage_params_local, x_mb) -> y_mb applies ONE stage's blocks
    (leading dim of each `stage_params` leaf is the global stage count;
    locally each device sees its own slice). x: (batch, ...) with batch
    sharded over 'data'; output has the same shape as x (residual-stack
    contract). num_microbatches must divide the per-data-shard batch.
    """
    mesh = mesh or get_current_mesh()
    if mesh is None:
        raise ValueError(
            "pipeline_apply needs a mesh (set via parallel.ring.set_current_mesh)"
        )
    data_spec = P(MeshConfig.AXIS_DATA)  # batch dim over 'data', repl. over 'pipe'
    param_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    # manual ONLY over 'pipe' (stage hops) and 'data' (microbatch split):
    # every other mesh axis stays GSPMD-automatic inside the stage body, so
    # tensor-parallel parameter shardings (sharding_rules._vit_pipe_rule)
    # propagate into the per-stage matmuls and XLA inserts the Megatron
    # all-reduces over 'tensor' there — TP x PP without hand collectives
    fn = shard_map(
        functools.partial(
            _pipeline_local,
            block_fn=block_fn,
            num_mb=num_microbatches,
            axis_name=axis_name,
            remat=remat,
        ),
        mesh=mesh,
        in_specs=(param_spec, data_spec),
        out_specs=data_spec,
        axis_names=frozenset({axis_name, MeshConfig.AXIS_DATA}),
        check_vma=False,
    )
    # Boundary values stay fp32: XLA 0.9 CHECK-fails ("Invalid binary
    # instruction opcode copy") building any sub-fp32 psum over the manual
    # axes of a PARTIAL-manual shard_map — including the implicit psums
    # grad-transpose inserts for operands replicated over a manual axis
    # (activations are replicated over 'pipe', params over 'data'). Params
    # are already fp32 under the bf16 policy; activations are cast here and
    # per-tick (_pipeline_local), while block compute stays in the model's
    # dtype. Cost: ppermute hops carry fp32 — 2x ICI bytes on one
    # activation tensor per tick.
    in_dtype = x.dtype
    out = jax.jit(fn)(stage_params, x.astype(jnp.float32))
    # the scan-over-ticks body can't be evaluated eagerly inside shard_map;
    # jit is a no-op when already under an outer jit trace
    return out.astype(in_dtype)


def _pipeline_local(stage_params, x, *, block_fn, num_mb, axis_name, remat):
    # local param leaves are (1, ...) — this device's single stage slice
    params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), stage_params)
    n_stages = lax.psum(1, axis_name)  # trace-time constant
    idx = lax.axis_index(axis_name)
    batch = x.shape[0]
    if batch % num_mb != 0:
        raise ValueError(
            f"per-shard batch {batch} not divisible by microbatches {num_mb}"
        )
    mb = batch // num_mb
    xs = x.reshape((num_mb, mb) + x.shape[1:])

    apply_stage = jax.checkpoint(block_fn) if remat else block_fn
    # stage i sends to stage i+1; the wrap-around link carries garbage that
    # stage 0 immediately overwrites with the next fresh microbatch
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        t_in = jnp.clip(t, 0, num_mb - 1)
        inp = jnp.where(idx == 0, xs[t_in], state)
        # carry stays in the (fp32) boundary dtype — see pipeline_apply —
        # while the block computes in the model's own dtype
        y = apply_stage(params, inp).astype(x.dtype)
        t_out = t - (n_stages - 1)
        emit = jnp.logical_and(idx == n_stages - 1, t_out >= 0)
        t_out = jnp.clip(t_out, 0, num_mb - 1)
        cur = lax.dynamic_index_in_dim(outputs, t_out, axis=0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, y, cur), t_out, 0
        )
        state = lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    state0 = jnp.zeros((mb,) + x.shape[1:], x.dtype)
    out0 = jnp.zeros_like(xs)
    (_, outputs), _ = lax.scan(
        tick, (state0, out0), jnp.arange(num_mb + n_stages - 1)
    )
    # only the last stage holds real outputs; masked psum replicates them
    # over 'pipe' so downstream GSPMD code is stage-agnostic. The psum runs
    # in fp32: XLA (0.9 CPU backend) CHECK-fails building a sub-fp32
    # all-reduce when the shard_map is manual over a subset of mesh axes
    # ("Invalid binary instruction opcode copy"), and the upcast is free
    # here (one masked tensor, bandwidth-bound either way).
    masked = jnp.where(idx == n_stages - 1, outputs, jnp.zeros_like(outputs))
    outputs = lax.psum(masked.astype(jnp.float32), axis_name).astype(x.dtype)
    return outputs.reshape((batch,) + x.shape[1:])


def stack_stages(per_block_params, n_stages: int):
    """Reshape a depth-stacked params tree (leading dim = depth) into a
    stage-stacked tree (leading dim = n_stages, second dim = depth/n_stages)
    suitable for `pipeline_apply` with a block_fn that scans its local
    blocks."""

    def reshape(leaf):
        depth = leaf.shape[0]
        if depth % n_stages != 0:
            raise ValueError(f"depth {depth} not divisible by {n_stages} stages")
        return leaf.reshape((n_stages, depth // n_stages) + leaf.shape[1:])

    return jax.tree.map(reshape, per_block_params)
