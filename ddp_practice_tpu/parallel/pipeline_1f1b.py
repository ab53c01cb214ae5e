"""1F1B pipeline schedule: memory-bounded training over the 'pipe' axis.

The GPipe path (parallel/pipeline.py) runs all forwards as one scan and
lets XLA differentiate it — simple, but the scan transpose stashes one
boundary activation per tick, so training memory grows O(M + P) with the
microbatch count M. This module is the memory-bounded alternative the
scale story needs (reference has no pipeline at all — SURVEY §2.3): the
backward is NOT autodiff-of-scan; each backward microbatch runs as an
explicit `jax.vjp` inside the schedule, so the only cross-tick activation
state is a ring stash of the last 2P-1 stage INPUTS — O(P), independent
of M. Double the microbatches and GPipe's activation memory doubles;
this schedule's stays put.

Schedule ("eager 1F1B", one combined F+B tick):

- F(i, m) at tick i + m — the GPipe forward flood, unchanged;
- B(i, m) at tick 2(P-1) - i + m — each cotangent drains back the moment
  it exists: the LAST stage runs B(m) in the same tick as its input
  arrives (head + loss fold into its vjp, loss cotangent = 1), stage i
  one tick after stage i+1;
- total ticks T = M + 2(P-1) vs GPipe's fwd+bwd 2(M+P-1); in-flight
  microbatches at stage i are bounded by 2(P-1-i)+1 <= 2P-1 = the stash.

SPMD form mirrors _pipeline_local: ONE jitted program, partial-manual
shard_map over {'pipe', 'data'} (tensor/seq axes stay GSPMD-automatic
inside the stage body, so TP/SP compose exactly as in GPipe), activations
and cotangents hop via paired forward/backward `lax.ppermute`s every
tick. Within a tick, work is masked, not branched: every device executes
the same compute and gates results by schedule validity (collectives
would deadlock under divergent control flow, so masking is the safe SPMD
idiom). ACROSS ticks, validity is static — so the schedule is three
scans, not one (round 4): fill (first P-1 ticks, F-only — no stage has
a valid backward yet), steady (M-1 ticks, F+B), drain (last P ticks,
B-only — all forwards are done). Bubble ticks no longer pay the other
sub-phase's compute: fill skips the vjp re-run + head entirely, drain
skips the forward and its hop. The remaining (inherent) masking cost is
per-STAGE idle work inside valid ticks. The price vs GPipe at equal M is
the longer combined schedule; the purchase is O(P) activation memory.
BENCHMARKS.md records both sides of that trade, measured.

Boundary values (hops, stash, psums) stay fp32 — same JAX 0.9
partial-manual sub-fp32 psum CHECK-failure workaround as pipeline.py;
stage compute still runs in the model's own (bf16) dtype inside the vjp.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ddp_practice_tpu.config import MeshConfig
from ddp_practice_tpu.parallel.ring import get_current_mesh


def _head_cond(head_loss_fn, head_params, y_b, tgt, wgt, aux_shape,
               is_head):
    """The last-stage head+loss vjp under lax.cond — ONE definition for
    both schedules (plain 1F1B and interleaved). `is_head` is uniform
    across a device's tensor/seq shards, so GSPMD collectives inside the
    taken branch stay lockstep. Returns (loss_sum, aux, dhp, dy)."""
    f32 = jnp.float32

    def do_head(operands):
        hp_, y_ = operands
        loss_sum, h_vjp, aux = jax.vjp(
            lambda h, yy: head_loss_fn(h, yy, tgt, wgt),
            hp_, y_, has_aux=True,
        )
        dhp, dy = h_vjp(jnp.ones((), loss_sum.dtype))
        return loss_sum, aux, dhp, dy.astype(f32)

    def skip_head(operands):
        hp_, y_ = operands
        return (
            jnp.zeros((), f32),
            jax.tree.map(lambda a: jnp.zeros((), f32), aux_shape),
            jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), hp_),
            jnp.zeros_like(y_),
        )

    return lax.cond(is_head, do_head, skip_head, (head_params, y_b))


def _reduce_outputs(axis_name, dsp_acc, dhp_acc, loss_acc, aux_acc,
                    dxs_buf):
    """Final psums shared by both schedule kernels: grads/loss sum over
    'data'; last-stage-only values replicate over 'pipe' via the
    masked-psum idiom (accumulators are zero off their producing stage,
    so a plain psum IS the mask)."""
    data = MeshConfig.AXIS_DATA
    loss = lax.psum(loss_acc, (axis_name, data))
    aux = jax.tree.map(lambda a: lax.psum(a, (axis_name, data)), aux_acc)
    stage_grads = jax.tree.map(lambda g: lax.psum(g, data)[None], dsp_acc)
    head_grads = jax.tree.map(
        lambda g: lax.psum(g, (axis_name, data)), dhp_acc
    )
    dxs = lax.psum(dxs_buf, axis_name)
    return loss, aux, stage_grads, head_grads, dxs


def pipeline_1f1b_loss_and_grad(
    block_fn: Callable,
    head_loss_fn: Callable,
    stage_params,
    head_params,
    xs: jnp.ndarray,
    targets: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    num_microbatches: int,
    compute_dtype=jnp.float32,
    axis_name: str = MeshConfig.AXIS_PIPE,
    mesh=None,
):
    """Run the 1F1B schedule; return loss/metric sums, grads and dx.

    block_fn(stage_params_local, x_mb) -> y_mb: one stage's blocks
    (leading leaf dim of `stage_params` = global stage count, as in
    pipeline_apply). head_loss_fn(head_params, y_mb, targets_mb,
    weights_mb) -> (loss_sum, aux) applies the head and a SUM-reduced
    loss for one microbatch; `aux` is a pytree of fp32 SCALARS (e.g.
    weight and correct-prediction counts) accumulated across microbatches
    and summed over every axis. Deliberately scalars only: full logits
    would put an (M, mb, s, V) buffer in the scan carry of EVERY stage
    and a V-wide psum at the end — at real vocab sizes that single
    metrics buffer dwarfs the O(P) activation stash this schedule exists
    to provide.

    xs: (M, mb, ...) fp32 embedded activations, microbatch dim first,
    per-microbatch batch sharded over 'data'. targets/weights: (M, mb, s).

    Returns (loss_sum, aux_sums, stage_grads, head_grads, dxs
    (M, mb, ...)): loss/aux/grads summed over 'data' (and replicated over
    'pipe'); dxs keeps the microbatch layout for the caller to un-permute
    into its embedding vjp. Grads are of the loss SUM — divide by the
    caller's token count for mean-loss gradients.
    """
    mesh = mesh or get_current_mesh()
    if mesh is None:
        raise ValueError(
            "pipeline_1f1b needs a mesh (set via parallel.ring.set_current_mesh)"
        )
    data = MeshConfig.AXIS_DATA
    mb_spec = P(None, data)  # microbatch dim replicated, batch over 'data'
    param_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    head_spec = jax.tree.map(lambda _: P(), head_params)
    fn = shard_map(
        functools.partial(
            _1f1b_local,
            block_fn=block_fn,
            head_loss_fn=head_loss_fn,
            num_mb=num_microbatches,
            axis_name=axis_name,
            compute_dtype=compute_dtype,
        ),
        mesh=mesh,
        in_specs=(param_spec, head_spec, mb_spec, mb_spec, mb_spec),
        out_specs=(P(), P(), param_spec, head_spec, mb_spec),
        axis_names=frozenset({axis_name, data}),
        check_vma=False,
    )
    return jax.jit(fn)(
        stage_params, head_params, xs.astype(jnp.float32), targets, weights
    )


def pipeline_interleaved_loss_and_grad(
    block_fn: Callable,
    head_loss_fn: Callable,
    stage_params,
    head_params,
    xs: jnp.ndarray,
    targets: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    num_microbatches: int,
    num_virtual: int = 2,
    compute_dtype=jnp.float32,
    axis_name: str = MeshConfig.AXIS_PIPE,
    mesh=None,
):
    """Interleaved (virtual-stage) 1F1B — Megatron §2.2 on the masked-SPMD
    scan machinery.

    Same contract as pipeline_1f1b_loss_and_grad, except `stage_params`'
    leading leaf dim is S = num_virtual * P logical stages (stage
    s = v*P + i runs as chunk v on device i), and the schedule comes
    from constant tables (parallel/interleave.py: generated at trace
    time, dependency-validated by its own tests). Each device executes
    ONE chunk-op per tick (lax.cond picks the F or B body — `kind` is
    uniform across a device's tensor/seq shards, so collectives inside
    the branch stay lockstep); activations and cotangents ride the same
    single fwd/bwd ppermute pair per tick, with chunk-boundary hops
    (device P-1 -> 0 forward, 0 -> P-1 backward) carried by the ring
    wrap and re-keyed by the RECEIVER from the sender's table row. The
    purchase over plain 1F1B is the bubble: fill/drain ramps cost P
    ticks per chunk instead of P*V (measured table: P=4, M=8 idle
    fraction 0.273 -> 0.158 at V=2; BENCHMARKS.md schedule table)."""
    import numpy as np

    from ddp_practice_tpu.parallel.interleave import build_tables

    mesh = mesh or get_current_mesh()
    if mesh is None:
        raise ValueError(
            "pipeline_interleaved needs a mesh (set_current_mesh)"
        )
    P_ = mesh.shape[axis_name]
    V = num_virtual
    tables = build_tables(P_, V, num_microbatches)
    data = MeshConfig.AXIS_DATA
    mb_spec = P(None, data)
    # (S, ...) logical-stage params -> (P, V, ...): device i holds chunks
    # [i, P+i, ...] (stage s = v*P + i)
    def to_device_major(p):
        return jnp.swapaxes(
            p.reshape((V, P_) + p.shape[1:]), 0, 1
        )

    dev_params = jax.tree.map(to_device_major, stage_params)
    param_spec = jax.tree.map(lambda _: P(axis_name), dev_params)
    head_spec = jax.tree.map(lambda _: P(), head_params)
    fn = shard_map(
        functools.partial(
            _interleaved_local,
            block_fn=block_fn,
            head_loss_fn=head_loss_fn,
            num_mb=num_microbatches,
            num_virtual=V,
            axis_name=axis_name,
            compute_dtype=compute_dtype,
            kind_tab=tables.kind, chunk_tab=tables.chunk,
            mb_tab=tables.mb,
        ),
        mesh=mesh,
        in_specs=(param_spec, head_spec, mb_spec, mb_spec, mb_spec),
        out_specs=(P(), P(), param_spec, head_spec, mb_spec),
        axis_names=frozenset({axis_name, data}),
        check_vma=False,
    )
    loss, aux, dev_grads, head_grads, dxs = jax.jit(fn)(
        dev_params, head_params, xs.astype(jnp.float32), targets, weights
    )
    # back to (S, ...) logical-stage layout
    def to_stage_major(g):
        return jnp.swapaxes(g, 0, 1).reshape(
            (V * P_,) + g.shape[2:]
        )

    return loss, aux, jax.tree.map(to_stage_major, dev_grads), head_grads, dxs


def _interleaved_local(dev_params, head_params, xs, targets, weights, *,
                       block_fn, head_loss_fn, num_mb, num_virtual,
                       axis_name, compute_dtype, kind_tab, chunk_tab,
                       mb_tab):
    sp = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), dev_params)  # (V,...)
    n_stages = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    M, V = num_mb, num_virtual
    mb_shape = xs.shape[1:]
    T = kind_tab.shape[0]
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    f32 = jnp.float32
    kind_c = jnp.asarray(kind_tab)    # (T, P) int32 constants
    chunk_c = jnp.asarray(chunk_tab)
    mb_c = jnp.asarray(mb_tab)

    def fwd_chunk(sp_, v, x_):
        """One chunk's blocks, chunk picked by traced v via lax.switch
        (vjp flows only through the taken branch — off-chunk param
        grads come out zero, which is exactly the masked accumulate)."""
        return lax.switch(
            v,
            [
                (lambda xx, vv=vv: block_fn(
                    jax.tree.map(lambda p: p[vv], sp_),
                    xx.astype(compute_dtype),
                ).astype(f32))
                for vv in range(V)
            ],
            x_,
        )

    aux_shape = jax.eval_shape(
        lambda hp, y, t, w: head_loss_fn(hp, y, t, w)[1],
        head_params, jnp.zeros(mb_shape, f32), targets[0], weights[0],
    )

    def tick(carry, t):
        (act_buf, dy_buf, stash, dsp_acc, dhp_acc, loss_acc, aux_acc,
         dxs_buf) = carry
        krow = lax.dynamic_index_in_dim(kind_c, t, 0, False)   # (P,)
        crow = lax.dynamic_index_in_dim(chunk_c, t, 0, False)
        mrow = lax.dynamic_index_in_dim(mb_c, t, 0, False)
        my_k, my_v, my_m = krow[idx], crow[idx], mrow[idx]
        # buffers key on the raw microbatch index: interleaved in-flight
        # counts per (device, chunk) reach M (chunk 0's backwards all run
        # last), so the plain-1F1B 2P-1 ring would collide — O(M*V)
        # activation state is the documented Megatron trade for the
        # V-fold smaller bubble
        slot = jnp.clip(my_m, 0, M - 1)

        # ---- forward body (kind == 1) ----
        def do_f(ops):
            act_buf, stash, *_rest = ops
            x_in = jnp.where(
                (my_v == 0) & (idx == 0),
                lax.dynamic_index_in_dim(
                    xs, jnp.clip(my_m, 0, M - 1), 0, False
                ),
                act_buf[my_v, slot],
            )
            y = fwd_chunk(sp, my_v, x_in)
            stash = stash.at[my_v, slot].set(x_in)
            return y, stash

        def skip_f(ops):
            return jnp.zeros(mb_shape, f32), ops[1]

        y_f, stash = lax.cond(my_k == 1, do_f, skip_f, (act_buf, stash))
        y_hop = lax.ppermute(y_f, axis_name, fwd_perm)
        # receiver files the arrival under the SENDER's table row
        prev = (idx - 1) % n_stages
        sv = crow[prev]
        recv_v = jnp.where(idx == 0, sv + 1, sv)
        recv_ok = (krow[prev] == 1) & (recv_v < V)
        act_buf = jnp.where(
            recv_ok,
            act_buf.at[jnp.clip(recv_v, 0, V - 1),
                       jnp.clip(mrow[prev], 0, M - 1)].set(y_hop),
            act_buf,
        )

        # ---- backward body (kind == 2) ----
        def do_b(ops):
            dy_buf_, stash_ = ops
            x_b = stash_[my_v, slot]
            y_b, blocks_vjp = jax.vjp(
                lambda p_, x_: fwd_chunk(p_, my_v, x_), sp, x_b
            )
            tgt = lax.dynamic_index_in_dim(
                targets, jnp.clip(my_m, 0, M - 1), 0, False
            )
            wgt = lax.dynamic_index_in_dim(
                weights, jnp.clip(my_m, 0, M - 1), 0, False
            )
            is_head = (idx == n_stages - 1) & (my_v == V - 1)
            loss_m, aux_m, dhp_m, dy_head = _head_cond(
                head_loss_fn, head_params, y_b, tgt, wgt, aux_shape,
                is_head,
            )
            dy_ct = jnp.where(is_head, dy_head, dy_buf_[my_v, slot])
            dsp_m, dx_m = blocks_vjp(dy_ct)
            # f32 so both cond branches agree regardless of param dtype
            dsp_m = jax.tree.map(lambda g: g.astype(f32), dsp_m)
            return loss_m, aux_m, dhp_m, dsp_m, dx_m.astype(f32), is_head

        def skip_b(ops):
            return (
                jnp.zeros((), f32),
                jax.tree.map(lambda a: jnp.zeros((), f32), aux_shape),
                jax.tree.map(
                    lambda p: jnp.zeros(p.shape, p.dtype), head_params
                ),
                jax.tree.map(lambda p: jnp.zeros(p.shape, f32), sp),
                jnp.zeros(mb_shape, f32),
                jnp.asarray(False),
            )

        b_on = my_k == 2
        loss_m, aux_m, dhp_m, dsp_m, dx_m, is_head = lax.cond(
            b_on, do_b, skip_b, (dy_buf, stash)
        )
        bmask = b_on.astype(f32)
        dsp_acc = jax.tree.map(
            lambda a, gr: a + gr.astype(f32) * bmask, dsp_acc, dsp_m
        )
        dhp_acc = jax.tree.map(
            lambda a, gr: a + gr.astype(f32) * bmask, dhp_acc, dhp_m
        )
        emit = b_on & is_head
        loss_acc = loss_acc + jnp.where(emit, loss_m, 0.0)
        aux_acc = jax.tree.map(
            lambda a, v_: a + jnp.where(emit, v_.astype(f32), 0.0),
            aux_acc, aux_m,
        )
        dxs_buf = jnp.where(
            b_on & (idx == 0) & (my_v == 0),
            lax.dynamic_update_index_in_dim(
                dxs_buf, dx_m.astype(f32), jnp.clip(my_m, 0, M - 1), 0
            ),
            dxs_buf,
        )
        dx_hop = lax.ppermute(dx_m, axis_name, bwd_perm)
        nxt = (idx + 1) % n_stages
        rv = crow[nxt]
        recv_bv = jnp.where(idx == n_stages - 1, rv - 1, rv)
        recv_ok_b = (krow[nxt] == 2) & (recv_bv >= 0)
        dy_buf = jnp.where(
            recv_ok_b,
            dy_buf.at[jnp.clip(recv_bv, 0, V - 1),
                      jnp.clip(mrow[nxt], 0, M - 1)].set(dx_hop),
            dy_buf,
        )
        return (act_buf, dy_buf, stash, dsp_acc, dhp_acc, loss_acc,
                aux_acc, dxs_buf), None

    carry = (
        jnp.zeros((V, M) + mb_shape, f32),            # act inbox
        jnp.zeros((V, M) + mb_shape, f32),            # dy inbox
        jnp.zeros((V, M) + mb_shape, f32),            # stash
        jax.tree.map(lambda p: jnp.zeros(p.shape, f32), sp),
        jax.tree.map(lambda p: jnp.zeros(p.shape, f32), head_params),
        jnp.zeros((), f32),
        jax.tree.map(lambda a: jnp.zeros((), f32), aux_shape),
        jnp.zeros((M,) + mb_shape, f32),
    )
    carry, _ = lax.scan(tick, carry, jnp.arange(T))
    (_, _, _, dsp_acc, dhp_acc, loss_acc, aux_acc, dxs_buf) = carry
    return _reduce_outputs(
        axis_name, dsp_acc, dhp_acc, loss_acc, aux_acc, dxs_buf
    )


def _1f1b_local(stage_params, head_params, xs, targets, weights, *,
                block_fn, head_loss_fn, num_mb, axis_name, compute_dtype):
    sp = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), stage_params)
    n_stages = lax.psum(1, axis_name)  # trace-time constant
    idx = lax.axis_index(axis_name)
    M = xs.shape[0]
    assert M == num_mb, (M, num_mb)
    mb_shape = xs.shape[1:]
    W = 2 * n_stages - 1               # stash ring: max in-flight per stage
    T = M + 2 * (n_stages - 1)
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    f32 = jnp.float32

    def fwd(sp_, x_):
        return block_fn(sp_, x_.astype(compute_dtype)).astype(f32)

    def make_tick(do_f: bool, do_b: bool):
        """One schedule tick, specialized to its phase. Tick validity is
        STATIC per phase (round 4 fill/steady/drain split): fill ticks
        carry no valid B anywhere, drain ticks no valid F — so the
        specialized bodies simply omit that sub-phase's compute and hop
        instead of running it masked. Within a phase every device still
        executes the same program (collectives stay lockstep)."""

        def tick(carry, t):
            (stash, y_in, dy_in, dsp_acc, dhp_acc, loss_acc, aux_acc,
             dxs_buf) = carry

            if do_f:
                # ---- F sub-phase: stage i forwards microbatch t - i
                fm = t - idx
                f_valid = (fm >= 0) & (fm < M) & (idx < n_stages - 1)
                fm_c = jnp.clip(fm, 0, M - 1)
                x_f = jnp.where(
                    idx == 0,
                    lax.dynamic_index_in_dim(xs, fm_c, 0, False), y_in,
                )
                y_f = fwd(sp, x_f)
                stash = jnp.where(
                    f_valid,
                    lax.dynamic_update_index_in_dim(stash, x_f, fm_c % W, 0),
                    stash,
                )
                # activations hop forward; invalid slots carry garbage —
                # every consumer gates by its own schedule
                y_next = lax.ppermute(y_f, axis_name, fwd_perm)
            else:
                # drain: all forwards are done; the inbox must PERSIST —
                # the last stage consumes its final activation on the
                # first drain tick
                y_next = y_in

            if not do_b:
                # fill: no stage has a valid backward yet
                return (stash, y_next, dy_in, dsp_acc, dhp_acc, loss_acc,
                        aux_acc, dxs_buf), None

            # ---- B sub-phase: stage i backwards microbatch
            # t - (2(P-1) - i). Blocks re-run under jax.vjp on every
            # stage (that is the work); the vocab-wide head + loss runs
            # under lax.cond on the LAST stage only — `is_last` is
            # uniform across the 'tensor'/'seq' shards of a stage, so
            # GSPMD collectives inside the branch are taken (or skipped)
            # by every member of their group together. Elsewhere the
            # cotangent flows in from the next stage's B of the previous
            # tick.
            bm = t - (2 * (n_stages - 1) - idx)
            b_valid = (bm >= 0) & (bm < M)
            bm_c = jnp.clip(bm, 0, M - 1)
            is_last = idx == n_stages - 1
            # last stage consumes straight from its inbox (it never
            # forwards); a single-stage pipeline (last AND first) reads
            # the source batch
            x_b = jnp.where(
                is_last,
                jnp.where(
                    idx == 0,
                    lax.dynamic_index_in_dim(xs, bm_c, 0, False), y_in,
                ),
                lax.dynamic_index_in_dim(stash, bm_c % W, 0, False),
            )
            tgt = lax.dynamic_index_in_dim(targets, bm_c, 0, False)
            wgt = lax.dynamic_index_in_dim(weights, bm_c, 0, False)

            y_b, blocks_vjp = jax.vjp(fwd, sp, x_b)
            loss_m, aux_m, dhp_m, dy_head = _head_cond(
                head_loss_fn, head_params, y_b, tgt, wgt, aux_shape,
                is_last,
            )
            zero_f = jnp.asarray(0.0, f32)
            dy_ct = jnp.where(is_last, dy_head, dy_in)
            dsp_m, dx_m = blocks_vjp(dy_ct)

            bmask = b_valid.astype(f32)
            dsp_acc = jax.tree.map(
                lambda a, gr: a + gr.astype(f32) * bmask, dsp_acc, dsp_m
            )
            dhp_acc = jax.tree.map(
                lambda a, gr: a + gr.astype(f32) * bmask, dhp_acc, dhp_m
            )
            emit = b_valid & is_last
            loss_acc = loss_acc + jnp.where(emit, loss_m, zero_f)
            aux_acc = jax.tree.map(
                lambda a, v: a + jnp.where(emit, v.astype(f32), zero_f),
                aux_acc, aux_m,
            )
            dxs_buf = jnp.where(
                b_valid & (idx == 0),
                lax.dynamic_update_index_in_dim(
                    dxs_buf, dx_m.astype(f32), bm_c, 0
                ),
                dxs_buf,
            )

            # cotangents hop backward
            dy_next = lax.ppermute(dx_m.astype(f32), axis_name, bwd_perm)
            return (stash, y_next, dy_next, dsp_acc, dhp_acc, loss_acc,
                    aux_acc, dxs_buf), None

        return tick

    aux_shape = jax.eval_shape(
        lambda hp, y, t, w: head_loss_fn(hp, y, t, w)[1],
        head_params, jnp.zeros(mb_shape, f32), targets[0], weights[0],
    )
    carry = (
        jnp.zeros((W,) + mb_shape, f32),            # stash
        jnp.zeros(mb_shape, f32),                   # y inbox
        jnp.zeros(mb_shape, f32),                   # dy inbox
        jax.tree.map(lambda p: jnp.zeros(p.shape, f32), sp),
        jax.tree.map(lambda p: jnp.zeros(p.shape, f32), head_params),
        jnp.zeros((), f32),                         # loss sum
        jax.tree.map(lambda a: jnp.zeros((), f32), aux_shape),
        jnp.zeros((M,) + mb_shape, f32),            # dxs
    )
    # phase boundaries (static): the last valid F anywhere is stage P-2's
    # microbatch M-1 at tick M+P-3; the first valid B anywhere is the
    # last stage's microbatch 0 at tick P-1. fill = [0, P-2] F-only,
    # steady = [P-1, M+P-3] F+B, drain = [M+P-2, T-1] B-only. Lengths
    # (P-1) + (M-1) + P = T. Empty phases (P=1, M=1) scan zero ticks.
    P_ = n_stages
    fill_end = P_ - 1
    steady_end = M + P_ - 2
    carry, _ = lax.scan(make_tick(True, False), carry,
                        jnp.arange(0, fill_end))
    carry, _ = lax.scan(make_tick(True, True), carry,
                        jnp.arange(fill_end, steady_end))
    carry, _ = lax.scan(make_tick(False, True), carry,
                        jnp.arange(steady_end, T))
    (_, _, _, dsp_acc, dhp_acc, loss_acc, aux_acc, dxs_buf) = carry
    return _reduce_outputs(
        axis_name, dsp_acc, dhp_acc, loss_acc, aux_acc, dxs_buf
    )
