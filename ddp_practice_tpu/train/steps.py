"""Jitted train/eval step factories.

The whole per-batch sequence of the reference — H2D copy, autocast forward,
loss, zero_grad, scaled backward with overlapped gradient all-reduce, scaler
step/update (ddp_main.py:85-93, SURVEY §3.4) — compiles here into ONE XLA
program per step. Distribution is by sharding, not wrappers: with the batch
sharded over the 'data' mesh axis and params replicated (or TP-sharded),
XLA inserts and overlaps the gradient all-reduce that DDP's bucketing reducer
performs in C++ (ddp_main.py:121-123), and BatchNorm's batch-axis mean IS the
global-batch mean (the SyncBatchNorm contract, ddp_main.py:120) because the
mean of a 'data'-sharded axis lowers to a cross-replica reduction.

Eval returns weighted (correct, total) sums — the dist.reduce(SUM) pair of
ddp_main.py:108-109, but exact under padding (SURVEY §2.5).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax

from ddp_practice_tpu.ops.losses import accuracy_counts, cross_entropy
from ddp_practice_tpu.train.state import TrainState


def prepare_image(img):
    """On-device ToTensor: uint8 batches ride H2D at 1/4 the bandwidth and
    become [0,1] float here, where XLA fuses the scale into the first conv
    (the reference's `ToTensor()` runs on host CPU per sample,
    origin_main.py:89). float32 batches pass through untouched. True
    division, not *(1/255): x/255.0 and x*(1/255.0) differ by 1 ulp for
    168 of the 256 uint8 values, and bit-identity with a host-side
    .astype(float32)/255.0 corpus is part of the storage contract
    (data/datasets.py)."""
    if img.dtype == jnp.uint8:
        return img.astype(jnp.float32) / 255.0
    return img


def _sown_aux_loss(intermediates):
    """Sum every sown leaf whose name carries the "aux_loss" suffix (MoE
    load-balance); diagnostic sows (router health, activations) never
    leak into the objective."""
    return sum(
        jnp.sum(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            intermediates
        )[0]
        if "aux_loss" in jax.tree_util.keystr(path)
    )


def _moe_metrics(intermediates):
    """Router-health scalars from the MoE diagnostic sows (ops/moe.py):
    worst/best per-expert share of routed tokens (ideal = 1/E each) and
    the mean assignment-slot drop rate, aggregated over MoE layers."""
    fracs, drops = [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        intermediates
    )[0]:
        name = jax.tree_util.keystr(path)
        if "moe_load_frac" in name:
            fracs.append(jnp.ravel(leaf))
        elif "moe_drop_rate" in name:
            drops.append(jnp.ravel(leaf))
    out = {}
    if fracs:
        stacked = jnp.concatenate(fracs)
        out["moe_load_max"] = jnp.max(stacked)
        out["moe_load_min"] = jnp.min(stacked)
    if drops:
        out["moe_drop_rate"] = jnp.mean(jnp.concatenate(drops))
    return out


def _step_rngs(step, seed: int = 0):
    """Per-step RNGs for stochastic layers (dropout).

    Keyed on (run seed, global step): reproducible for a given --seed,
    decorrelated across seeds, deterministic across checkpoint resume
    (state.step restores), and identical under the per-step, chunked-scan,
    and device-resident drivers at the same step. Under GSPMD the key is
    replicated and the dropout mask is a global array — each device
    materializes only its shard."""
    return {"dropout": jax.random.fold_in(jax.random.PRNGKey(seed), step)}


def _apply_gradients(tx, grads, state: TrainState):
    """(new params, new optimizer state). What no module owns runs under a
    scope of its own, here `optimizer` and in the loss functions `loss`: a
    scope is a token in each device op's `op_name`, by which a profiler
    trace's time is given to the model's parts (perf/lib/scopes.py reads
    it, tests/test_scopes.py holds it). Metadata only: no op, shape or
    fusion changes with it."""
    with jax.named_scope("optimizer"):
        updates, new_opt_state = tx.update(
            grads, state.opt_state, state.params
        )
        return optax.apply_updates(state.params, updates), new_opt_state


def _grad_norm(grads):
    with jax.named_scope("optimizer"):
        return optax.global_norm(grads)


def _train_step_fn(model, tx, label_smoothing: float, seed: int = 0,
                   augment: bool = False):
    """The pure (state, batch) -> (state, metrics) function both the
    per-step and the scan-chunked factories jit."""

    def train_step(state: TrainState, batch):
        has_bn = state.batch_stats is not None
        images = prepare_image(batch["image"])
        if augment:
            # inside the jitted step, after the (resident) gather +
            # normalize; keyed on the global step so every driver variant
            # sees the same crops at the same step. `augment` is a kind:
            # True/"crop_flip" = pad-crop+flip, "rrc" = random resized
            # crop, the ImageNet rung (ops/augment.py)
            from ddp_practice_tpu.ops.augment import apply_augment, augment_rng

            images = apply_augment(
                images, augment_rng(seed, state.step), augment
            )

        def loss_fn(params):
            variables = {"params": params}
            mutable = ["intermediates"]  # routed layers sow aux losses here
            if has_bn:
                variables["batch_stats"] = state.batch_stats
                mutable.append("batch_stats")
            logits, updated = model.apply(
                variables, images, train=True,
                mutable=mutable, rngs=_step_rngs(state.step, seed),
            )
            new_stats = updated["batch_stats"] if has_bn else None
            inter = updated.get("intermediates", {})
            with jax.named_scope("loss"):
                loss = cross_entropy(
                    logits, batch["label"], label_smoothing=label_smoothing
                )
                loss = loss + _sown_aux_loss(inter)
            return loss, (logits, new_stats, inter)

        (loss, (logits, new_stats, inter)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        new_params, new_opt_state = _apply_gradients(tx, grads, state)
        with jax.named_scope("loss"):
            correct, total = accuracy_counts(logits, batch["label"])
        metrics = {
            "loss": loss,
            "accuracy": correct / total,
            "grad_norm": _grad_norm(grads),
            **_moe_metrics(inter),
        }
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    return train_step


def make_train_step(
    model,
    tx,
    *,
    label_smoothing: float = 0.0,
    seed: int = 0,
    augment: bool = False,
    mesh=None,
    state_shardings=None,
    batch_shardings=None,
):
    """Build the jitted train step.

    When mesh/shardings are given, they pin input/output layouts (GSPMD);
    the state buffer is donated so parameters update in place in HBM.
    """
    train_step = _train_step_fn(model, tx, label_smoothing, seed, augment)
    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        return jax.jit(
            train_step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, rep),
            donate_argnums=0,
        )
    return jax.jit(train_step, donate_argnums=0)


def stack_shardings(batch_shardings):
    """Sharding for (num_steps, batch, ...) stacked batches: leading scan
    dim replicated, inner dims as the per-batch shardings. Single source of
    truth for make_chunked_train_step, the Trainer, and prefetch_chunked
    callers — the jit in_shardings and the device_put layout must agree or
    every chunk pays a reshard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(sh):
        return NamedSharding(sh.mesh, P(None, *sh.spec))

    return jax.tree.map(
        one, batch_shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
    )


def make_chunked_train_step(
    model,
    tx,
    *,
    num_steps: int,
    label_smoothing: float = 0.0,
    seed: int = 0,
    augment: bool = False,
    mesh=None,
    state_shardings=None,
    batch_shardings=None,
):
    """Build a jitted K-steps-per-call train step: `lax.scan` over batches
    stacked on a leading (num_steps, ...) dim.

    For small models the per-step cost is host dispatch + H2D latency, not
    device compute (the reference pays the same per-step H2D, pinned-memory
    copies at origin_main.py:60-61); scanning K optimizer steps inside one
    XLA program amortizes both by K. Identical math to K calls of
    make_train_step. Returned metrics are the final step's.
    """
    step_fn = _train_step_fn(model, tx, label_smoothing, seed, augment)

    def chunk_step(state, batches):
        state, ms = jax.lax.scan(step_fn, state, batches)
        return state, jax.tree.map(lambda v: v[-1], ms)

    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        stacked = stack_shardings(batch_shardings)
        return jax.jit(
            chunk_step,
            in_shardings=(state_shardings, stacked),
            out_shardings=(state_shardings, rep),
            donate_argnums=0,
        )
    return jax.jit(chunk_step, donate_argnums=0)


def _lm_train_step_fn(model, tx, label_smoothing: float = 0.0, seed: int = 0,
                      with_accuracy: bool = True):
    """(state, batch) -> (state, metrics) for next-token language modeling.

    batch["tokens"] is (batch, seq+1) int32; position t predicts t+1 (the
    standard shifted objective). Optional batch["weight"] (batch, seq)
    masks padded positions out of the mean loss. Metrics report loss,
    perplexity (exp loss), next-token accuracy, and grad_norm — the LM
    equivalents of the image metrics in _train_step_fn.

    with_accuracy=False drops the per-step next-token accuracy from the
    metrics: its argmax is a full extra pass over the (tokens, vocab)
    logits (~1.7 ms/step at lm_base/32k vocab — round-4 profile), and the
    reference's own train loop computes loss only (train() at
    ddp_main.py:83-93; accuracy is the EVAL contract, ddp_main.py:96-112,
    which eval_step keeps exact). The bench uses the loss-only form."""

    def train_step(state: TrainState, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        weight = batch.get("weight")
        # lm_moe routers keep their aux-free balancing bias in
        # batch_stats (ops/moe.py MoEMlp) — threaded through the step
        # exactly like BatchNorm stats in the image step above
        has_stats = state.batch_stats is not None

        def loss_fn(params):
            variables = {"params": params}
            mutable = ["intermediates"]
            if has_stats:
                variables["batch_stats"] = state.batch_stats
                mutable.append("batch_stats")
            logits, updated = model.apply(
                variables, inputs, train=True,
                mutable=mutable,
                rngs=_step_rngs(state.step, seed),
            )
            new_stats = updated["batch_stats"] if has_stats else None
            inter = updated.get("intermediates", {})
            with jax.named_scope("loss"):
                loss = cross_entropy(
                    logits, targets, weight=weight,
                    label_smoothing=label_smoothing,
                )
                # MoE blocks (lm_moe) sow their load-balance loss + router
                # health here, exactly like the image step
                loss = loss + _sown_aux_loss(inter)
            return loss, (logits, new_stats, inter)

        if getattr(model, "schedule", None) in ("1f1b", "interleaved"):
            # memory-bounded pipeline: the model runs its own fwd+bwd
            # interleaving (parallel/pipeline_1f1b.py) — autodiff of the
            # forward would force the GPipe all-F-then-all-B order. The
            # accuracy counts come back as scalars (full logits would be
            # an O(batch*seq*vocab) metrics buffer inside the schedule)
            (loss, counts), grads = model.loss_and_grad(
                state.params, inputs, targets, weight=weight,
                label_smoothing=label_smoothing,
                with_accuracy=with_accuracy,
            )
            if counts is not None:
                correct, total = counts["correct"], counts["total"]
            else:
                correct, total = None, None
            inter = {}
            # pipelined LMs carry no non-param state; a future pipelined
            # MoE would need its router bias threaded through the
            # schedule, not silently dropped here
            assert state.batch_stats is None, (
                "1F1B schedule does not thread batch_stats"
            )
            new_stats = None
        else:
            (loss, (logits, new_stats, inter)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            if with_accuracy:
                with jax.named_scope("loss"):
                    correct, total = accuracy_counts(
                        logits, targets, weight=weight
                    )
            else:
                correct, total = None, None
        new_params, new_opt_state = _apply_gradients(tx, grads, state)
        metrics = {
            "loss": loss,
            "perplexity": jnp.exp(loss),
            "grad_norm": _grad_norm(grads),
            **_moe_metrics(inter),
        }
        if correct is not None:
            metrics["accuracy"] = correct / jnp.maximum(total, 1.0)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    return train_step


def make_lm_train_step(
    model,
    tx,
    *,
    label_smoothing: float = 0.0,
    seed: int = 0,
    mesh=None,
    state_shardings=None,
    batch_shardings=None,
):
    """Jitted next-token LM train step; sharding contract identical to
    make_train_step (batch leaves sharded over 'data' and — for sequence
    parallelism — the token dim over 'seq')."""
    train_step = _lm_train_step_fn(model, tx, label_smoothing, seed)
    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        return jax.jit(
            train_step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, rep),
            donate_argnums=0,
        )
    return jax.jit(train_step, donate_argnums=0)


def make_chunked_lm_train_step(
    model,
    tx,
    *,
    num_steps: int,
    label_smoothing: float = 0.0,
    seed: int = 0,
    mesh=None,
    state_shardings=None,
    batch_shardings=None,
):
    """K LM steps per dispatch (`lax.scan` over stacked token batches) —
    the dispatch-amortization scheme of make_chunked_train_step for the
    LM objective."""
    step_fn = _lm_train_step_fn(model, tx, label_smoothing, seed)

    def chunk_step(state, batches):
        state, ms = jax.lax.scan(step_fn, state, batches)
        return state, jax.tree.map(lambda v: v[-1], ms)

    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        stacked = stack_shardings(batch_shardings)
        return jax.jit(
            chunk_step,
            in_shardings=(state_shardings, stacked),
            out_shardings=(state_shardings, rep),
            donate_argnums=0,
        )
    return jax.jit(chunk_step, donate_argnums=0)


def make_lm_eval_step(model, *, mesh=None, state_shardings=None,
                      batch_shardings=None):
    """Jitted LM eval: weighted (correct, total) next-token counts plus
    summed token NLL — the LM analogues of the image eval contract
    (accuracy for the parity-visible print, NLL/total = perplexity)."""

    def eval_step(state: TrainState, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        variables = {"params": state.params}
        if state.batch_stats is not None:
            # lm_moe router balancing bias (read-only at eval)
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, inputs, train=False)
        correct, total = accuracy_counts(logits, targets)
        nll = cross_entropy(logits, targets) * total
        return correct, total, nll

    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        return jax.jit(
            eval_step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(rep, rep, rep),
        )
    return jax.jit(eval_step)


def _resident_gather(data, idx, batch_sharding=None):
    """Materialize one batch from the device-resident corpus: a gather of
    rows `idx` (B,) from each (N, ...) leaf. With the corpus replicated and
    `idx` sharded over 'data', GSPMD slices the index vector per device —
    each replica gathers only its rows, no collective.

    The sharding constraint + optimization_barrier pin the gathered batch
    to exactly the layout a host-fed batch has at the jit boundary
    (batch-dim sharded over 'data', materialized). Without them GSPMD may
    leave the batch replicated and fuse the gather into the first conv —
    BatchNorm's batch mean and the gradient reductions then partition
    differently and the resident path drifts bitwise from the host path it
    must mirror. Cost: one batch-sized buffer per step, negligible."""
    batch = {k: jnp.take(v, idx, axis=0) for k, v in data.items()}
    if batch_sharding is not None:
        batch = jax.lax.with_sharding_constraint(batch, batch_sharding)
    return jax.lax.optimization_barrier(batch)


def make_resident_train_step(
    model,
    tx,
    *,
    label_smoothing: float = 0.0,
    seed: int = 0,
    augment: bool = False,
    mesh=None,
    state_shardings=None,
):
    """Train G steps per jitted call against a device-RESIDENT dataset.

    `(state, data, idx)` where `data = {"image": (N,H,W,C) uint8, "label":
    (N,)}` lives in HBM (uploaded once per run) and `idx` is a (G, B) int32
    grid — one row per optimizer step. The scan body gathers its batch
    on device, so the only per-epoch host↔device traffic is the index grid
    (4·G·B bytes, ~240 KB for an MNIST epoch vs ~47 MB of pixels).

    This is the TPU-idiomatic endpoint of the reference's pinned-memory H2D
    pipeline (origin_main.py:96,60-61): for corpora that fit in HBM there is
    nothing left to transfer. Same math as G calls of make_train_step on
    the host-gathered batches (agreement to float noise — different XLA
    programs associate reductions differently; tests/test_resident.py).
    G is read from idx's shape — one factory serves any group size; each
    distinct G compiles once. Returned metrics are the final step's.
    """
    step_fn = _train_step_fn(model, tx, label_smoothing, seed, augment)
    bsh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        bsh = NamedSharding(mesh, P("data"))

    def resident_chunk(state, data, idx):
        def body(st, row):
            return step_fn(st, _resident_gather(data, row, bsh))

        state, ms = jax.lax.scan(body, state, idx)
        return state, jax.tree.map(lambda v: v[-1], ms)

    if mesh is not None and state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        idx_sh = NamedSharding(mesh, P(None, "data"))
        return jax.jit(
            resident_chunk,
            in_shardings=(state_shardings, rep, idx_sh),
            out_shardings=(state_shardings, rep),
            donate_argnums=0,
        )
    return jax.jit(resident_chunk, donate_argnums=0)


def make_resident_eval_step(model, *, mesh=None, state_shardings=None):
    """Eval G batches per jitted call against the device-resident corpus:
    scan over (idx, weight) (G, B) grids, summing weighted (correct, total)
    in-graph — same exact-under-padding contract as the host eval steps."""
    step_fn = _eval_step_fn(model)
    bsh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        bsh = NamedSharding(mesh, P("data"))

    def resident_eval(state, data, idx, weight):
        def body(carry, row):
            i, w = row
            batch = _resident_gather(data, i, bsh)
            batch["weight"] = w
            c, t = step_fn(state, batch)
            return (carry[0] + c, carry[1] + t), None

        zero = jnp.zeros((), jnp.float32)
        (correct, total), _ = jax.lax.scan(body, (zero, zero), (idx, weight))
        return correct, total

    if mesh is not None and state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        grid_sh = NamedSharding(mesh, P(None, "data"))
        return jax.jit(
            resident_eval,
            in_shardings=(state_shardings, rep, grid_sh, grid_sh),
            out_shardings=(rep, rep),
        )
    return jax.jit(resident_eval)


def _eval_step_fn(model):
    def eval_step(state: TrainState, batch):
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, prepare_image(batch["image"]), train=False)
        return accuracy_counts(logits, batch["label"], weight=batch["weight"])

    return eval_step


def make_eval_step(model, *, mesh=None, state_shardings=None, batch_shardings=None):
    """Build the jitted eval step: weighted (correct, total) counts."""
    eval_step = _eval_step_fn(model)
    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        return jax.jit(
            eval_step,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(rep, rep),
        )
    return jax.jit(eval_step)


def make_chunked_eval_step(
    model,
    *,
    num_steps: int,
    mesh=None,
    state_shardings=None,
    batch_shardings=None,
):
    """K eval batches per jitted call: `lax.scan` over a stacked
    (num_steps, batch, ...) input, summing (correct, total) in-graph.

    Same dispatch-amortization rationale as make_chunked_train_step — the
    reference's eval loop pays one launch + H2D per batch
    (ddp_main.py:101-107); here one call covers K batches. The weight
    field keeps padded-tail exactness identical to the per-batch step.
    """
    step_fn = _eval_step_fn(model)

    def chunk_eval(state, batches):
        def body(carry, batch):
            c, t = step_fn(state, batch)
            return (carry[0] + c, carry[1] + t), None

        zero = jnp.zeros((), jnp.float32)
        (correct, total), _ = jax.lax.scan(body, (zero, zero), batches)
        return correct, total

    if mesh is not None and state_shardings is not None:
        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        stacked = stack_shardings(batch_shardings)
        return jax.jit(
            chunk_eval,
            in_shardings=(state_shardings, stacked),
            out_shardings=(rep, rep),
        )
    return jax.jit(chunk_eval)


def _lm_window_gather(tokens, starts, window: int, batch_sharding=None):
    """Materialize one (B, window) token batch from the HBM-resident
    stream: a strided gather at `starts` (B,) offsets. Same layout-pinning
    rationale as _resident_gather (sharding constraint + barrier keep the
    resident path bitwise on the host path's program shape)."""
    batch = tokens[starts[:, None] + jnp.arange(window)[None, :]]
    if batch_sharding is not None:
        batch = jax.lax.with_sharding_constraint(batch, batch_sharding)
    return jax.lax.optimization_barrier(batch)


def make_resident_lm_train_step(
    model,
    tx,
    *,
    window: int,
    label_smoothing: float = 0.0,
    seed: int = 0,
    mesh=None,
    state_shardings=None,
):
    """LM counterpart of make_resident_train_step: the token STREAM (a 1D
    int32 array — megabytes where the image corpora are tens of MB) lives
    in HBM, and each scanned step gathers its (B, seq_len + 1) windows
    on device from a (G, B) grid of start offsets (LMDataLoader
    .epoch_plan). Per-epoch host→device traffic: the grid alone."""
    step_fn = _lm_train_step_fn(model, tx, label_smoothing, seed)
    bsh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        bsh = NamedSharding(mesh, P("data"))

    def resident_chunk(state, data, starts):
        def body(st, row):
            batch = {
                "tokens": _lm_window_gather(data["tokens"], row, window, bsh)
            }
            return step_fn(st, batch)

        state, ms = jax.lax.scan(body, state, starts)
        return state, jax.tree.map(lambda v: v[-1], ms)

    if mesh is not None and state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        grid_sh = NamedSharding(mesh, P(None, "data"))
        return jax.jit(
            resident_chunk,
            in_shardings=(state_shardings, rep, grid_sh),
            out_shardings=(state_shardings, rep),
            donate_argnums=0,
        )
    return jax.jit(resident_chunk, donate_argnums=0)


def make_resident_lm_eval_step(
    model, *, window: int, mesh=None, state_shardings=None
):
    """Eval G batches per call against the resident token stream: summed
    (correct, total, nll) over the scanned grid — the resident analogue of
    make_lm_eval_step's per-batch triple."""
    bsh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        bsh = NamedSharding(mesh, P("data"))

    def resident_eval(state, data, starts):
        def body(carry, row):
            tokens = _lm_window_gather(data["tokens"], row, window, bsh)
            inputs, targets = tokens[:, :-1], tokens[:, 1:]
            logits = model.apply(
                {"params": state.params}, inputs, train=False
            )
            c, t = accuracy_counts(logits, targets)
            s = cross_entropy(logits, targets) * t
            return (carry[0] + c, carry[1] + t, carry[2] + s), None

        zero = jnp.zeros((), jnp.float32)
        (correct, total, nll), _ = jax.lax.scan(
            body, (zero, zero, zero), starts
        )
        return correct, total, nll

    if mesh is not None and state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddp_practice_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        grid_sh = NamedSharding(mesh, P(None, "data"))
        return jax.jit(
            resident_eval,
            in_shardings=(state_shardings, rep, grid_sh),
            out_shardings=(rep, rep, rep),
        )
    return jax.jit(resident_eval)
