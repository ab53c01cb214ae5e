"""The reference's side of a training check: follow the first steps.

Plain float32 AdamW (optax's `adamw` written out: bias-corrected moments,
decoupled weight decay, eps 1e-8) around a reference `loss(params, batch, cfg,
quant)`. The gradient of a mean loss over a batch is the mean of the gradients
of equal blocks of rows, so the batch is walked in blocks that fit beside
nothing else: the program's state is freed before this runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def leaf_norms(tree) -> np.ndarray:
    """L2 norm of every leaf, in tree-leaf order, float64 on the host."""
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(t)])(tree)
    return np.asarray(jax.device_get(norms), np.float64)


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _adamw(params, m, v, grads, t, lr, wd):
    def one(p, m_, v_, g):
        m_ = B1 * m_ + (1.0 - B1) * g
        v_ = B2 * v_ + (1.0 - B2) * g * g
        mhat = m_ / (1.0 - B1 ** t)
        vhat = v_ / (1.0 - B2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + EPS) + wd * p), m_, v_

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def follow(loss_fn, params0, batches, cfg: dict, *, lr: float, wd: float,
           block_rows: int, quant=None) -> dict:
    """Take `len(batches)` AdamW steps from `params0` (float32 tree).

    `batches` are dicts of host arrays whose leading axis is the row axis.
    Returns {"loss": [per step], "grad_norms": per-leaf norms of the FIRST
    step's gradient, "delta_norms": per-leaf norms of params_after -
    params0, "names": leaf names}.
    """
    vg = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, quant)))
    add = jax.jit(lambda a, b, w: jax.tree.map(lambda x, y: x + w * y, a, b))
    params = params0
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(params0), zeros(params0)
    losses, grad_norms = [], None
    for step, batch in enumerate(batches, start=1):
        rows = len(next(iter(batch.values())))
        acc, loss = zeros(params0), 0.0
        for r0 in range(0, rows, block_rows):
            blk = {k: jnp.asarray(a[r0:r0 + block_rows])
                   for k, a in batch.items()}
            w = len(next(iter(blk.values()))) / rows
            l, g = vg(params, blk)
            acc = add(acc, g, w)
            loss += float(l) * w
        losses.append(loss)
        if grad_norms is None:
            grad_norms = leaf_norms(acc)
        params, m, v = _adamw(params, m, v, acc, jnp.float32(step),
                              jnp.float32(lr), jnp.float32(wd))
    delta = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(
        params, params0)
    return {"loss": losses, "grad_norms": grad_norms,
            "delta_norms": leaf_norms(delta), "names": leaf_names(params0)}
