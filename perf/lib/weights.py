"""Weights made on the device from the seed, in one jitted call.

The benchmark makes the weights, not the program: the system under test and
the plain reference are both handed these, so the reference takes nothing the
program has made. A leaf's distribution follows from its NAME in the tree the
program lays out (flax names), nothing else:

    kernel, embedding, pos_embed   normal, std 0.02 (GPT-2's and ViT's init)
    scale                          1 + 0.1 normal  (so a dropped LayerNorm
    bias                           0.02 normal      scale or bias shows)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def make_params(abstract, seed: int, *, dtype=None, shardings=None):
    """A tree like `abstract` (anything with .shape/.dtype leaves), filled
    from `seed`. `dtype` overrides the leaves' type (bf16 for serving);
    `shardings` is a matching tree of shardings or None. One normal draw of
    all the elements, cut into the leaves: one small program, not one
    generator a leaf."""
    import math

    leaves, _ = jax.tree_util.tree_flatten_with_path(abstract)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in leaves]
    shapes = [tuple(l.shape) for _, l in leaves]
    dtypes = [dtype or l.dtype for _, l in leaves]
    sizes = [math.prod(s) for s in shapes]

    def gen(key):
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, at = [], 0
        for name, shape, dt, n in zip(names, shapes, dtypes, sizes):
            leaf = z[at:at + n].reshape(shape)
            leaf = 1.0 + 0.1 * leaf if name == "scale" else 0.02 * leaf
            out.append(leaf.astype(dt))
            at += n
        return out

    out_sh = None
    if shardings is not None:
        out_sh = jax.tree_util.tree_leaves(shardings)
    flat = jax.jit(gen, out_shardings=out_sh)(seed_key(seed))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), flat)
