"""Weights made on the device from the seed, one jitted draw a leaf.

`lib/weights.py` draws every element in ONE float32 normal and cuts it into
the leaves. At 4.65 billion elements (perf/configs/nemotron3_super_ep4.json)
the chip's compiler refuses that program: it wants 34 GB for the generator's
counter, on a 16 GB chip (PERF.md section 6, PR 26). Here each leaf is drawn
by itself from `fold_in(seed_key(seed), leaf index)`, so the most that is
alive beside the finished leaves is one leaf in float32 (1.4 GB for an
expert matrix stack). Same rule from a leaf's NAME to its distribution:

    scale                          1 + 0.1 normal
    everything else                0.02 normal

A configuration whose traffic file names the driver `serve_by_leaf` gets its
weights from here; the numbers differ from `lib/weights.py`'s for the same
seed (another stream), which no cell that exists sees.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perf.lib.weights import seed_key


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "is_scale"))
def _draw(key, *, shape, dtype, is_scale):
    z = jax.random.normal(key, shape, jnp.float32)
    return (1.0 + 0.1 * z if is_scale else 0.02 * z).astype(dtype)


def make_params(abstract, seed: int, *, dtype=None, shardings=None):
    """A tree like `abstract` (anything with .shape/.dtype leaves), filled
    from `seed`; `dtype` overrides the leaves' type. Handed a tree of
    ARRAYS (the calibration re-draws the weights of a built engine), each
    old leaf is deleted before its successor is drawn: two sets of a 9.3 GB
    model do not fit the chip."""
    if shardings is not None:
        raise ValueError("weights_by_leaf draws for one chip")
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    base, out = seed_key(seed), []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        shape, dt = tuple(leaf.shape), dtype or leaf.dtype
        if isinstance(leaf, jax.Array):
            leaf.delete()
        out.append(_draw(jax.random.fold_in(base, i), shape=shape,
                         dtype=jnp.dtype(dt), is_scale=name == "scale"))
    return jax.tree_util.tree_unflatten(treedef, out)
