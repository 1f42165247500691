"""Weights from the run seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program is handed
them, and the reference reads the same arrays. The tree has the layout of
the program's parameters (its leaves' paths and shapes), each leaf drawn
from ``fold_in(key(seed), leaf index)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        x = 1.0 + 0.1 * z                        # norm scales
    elif name in ("bq", "bk", "bv"):
        x = 0.1 * z                              # projection biases
    elif name == "embed":
        x = z * shape[-1] ** -0.5                # (vocab, d)
    else:
        x = z * shape[-2] ** -0.5                # (..., fan_in, fan_out)
    return x.astype(dtype)


def make(abstract, seed: int):
    """``abstract``: the program's parameter tree as ShapeDtypeStructs."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        leaves = []
        for i, (path, sd) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            leaves.append(_leaf(jax.random.fold_in(key, i), name, sd.shape,
                                sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(build)(jax.random.key(seed)))
