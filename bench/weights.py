"""Seeded random weights in the program's parameter layout.

The benchmark makes the weights, not the program: one jitted call on
the device, from the seed, in the dtype they are served in.
The reference reads the same arrays (or makes them again from the same
seed), so it takes nothing that the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def jax_key(seed: int):
    """A PRNG key from any whole-number seed (seeds may exceed 31 bits)."""
    return jax.random.key(seed % (1 << 32))


def _leaf(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("scale", "q_norm", "k_norm"):
        v = 1.0 + 0.1 * z                       # norm gains
    elif name in ("bq", "bk", "bv"):
        v = 0.1 * z                             # projection biases
    elif name == "table":
        v = 0.02 * z                            # embedding (tied head)
    else:
        v = z / math.sqrt(shape[-2])            # matrices: fan-in scaled
    return v.astype(dtype)


def make_params(model, seed: int, dtype, out_shardings=None):
    """The program's parameter tree, every leaf drawn from ``seed``."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(key):
        keys = jax.random.split(key, len(paths))
        leaves = [_leaf(k, path[-1].key, sd.shape, dtype)
                  for (path, sd), k in zip(paths, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(gen, out_shardings=out_shardings)(jax_key(seed))
