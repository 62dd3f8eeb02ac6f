"""Random weights from the seed, in the program's parameter layout.

One jitted call makes every leaf on the device, in the dtype the cell
runs in. The references call the same function, so they see the same
numbers without taking anything the program made.

Matrices are N(0, 1/fan_in) with fan_in the leaf's second-to-last dim
(the embedding: N(0, 1/d_model)); QKV biases N(0, 0.1^2); norm scales 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf(k, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return (0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    fan_in = shape[-1] if name == "embedding" else shape[-2]
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def make(shapes, key: jax.Array, dtype=jnp.bfloat16, out_shardings=None):
    """A tree like ``shapes`` (a tree of ShapeDtypeStruct) filled from
    ``key``; leaf i draws from ``fold_in(key, i)``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        leaves = [_leaf(jax.random.fold_in(k, i), _path(p), s.shape, dtype)
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=out_shardings)(key)
