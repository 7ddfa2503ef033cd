"""Seeded weights, made on the device in one jitted call.

The pytree layout and each leaf's shape and dtype are the program's (the
caller passes the abstract tree that the program's own init gives under
``jax.eval_shape``); every value comes from here. A leaf's values depend
only on the seed, its path and, for a layer leaf (leading axis =
layers), the layer index, so the plain reference makes any one layer's
weights again, in float32, without the program or its arrays.

  matrix (>= 2 dims)   N(0, 1) / sqrt(fan_in)   (fan_in = second-to-last)
  embedding table      N(0, 1) * 0.02
  norm scale (1 dim)   1 + 0.1 * N(0, 1)        (a scale that is not 1,
                                                 so a norm that drops it
                                                 is seen)
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

WEIGHT_SALT = 0x5EED


def base_key(seed: int):
    return jax.random.fold_in(jax.random.key(seed), WEIGHT_SALT)


def _path_str(path) -> str:
    parts = []
    for e in path:
        parts.append(str(getattr(e, "key", getattr(e, "idx", e))))
    return "/".join(parts)


def leaf_values(key, path: str, shape: Tuple[int, ...]):
    """One leaf's (or one layer's slice of a leaf's) values in float32."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    name = path.rsplit("/", 1)[-1]
    if len(shape) == 1:
        return 1.0 + 0.1 * z
    if name == "embed":
        return 0.02 * z
    return z / jnp.sqrt(jnp.float32(shape[-2]))


def _layer_leaf(key, path, shape, dtype):
    """A stacked (layers, ...) leaf, one layer at a time, each layer keyed
    by its index (lax.map keeps one float32 layer alive at a time)."""
    def one(i):
        return leaf_values(jax.random.fold_in(key, i), path,
                           shape[1:]).astype(dtype)
    return jax.lax.map(one, jnp.arange(shape[0]))


def make_params(seed: int, abstract_tree):
    """All weights for ``abstract_tree`` (ShapeDtypeStructs), in the dtypes
    it gives, in one jitted call on the default device. Leaves under
    ``layers/`` are stacked over the layers."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    specs = [(_path_str(p), tuple(x.shape), x.dtype) for p, x in flat]

    def build(key):
        leaves = []
        for path, shape, dtype in specs:
            if path.startswith("layers/"):
                leaves.append(_layer_leaf(key, path, shape, dtype))
            else:
                leaves.append(leaf_values(key, path, shape).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(base_key(seed))


def layer_weights(key, layer: int, paths: Dict[str, Tuple[int, ...]],
                  dtypes: Dict[str, object]):
    """One layer's weights as float32 arrays of the values served:
    ``paths`` maps a leaf path (under the layer root) to its per-layer
    shape; ``dtypes`` the dtype each is served in (values are rounded to
    it, then widened)."""
    k = jax.random.fold_in(key, layer)
    return {p: leaf_values(k, p, s).astype(dtypes[p]).astype(jnp.float32)
            for p, s in paths.items()}
