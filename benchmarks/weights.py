"""Weights made by the benchmark, on the device, from the seed, in one
jitted call. The program and the plain reference are both handed these, so
the reference takes nothing the program has made. The tree's structure and
shapes come from `jax.eval_shape` of the model's `init` (shapes only); the
values come from the leaf's name:

  kernel      N(0, 1/fan_in)        bias        N(0, 0.02^2)
  embedding   N(0, 1/width)         scale       1 + N(0, 0.02^2)
  pos_embed, rel_bias  N(0, 0.02^2) log_scale   log(temperature_init)
"""
from __future__ import annotations

import math


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _key(seed: int):
    import jax
    seed = int(seed)
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFF)
    return jax.random.fold_in(key, (seed >> 16) & 0xFFFFFF)


def make_params(shape_tree, seed: int, temperature_init: float = 20.0):
    """One jitted call: a tree like `shape_tree` (of ShapeDtypeStruct),
    float32, filled from `seed`."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = path_str(path).split("/")[-1]
            k = jax.random.fold_in(key, i)
            shape = leaf.shape
            noise = jax.random.normal(k, shape, jnp.float32)
            if name == "log_scale":
                val = jnp.full(shape, math.log(temperature_init), jnp.float32)
            elif name == "kernel":
                val = noise / math.sqrt(shape[0])
            elif name == "embedding":
                val = noise / math.sqrt(shape[-1])
            elif name == "scale":
                val = 1.0 + 0.02 * noise
            else:   # bias, pos_embed, rel_bias
                val = 0.02 * noise
            out.append(val)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(_key(seed))
