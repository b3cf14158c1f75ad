"""`weights.make_params` for a tower with routed experts. `weights.py` scales
a `kernel` by `shape[0]`, which for a stacked `[experts held, in, out]` leaf
would be the expert count. Here the stacked kernels (`w_gate`, `w_up`,
`w_down`) get N(0, 1/fan_in) with fan_in `shape[-2]`; the router's kernel is
a plain `kernel`, N(0, 1/hidden); the selection bias `select_bias` gets
N(0, 0.02^2) and is held; every other leaf is made as `weights.py` makes it,
from the same key per leaf index."""
from __future__ import annotations

import math

from .weights import _key, path_str

STACKED = ("w_gate", "w_up", "w_down")


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
            shape = leaf.shape
            noise = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
            if name == "log_scale":
                val = jnp.full(shape, math.log(temperature_init), jnp.float32)
            elif name in STACKED:
                val = noise / math.sqrt(shape[-2])
            elif name == "kernel":
                val = noise / math.sqrt(shape[0])
            elif name == "embedding":
                val = noise / math.sqrt(shape[-1])
            elif name == "scale":
                val = 1.0 + 0.02 * noise
            else:   # bias, select_bias
                val = 0.02 * noise
            out.append(val)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(_key(seed))
