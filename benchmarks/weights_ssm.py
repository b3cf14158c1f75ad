"""`weights.make_params` for the `granitemoehybrid` tower: drawn in float32
from the seed, leaf by leaf (the whole tree in float32 would be 19 GB at the
published widths), and rounded to bfloat16 where the configuration holds its
weights so; the leaves it names under `assumed.float32_leaves` stay float32.
The family's published initialisers, so that the decays are neither all 0 nor
all 1:

  kernel      N(0, 1/fan_in)          w_gate, w_up, w_down  N(0, 1/shape[-2])
  embedding   N(0, 1/width)           scale       1 + N(0, 0.02^2)
  A_log       log(U[1, 16])           D           ones
  dt_bias     the inverse softplus of a time step drawn log-uniform in
              [1e-3, 1e-1]            conv_kernel N(0, 1/d_conv)
  log_scale   log(temperature_init)   conv_bias, bias  N(0, 0.02^2)

The program and the plain reference are both handed these; the same key per
leaf index as `weights.py`.
"""
from __future__ import annotations

import functools
import math

from .weights import _key, path_str

STACKED = ("w_gate", "w_up", "w_down")


@functools.lru_cache(maxsize=None)
def _maker(name: str, shape: tuple, dtype: str, temperature: float):
    import jax
    import jax.numpy as jnp

    def make(key):
        noise = lambda: jax.random.normal(key, shape, jnp.float32)
        if name == "log_scale":
            val = jnp.full(shape, math.log(temperature), jnp.float32)
        elif name in STACKED:
            val = noise() / math.sqrt(shape[-2])
        elif name in ("kernel", "conv_kernel"):
            val = noise() / math.sqrt(shape[0])
        elif name == "embedding":
            val = noise() / math.sqrt(shape[-1])
        elif name == "scale":
            val = 1.0 + 0.02 * noise()
        elif name == "A_log":
            val = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                             16.0))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            val = step + jnp.log(-jnp.expm1(-step))
        elif name == "D":
            val = jnp.ones(shape, jnp.float32)
        else:   # bias, conv_bias
            val = 0.02 * noise()
        return val.astype(dtype)

    return jax.jit(make)


def leaf_dtype(path: str, ndim: int, weights_dtype: str,
               float32_leaves=()) -> str:
    """What the configuration holds the leaf at `path` in."""
    if weights_dtype == "float32" or ndim < 2 or any(
            path.endswith(s) for s in float32_leaves):
        return "float32"
    return weights_dtype


def make_params(shape_tree, seed: int, temperature_init: float = 20.0,
                weights_dtype: str = "float32", float32_leaves=()):
    """A tree like `shape_tree` (of ShapeDtypeStruct) filled from `seed`,
    one jitted call a leaf (one program per distinct name and shape)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    key = _key(seed)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        where = path_str(path)
        dtype = leaf_dtype(where, len(leaf.shape), weights_dtype,
                           tuple(float32_leaves))
        out.append(_maker(where.split("/")[-1], tuple(leaf.shape), dtype,
                          float(temperature_init))(jax.random.fold_in(key, i)))
    return jax.tree_util.tree_unflatten(treedef, out)
