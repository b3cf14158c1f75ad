"""`weights.make_params` for the `qwen3_next` tower, in one jitted call,
float32, the same key per leaf index as `weights.py`. The published
initialisers where the family has its own, so that norms start where the
published model starts them and the decays are neither all 0 nor all 1:

  kernel         N(0, 1/fan_in)     w_gate, w_up, w_down  N(0, 1/shape[-2])
  conv_kernel    N(0, 1/taps)       embedding             N(0, 1/width)
  centred_scale  0 (the scale is 1 + w)
  scale          1 (the gated norm's w, not zero-centred)
  A_log          log(U[1, 16])
  dt_bias        the inverse softplus of a time step drawn log-uniform in
                 [1e-3, 1e-1] (`weights_ssm.py`'s, the Mamba-2 convention)
  log_scale      log(temperature_init)
  bias           N(0, 0.02^2)

`dt_bias` is not the published 1: with it a head decays by
exp(-A softplus(a + 1)) a token with A >= 1, nothing the state holds
outlives a chunk of 64, and a program that dropped the state at every chunk
boundary would read as correct (it moved a page's vector by 0.09%, against
39.5% with these time steps, whose slow heads carry the state across many
chunks). The program and the plain reference are both handed these.
"""
from __future__ import annotations

import math

from .weights import _key, path_str
from .weights_moe import STACKED


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
            k = jax.random.fold_in(key, i)
            noise = lambda: jax.random.normal(k, shape, jnp.float32)
            if name == "log_scale":
                val = jnp.full(shape, math.log(temperature_init), jnp.float32)
            elif name in STACKED:
                val = noise() / math.sqrt(shape[-2])
            elif name in ("kernel", "conv_kernel"):
                val = noise() / math.sqrt(shape[0])
            elif name == "embedding":
                val = noise() / math.sqrt(shape[-1])
            elif name == "centred_scale":
                val = jnp.zeros(shape, jnp.float32)
            elif name == "scale":
                val = jnp.ones(shape, jnp.float32)
            elif name == "dt_bias":
                step = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                val = step + jnp.log(-jnp.expm1(-step))
            elif name == "A_log":
                val = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                                 16.0))
            else:   # bias
                val = 0.02 * noise()
            out.append(val)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(_key(seed))
