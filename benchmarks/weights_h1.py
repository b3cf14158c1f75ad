"""`weights.make_params` for the `falcon_h1` tower: drawn in float32 from the
seed, leaf by leaf, and rounded to bfloat16 where the configuration holds its
weights so (`weights_ssm.leaf_dtype`; the leaves the configuration names
under `assumed.float32_leaves` stay float32). Every leaf as `weights_ssm.py`
draws it (A_log, dt_bias, D, the convolution, the norm scales, the biases),
but for the matrices, which take a GAIN:

  <module>/kernel   N(0, gain^2 / fan_in)     gain = gains[<module>], else 1
  embedding         N(0, gain^2 / width)      gain = gains["embedding"]

A gain may be a list: one value for each run of the kernel's columns, the
runs' widths given by `segments[<module>]` (the mixer's `in_proj`, whose
columns are [z | x | B | C | dt]: a trained projection scales them apart, as
the published `ssm_multipliers` do).

The gains are part of the configuration (`assumed.gains`), because the
published multipliers presuppose trained scales: with N(0, 1/fan_in)
everywhere `key_multiplier` / sqrt(head_dim) makes every score about 0.01 and
the softmax uniform, so that rotary, the causal order and the grouped-query
map reach no compared number, and the out-multipliers bury two of the three
branches under the residual. The gains are chosen so that at layer 0 of a
seeded query the scores' standard deviation lies in 1-4 and each branch's
output is 0.1-1 of the residual's root mean square
(`reference/falcon_h1.py:branch_ratios`; the job prints the four).

The embedding (261,120 x 5,120: 5.3 GB in float32) is drawn in blocks of rows
inside one program, each rounded as it is made, so that set-up's peak stays
under the window's. The program and the plain reference are both handed these;
the same key per leaf index as `weights.py`.
"""
from __future__ import annotations

import functools
import math

from . import weights_ssm
from .weights import _key, path_str

_BLOCK_ELEMENTS = 1 << 26     # of a leaf drawn in blocks of rows


def _row_block(rows: int, width: int) -> int:
    """The largest divisor of `rows` whose block holds at most
    _BLOCK_ELEMENTS elements (at least one row)."""
    most = max(1, _BLOCK_ELEMENTS // width)
    return max(b for b in range(1, min(rows, most) + 1) if rows % b == 0)


@functools.lru_cache(maxsize=None)
def _matrix_maker(shape: tuple, dtype: str, std, widths: tuple = ()):
    """N(0, std^2) of `shape` in `dtype`, block of rows by block of rows;
    `std` a number, or one for each of the runs of columns `widths`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rows, width = shape[0], math.prod(shape[1:])
    block = _row_block(rows, width)
    if widths:
        if len(std) != len(widths) or sum(widths) != shape[-1]:
            raise ValueError(f"{len(std)} gains for runs {widths} of "
                             f"{shape[-1]} columns")
        std = np.repeat(np.asarray(std, np.float32), widths)

    def make(key):
        def body(i, out):
            part = std * jax.random.normal(jax.random.fold_in(key, i),
                                           (block,) + shape[1:], jnp.float32)
            return jax.lax.dynamic_update_slice_in_dim(
                out, part.astype(dtype), i * block, axis=0)
        return jax.lax.fori_loop(0, rows // block, body,
                                 jnp.zeros(shape, dtype))

    return jax.jit(make)


def make_params(shape_tree, seed: int, temperature_init: float = 20.0,
                weights_dtype: str = "float32", float32_leaves=(),
                gains: dict | None = None, segments: dict | None = None):
    """A tree like `shape_tree` (of ShapeDtypeStruct) filled from `seed`, one
    jitted call a leaf (one program per distinct name, shape and gain)."""
    import jax
    gains, segments = gains or {}, segments or {}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    key = _key(seed)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        where = path_str(path)
        parts = where.split("/")
        shape = tuple(leaf.shape)
        dtype = weights_ssm.leaf_dtype(where, len(shape), weights_dtype,
                                       tuple(float32_leaves))
        k = jax.random.fold_in(key, i)
        if parts[-1] == "kernel":
            gain, root = gains.get(parts[-2], 1.0), math.sqrt(shape[0])
            if isinstance(gain, (list, tuple)):
                out.append(_matrix_maker(
                    shape, dtype, tuple(g / root for g in gain),
                    tuple(segments[parts[-2]]))(k))
            else:
                out.append(_matrix_maker(shape, dtype, gain / root)(k))
        elif parts[-1] == "embedding":
            std = gains.get("embedding", 1.0) / math.sqrt(shape[-1])
            out.append(_matrix_maker(shape, dtype, std)(k))
        else:
            out.append(weights_ssm._maker(parts[-1], shape, dtype,
                                          float(temperature_init))(k))
    return jax.tree_util.tree_unflatten(treedef, out)
