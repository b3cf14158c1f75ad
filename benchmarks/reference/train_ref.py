"""Plain reference of the contrastive train step: both towers forward, the
loss, the gradients and the optimizer's update (global-norm clip, AdamW with
linear warm-up then cosine decay), float32 at `highest` matmul precision.

Rows are processed in blocks so that the activations fit beside the state:
the towers run forward block by block to give the [B, D] vectors, the loss
and its gradient with respect to those vectors are taken on the whole batch
(in-batch negatives couple the rows), and each block's vector-Jacobian
product is added into the gradient. That is the same arithmetic as one
backward pass over the whole batch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import towers
from ..weights import path_str


def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up from 0 to the peak over `warmup_steps`, then cosine
    decay to `end_factor` of it at `decay_steps`."""
    peak, warm = opt["learning_rate"], max(opt["warmup_steps"], 1)
    if count < warm:
        return peak * count / warm
    span = max(opt["decay_steps"], warm + 1) - warm
    frac = min(max((count - warm) / span, 0.0), 1.0)
    end = peak * opt["end_factor"]
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * frac))


class TrainReference:
    def __init__(self, arch: dict, opt: dict, block_rows: int,
                 quant=towers.identity):
        self.arch, self.opt, self.block = arch, opt, block_rows
        fwd = functools.partial(
            towers.tower, variant=arch["variant"],
            num_layers=arch["layers"], num_heads=arch["heads"], quant=quant)
        self._fwd = jax.jit(fwd)

        def add_vjp(acc, p, ids, g):
            _, pull = jax.vjp(lambda tp: fwd(tp, ids), p)
            return jax.tree_util.tree_map(jnp.add, acc, pull(g)[0])

        self._add_vjp = jax.jit(add_vjp, donate_argnums=(0,))
        self._loss_grad = jax.jit(jax.value_and_grad(
            functools.partial(towers.contrastive_loss, quant=quant),
            argnums=(0, 1, 2)))
        self._update = jax.jit(self._update_fn, donate_argnums=(0, 1, 2))

    # -- gradients ----------------------------------------------------------
    def _blocks(self, n):
        return [(s, min(s + self.block, n)) for s in range(0, n, self.block)]

    def loss_and_grads(self, params: dict, query_ids, page_ids,
                       rows=None):
        """(loss, grads) of one batch. `rows` restricts the batch to those
        rows (the planted half-batch fault uses it); None = every row."""
        p = params["params"]
        if rows is not None:
            query_ids, page_ids = query_ids[rows], page_ids[rows]
        n = query_ids.shape[0]
        q = jnp.concatenate([self._fwd(p["query_tower"], query_ids[a:b])
                             for a, b in self._blocks(n)])
        pv = jnp.concatenate([self._fwd(p["page_tower"], page_ids[a:b])
                              for a, b in self._blocks(n)])
        loss, (gq, gp, gs) = self._loss_grad(q, pv, p["log_scale"])
        grads = {"log_scale": gs}
        for name, ids, g in (("query_tower", query_ids, gq),
                             ("page_tower", page_ids, gp)):
            acc = jax.tree_util.tree_map(jnp.zeros_like, p[name])
            for a, b in self._blocks(n):
                acc = self._add_vjp(acc, p[name], ids[a:b], g[a:b])
            grads[name] = acc
        return loss, {"params": grads}

    # -- optimizer ----------------------------------------------------------
    def init_opt(self, params):
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
        return zeros(), zeros()

    def _update_fn(self, params, mu, nu, grads, lr, count):
        o = self.opt
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree_util.tree_leaves(grads)))
        clip = jnp.minimum(1.0, o["clip_global_norm"]
                           / jnp.maximum(gnorm, 1e-30))
        grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
        mu = jax.tree_util.tree_map(
            lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
        nu = jax.tree_util.tree_map(
            lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, nu, grads)
        c1 = 1 - o["b1"] ** count
        c2 = 1 - o["b2"] ** count

        def step(p, m, v):
            upd = (m / c1) / (jnp.sqrt(v / c2) + o["eps"]) \
                + o["weight_decay"] * p
            return p - lr * upd

        return jax.tree_util.tree_map(step, params, mu, nu), mu, nu, clip

    def apply(self, params, mu, nu, grads, step_index: int):
        """One AdamW update; `step_index` counts from 0. Returns (params,
        mu, nu, the clip factor applied to the gradient)."""
        lr = learning_rate(self.opt, step_index)
        return self._update(params, mu, nu, grads, jnp.float32(lr),
                            jnp.float32(step_index + 1))


def leaf_norms(tree, minus=None) -> dict:
    """{path: L2 norm} of every leaf of `tree`, or of `tree - minus`, as
    Python floats."""
    def norms(t, m):
        xs = jax.tree_util.tree_leaves(t)
        ms = [0.0] * len(xs) if m is None else jax.tree_util.tree_leaves(m)
        return [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y))) for x, y in zip(xs, ms)]
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {path_str(p): float(v)
            for (p, _), v in zip(leaves, jax.jit(norms)(tree, minus))}
