"""Sharding rules: param-path regex -> PartitionSpec (SURVEY.md §3 #13-14).

DP: every batch array is sharded on its leading dim over 'data'.
TP: transformer matmuls are sharded over 'model' by the rules below, keyed
on the param names in models/transformer.py.
EP: the stacked kernels of a routed-expert layer (models/glm_moe.py,
[experts held, in, out]) are sharded on their leading dim over 'expert'. No
mesh of this repo has that axis yet (parallel/mesh.py builds data x model x
seq), and an axis a mesh lacks is dropped from the spec: on one chip the
layer holds its share whole and runs without the exchange.
Everything unmatched is replicated. XLA propagates these annotations through
the whole program and inserts the ICI collectives (the reference's NCCL role,
BASELINE.json:5).
"""
from __future__ import annotations

import re
from typing import Any, List, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path-regex, spec). First match wins. Paths look like
# "params/page_tower/block0/attn/wq/kernel".
TP_RULES: List[Tuple[str, P]] = [
    # attention: qkv project model_dim -> heads (shard output/head dim)
    (r".*/attn/w[qkv]/kernel$", P(None, "model")),
    (r".*/attn/w[qkv]/bias$", P("model")),
    # attention output: heads -> model_dim (shard input/head dim)
    (r".*/attn/wo/kernel$", P("model", None)),
    # MLP in: model_dim -> mlp_dim (shard mlp dim)
    (r".*/(wi|wi_0|wi_1)/kernel$", P(None, "model")),
    (r".*/(wi|wi_0|wi_1)/bias$", P("model")),
    # MLP out: mlp_dim -> model_dim
    (r".*/wo_mlp/kernel$", P("model", None)),
    # token embedding: shard the embed dim (gather output stays sharded on
    # the feature axis, feeding the TP matmuls without a reshard)
    (r".*/tok_embed/embedding$", P(None, "model")),
    # routed experts: one chip's share is a slice of the expert dim
    (r".*/moe/w_(gate|up|down)$", P("expert", None, None)),
]


def _path_str(path: Tuple[Any, ...]) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def spec_for_param(path_str: str) -> P:
    for pattern, spec in TP_RULES:
        if re.match(pattern, path_str):
            return spec
    return P()


def param_shardings(params: Any, mesh: Mesh) -> Any:
    """Pytree of NamedSharding matching `params`. With mesh model=1 every
    rule degenerates to replication, so the same code path serves pure-DP."""
    def _one(path, _leaf):
        spec = spec_for_param(_path_str(path))
        return NamedSharding(mesh, P(*(
            a if a in mesh.axis_names else None for a in spec)))
    return jax.tree_util.tree_map_with_path(_one, params)


def put_global(x: Any, sharding: NamedSharding) -> jax.Array:
    """device_put that also works when `sharding` spans devices of OTHER
    processes (multi-host training): each process supplies its addressable
    shards from its local copy via make_array_from_callback. The host value
    must be identical on every process (true for seeded init and restored
    checkpoints — the only callers)."""
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def shard_params(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(put_global, params,
                                  param_shardings(params, mesh))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis 'data' sharding for every batch array (rank-agnostic:
    P('data') leaves trailing dims replicated)."""
    return NamedSharding(mesh, P("data"))


def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [K, B, ...] stack of K batches (the scan_steps fused
    dispatch): scan dim replicated, batch dim sharded over 'data'."""
    return NamedSharding(mesh, P(None, "data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
