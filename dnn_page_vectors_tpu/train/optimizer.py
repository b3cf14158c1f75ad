"""Optimizer + LR schedule (SURVEY.md §3 #11): adamw, warmup-cosine."""
from __future__ import annotations

import re
from typing import Optional

import jax
import optax

from dnn_page_vectors_tpu.config import TrainConfig

# the routed-expert layers' selection bias (models/glm_moe.py): it selects
# experts and never weighs them, so it has no gradient, and it is held
SELECT_BIAS = r".*/select_bias$"


def _decay_mask(no_decay: str):
    """A weight-decay mask for optax: False on leaves whose path matches."""
    def mask(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: re.match(no_decay, "/".join(
                str(getattr(k, "key", k)) for k in path)) is None, params)
    return mask


def make_optimizer(cfg: TrainConfig, no_decay: Optional[str] = None
                   ) -> optax.GradientTransformation:
    """`no_decay`: a regex of parameter paths that take no weight decay. A
    leaf without a gradient then gets an update of exactly zero (Adam's
    moments of a zero gradient are zero)."""
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=max(cfg.warmup_steps, 1),
        decay_steps=max(cfg.steps, cfg.warmup_steps + 1),
        end_value=cfg.learning_rate * 0.1,
    )
    if cfg.optimizer == "sgd":
        opt = optax.sgd(schedule)
    elif cfg.optimizer == "adamw":
        opt = optax.adamw(schedule, weight_decay=cfg.weight_decay,
                          mask=None if no_decay is None
                          else _decay_mask(no_decay))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    return optax.chain(optax.clip_by_global_norm(1.0), opt)
