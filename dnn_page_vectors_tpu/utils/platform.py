"""Process-level JAX set-up: the compile cache and the timing barrier.

The platform itself is chosen by the JAX_PLATFORMS environment variable
alone (unset: the TPU when one is attached; `cpu` for the tests).
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache — a FIXED path: the directory is part of the cache
# key, so a temp name, pid or timestamp in it would never hit. Listed in
# .gitignore.
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process. Where
    JAX_COMPILATION_CACHE_DIR is set, jax already reads it and nothing is
    set in code; otherwise the cache lives at `_REPO_CACHE`. Called once
    by every entry point (cli with its partition-worker command,
    chip_smoke.py, __graft_entry__) before the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)


def hard_sync(tree) -> None:
    """Timing barrier: returns once every array in `tree` is computed.
    `jax.block_until_ready` waits for the device on the v5e (chip_smoke.py's
    barrier phase re-checks it against the chip's peak on every run)."""
    import jax

    jax.block_until_ready(tree)
