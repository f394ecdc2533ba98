"""Persistent XLA compilation cache for the entry points.

A TPU compile takes seconds, and a campaign's pre-screen compiles once
per distinct task count, grid size and layer count. The entry points
(``python -m repro.sweep``, ``python -m repro.launch.train`` and
``chip_smoke.py``) call ``enable_compile_cache()`` before their first
compile, so a second run finds those programs on disk.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the path is part of what the cache is keyed on.

Nothing calls this on import: the refinement workers that a campaign
forks or spawns import no JAX (``exec/pool.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
