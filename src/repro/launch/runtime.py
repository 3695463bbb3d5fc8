"""Process-wide JAX runtime set-up shared by the entry points.

``enable_compile_cache`` gives every entry point (``chip_smoke.py``,
``launch/serve.py``) one persistent compilation cache. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on import and this
module sets no other directory; otherwise the cache lives at one fixed,
git-ignored path inside the checkout. The path is part of the cache's key,
so it must not move between runs: no temporary name, pid or timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    Touches no device, so a parent that must leave the chip to a child may
    call it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
