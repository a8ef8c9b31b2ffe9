"""Where the persistent XLA compilation cache lives.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) calls ``enable_compile_cache()`` before its first
compile, so a second run in the same checkout reads its programs back
instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path inside the checkout (listed in
# .gitignore), so every run of the same checkout shares one cache
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax reads the variable itself
    and nothing is set here; otherwise the cache goes to
    ``REPO_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
