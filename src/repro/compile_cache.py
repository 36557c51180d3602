"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that compile for the chip call :func:`configure` once, before
their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout (git-ignored): the cache directory is part
# of what lets a later process find an entry, so it must not move per run
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at a stable directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the root
    of the checkout.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
