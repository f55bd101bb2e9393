"""Where JAX keeps its persistent compilation cache.

A cold start on the chip compiles every chunk program again; the
persistent cache lets a later process (or run) reuse them. The path must
be fixed: an entry is found again only in the directory it was written
to, so a temporary name, a pid or a timestamp would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — src/repro/launch/ is three levels below the root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it. ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX
    itself reads it, and nothing else is set; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (git-ignored, never read as an input). Call
    it before the first compile: JAX fixes the cache at that point."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
