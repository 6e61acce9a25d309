"""Where JAX keeps its persistent compilation cache.

`JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it on import and
this module sets no other directory. Otherwise the cache lives at a
fixed path inside the checkout (`<repo>/.jax_cache`, git-ignored), so a
second run of the same program finds its compiled chunks again. Call
`configure()` from an entry point before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point the persistent cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
