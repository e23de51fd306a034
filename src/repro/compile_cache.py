"""Persistent XLA compilation cache for the repo's entry points.

A cold granite-3-2b run compiles one prefill program per prompt bucket, a
decode step and a chunk-extend program per bucket; the persistent cache
lets the next run load them instead.  Entry points call
:func:`enable_compile_cache` from ``main()`` -- never at import, so a
library user keeps control of JAX's global configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed location: the cache only hits when later runs look in the same
# directory, so the path carries no temporary name, pid or time.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins: nothing is set in code.  Otherwise the cache lives in
    ``<repo>/.jax_cache`` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
