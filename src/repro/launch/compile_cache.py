"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable` first thing, so a second run of the
same program on the same machine loads its executables instead of
compiling them again.  Library modules never call it: importing them
(the tests do) must not start writing a cache.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed path: the cache is keyed by program, so a directory that moves
# between runs is a cache that never hits.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and
    is left alone; otherwise the cache is ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
