"""Where JAX keeps its persistent compilation cache for this program.

A process that compiles the full-width serving step spends most of a cold
start compiling; the persistent cache lets the next process with the same
programs skip that.  The cache key includes the directory, so the
fallback is one fixed path inside the checkout, never a temporary name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else at ``<checkout>/.jax_cache``.  Call before the
    first compile, from an entry point's ``main`` (never at import).
    Returns the directory in use."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
