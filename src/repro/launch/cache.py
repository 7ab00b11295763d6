"""Persistent jit compilation cache wiring for the FL launchers.

Sweep workers and ``fl_sim`` re-trace the same round executables for
every (seed, scheme, partition) cell.  ``enable_jit_cache`` turns on
jax's persistent compilation cache so repeat launches (and sibling sweep
workers) hit disk instead of recompiling.

Where the cache lives, in order:

1. ``--jit-cache-dir DIR`` (``none`` disables the cache);
2. ``JAX_COMPILATION_CACHE_DIR`` when set — jax already reads it, and
   the program sets no other directory;
3. ``<checkout>/.jit-cache``, a fixed path resolved from this package's
   own location.  A cache directory is part of the cache key, so one
   that moved with ``--out`` or the cwd would never hit.

CPU compiles are fast and small, so the default persistence thresholds
(min compile seconds / min entry bytes) would skip everything — both are
forced to "always persist".
"""
from __future__ import annotations

import logging
import os
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/cache.py -> the checkout root
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jit-cache")


def resolve_cache_dir(arg: Optional[str]) -> Optional[str]:
    """The effective cache directory for ``--jit-cache-dir`` (``None``
    when the flag is absent): the flag's directory, else
    ``JAX_COMPILATION_CACHE_DIR``, else ``DEFAULT_CACHE_DIR``.  An
    explicit empty string or "none" disables caching (returns None)."""
    if arg is not None:
        if arg.strip().lower() in ("", "none", "off"):
            return None
        return arg
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


def enable_jit_cache(path: Optional[str]) -> Optional[str]:
    """Activate jax's persistent compilation cache at ``path`` (None
    turns it off).  A ``path`` equal to ``JAX_COMPILATION_CACHE_DIR`` is
    left as jax read it from the environment.

    Must run after jax import but before the first jit compilation.
    Returns the path (or None when disabled) for logging."""
    import jax
    if not path:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    jax.config.update("jax_enable_compilation_cache", True)
    if path != os.environ.get(CACHE_ENV):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # CPU executables compile in <1s and serialize small; the default
    # thresholds would persist nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    logger.info("persistent jit cache at %s", path)
    return path


def add_cache_arguments(ap) -> None:
    ap.add_argument("--jit-cache-dir", default=None, metavar="DIR",
                    help="persistent jit compilation cache directory "
                         f"(default: ${CACHE_ENV} when set, else "
                         ".jit-cache at the checkout root; 'none' "
                         "disables)")
