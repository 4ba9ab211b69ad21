"""Persistent XLA compilation cache for the entry-point scripts.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set this module
sets nothing. Otherwise the scripts keep their cache at one fixed
directory inside the checkout (``.jax_cache``, gitignored): the cache key
includes the path, so a directory that moves never hits. Tests leave the
cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax


def use_compile_cache(default_dir: pathlib.Path) -> None:
    """Cache compiled programs under ``default_dir`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already names a directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(default_dir))
