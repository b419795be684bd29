"""Persistent XLA compilation cache for the command-line entry points.

A process that compiles the serving executables once can hand them to the
next process through JAX's persistent cache. Where the environment names a
directory (``JAX_COMPILATION_CACHE_DIR``), JAX reads it by itself and
nothing is set here. Otherwise the cache lives in ``.jax_cache/`` at the
checkout root: a fixed path, because the directory is part of what the
cache is found by, and a path that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
