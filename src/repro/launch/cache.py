"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``bench/run.py``,
``benchmarks/run.py``, the sweep-dispatch workers): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache goes to ``.jax_cache/`` at the root of
the checkout. The path is part of what a
process can find again, so it is fixed — never a temporary directory.
The cache only saves compile time: nothing the program computes depends
on what is in it.
"""

from __future__ import annotations

import os

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch``).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache(path: str | None = None) -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``path`` (default :data:`DEFAULT_CACHE_DIR`) applies only where
    ``JAX_COMPILATION_CACHE_DIR`` is unset. Every compilation is cached,
    however short, so a fresh worker process loads its chunk program
    instead of compiling it again.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = path or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
