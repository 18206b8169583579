"""Where JAX keeps compiled programs between processes.

A cold serving process on the chip spends most of its start-up
compiling the engine ticks. JAX's persistent compilation cache keeps
those programs on disk, where the next process finds them only if the
path stays put: never a temporary or per-process directory.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed cache directory at the root of the checkout (git-ignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise accelerator compiles go to ``CACHE_DIR``. On
    the CPU backend (tests, rehearsals) nothing is cached: those
    compiles take about a second and the process stays hermetic.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


__all__ = ["CACHE_DIR", "enable_compile_cache"]
