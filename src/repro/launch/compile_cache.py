"""Where JAX keeps its persistent compilation cache.

Every entry point that may compile for the chip (``chip_smoke.py``,
``python -m repro.api``, ``repro.launch.train``/``serve``,
``benchmarks/run.py``) calls ``use_compile_cache`` before its first
compile, so a second run of the same programs loads them instead of
compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py → <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory
    and return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    wins: nothing is changed.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path with no process id, time or
    temporary name in it, so a later run finds what an earlier one
    wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
