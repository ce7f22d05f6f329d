"""JAX persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py``,
``benchmarks/run.py`` and each benchmark script's ``__main__`` block,
never at library import: tests and library users keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory stands.  Otherwise the cache lives at one fixed path in the
checkout (``<repo>/.jax_cache``, git-ignored): the directory is part of
what a later run must find again, so it never depends on a temp name,
a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: the in-checkout cache directory used when the environment names none
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> Optional[str]:
    """The directory this module would set, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already names one."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    d = compile_cache_dir()
    if d is not None:
        jax.config.update("jax_compilation_cache_dir", d)
    # the window_reduce kernels compile in about a second: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d or os.environ["JAX_COMPILATION_CACHE_DIR"]
