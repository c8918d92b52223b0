"""JAX's persistent compilation cache for the program's entry points.

Compiling the fused kernels and the jitted forward is a large part of a
cold start on a TPU.  ``enable_compile_cache`` is called at the start of
each ``main()`` (``chip_smoke.py``, ``repro.launch.spectral_serve``,
``benchmarks/e2e_latency.py``, ``benchmarks/serve_bench.py``) — never
at import time, so importing the library changes no JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path at the checkout root (gitignored).  The path is part of
#: the cache key, so it is never derived from a temporary name, a
#: process id or the time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    its cache directory and nothing else is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
