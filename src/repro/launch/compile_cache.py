"""JAX's persistent compilation cache for the entry points.

``launch/serve.py``, ``chip_smoke.py`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise, on a TPU, the cache sits at one fixed path
inside the checkout: the path is part of what a cache entry is found
by, so it never comes from a temp name, a pid or the time. On the CPU
nothing is set: XLA:CPU entries warn about target features when they
are loaded again, and a CPU run compiles in seconds.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ,
                      platform: str = "tpu") -> Optional[str]:
    """The directory to set in code: None when the environment already
    names one or the backend is not a TPU, else the fixed in-checkout
    default."""
    if environ.get(ENV) or platform != "tpu":
        return None
    return str(DEFAULT_DIR)


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent cache at ``compile_cache_dir()`` when the
    environment names none. Returns the directory set here, or None."""
    import jax
    path = compile_cache_dir(platform=jax.default_backend())
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
