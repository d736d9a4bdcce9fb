"""Where JAX keeps its persistent compilation cache.

Entry points that compile the full model (``chip_smoke.py``,
``python -m repro.launch.train``, ``python -m benchmarks.run``) call
``use_compile_cache()`` before their first compile, so a second run of the
same program on the same machine reads its executables back instead of
compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path under the checkout (never a temporary name, a pid or a time:
# a cache that moves between runs is never hit)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    ``jax_compilation_cache_dir`` and nothing is set here; otherwise the
    cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
