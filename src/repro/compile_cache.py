"""Where JAX keeps its persistent compilation cache.

The engine jit-compiles one program per bucketed shape, mostly
sub-second kernel programs.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``repro.launch.serve``, the examples) call
``configure_compile_cache()`` once, before their first compile, so that
a later process on the same machine reads those programs back instead
of compiling them again.  Importing this module changes nothing.

The cache is keyed by its directory, so the directory never moves: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module sets no other; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and let
    it keep programs of any compile time.  Returns the directory."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
