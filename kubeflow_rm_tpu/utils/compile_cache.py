"""Where compiled programs are kept between processes.

Every entry point (``chip_smoke.py``, ``bench.py``, the ``examples/``
mains) calls
``enable_compile_cache()`` before its first jit, so a second process on
the same machine finds the first one's programs instead of compiling
them again — on the chip that is over a minute per run.

The directory can be placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and this module
sets nothing. Otherwise the cache sits at ``<checkout>/.jax_cache``, a
path fixed by this file's own location — never a temp dir, a pid or the
time, because the path is part of what the cache is keyed on and a
directory that moves never hits.
"""

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.
    Touches ``jax.config`` only — no backend is initialised."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
