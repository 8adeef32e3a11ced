"""The one owner of JAX's persistent compilation cache.

Nothing else in the tree touches ``jax_compilation_cache_dir`` (a tier-1
test greps for it). Entry points that compile — tests/conftest.py,
chip_smoke.py, benchmark/run.py, benchmarks/aot_scale.py, serve/worker.py — call
:func:`enable_compile_cache` once, before their first compile.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: there. JAX reads the variable
  itself, so no directory is set in code — the operator (or the chip
  tool) placed the cache and it stays placed.
* unset: ``.jax_cache/`` at the root of this checkout (gitignored). A
  fixed path inside the tree, so every process started from one checkout
  shares it and nothing is written outside the repository.
"""

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything that took real compile time, however small
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
