"""Where the persistent XLA compile cache lives — decided from outside.

Every entry point that compiles (``cli.worker``, ``bench.py``, the chip
smoke's children, the example scripts) calls ``configure_compile_cache()``
once before its first jit. The directory is part of the cache key, so it
must not move between runs: no temp name, pid or time goes into it.

- ``JAX_COMPILATION_CACHE_DIR`` exported: jax reads it itself; nothing is
  set in code, and the cache is written there and nowhere else.
- otherwise: one fixed, git-ignored directory at the root of the checkout.

Only the directory is set. Whether the cache is ON stays with whoever owns
the process: ``tests/conftest.py`` turns it off for the CPU suite
(``jax_enable_compilation_cache=False``) and this helper leaves that alone.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def configure_compile_cache() -> str:
    """Place the compile cache (see module docstring); returns the
    directory in use."""
    exported = os.environ.get(ENV_VAR)
    if exported:
        return exported
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
