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

The same call counts what the compiler does (``compile_counters``): jax's
own monitoring events, so a compile under the persistent cache's time
threshold, which never becomes a cache entry, is counted too.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


# process-wide, like the event stream they count; written only by jax's
# monitoring callbacks (under the GIL), read by ``compile_counters``
_COUNTS = {"backend_compiles": 0, "backend_compile_s": 0.0,
           "cache_hits": 0, "cache_misses": 0}
_EVENT_KEYS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_installed = False


def _on_event(event: str, **_kw) -> None:
    key = _EVENT_KEYS.get(event)
    if key is not None:
        _COUNTS[key] += 1


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        _COUNTS["backend_compiles"] += 1
        _COUNTS["backend_compile_s"] += duration_secs


def install_compile_counters() -> None:
    """Register the ``jax.monitoring`` listeners once per process."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def compile_counters() -> dict:
    """What the compiler has done in this process since the listeners went
    in. ``backend_compiles`` / ``backend_compile_s`` count every program
    jax asked the backend for — one per new program shape, whether XLA
    compiled it or the persistent cache supplied it (``cache_hits``);
    ``cache_misses`` are the ones XLA compiled and the cache then stored."""
    install_compile_counters()
    return dict(_COUNTS)


def configure_compile_cache() -> str:
    """Place the compile cache (see module docstring) and start the
    compile counters; returns the directory in use."""
    install_compile_counters()
    exported = os.environ.get(ENV_VAR)
    if exported:
        return exported
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
