"""Where the entry points keep JAX's persistent compile cache.

Called by ``chip_smoke.py`` before its first compile — never at import
of ``dmlc_tpu``, so a library user's own cache settings stand.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; the
  directory is left alone.
- unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
  because the path is part of what a later run looks up.

Either way every compile is cached (JAX's default skips compiles under
one second, which is most of this repo's steps) unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
"""

from __future__ import annotations

import os
from typing import Any, Dict

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_MIN_SECS = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")

_stats = {"hits": 0, "misses": 0, "compile_s": 0.0}
_listening = False


def place_compile_cache() -> str:
    """Point JAX's persistent cache at its directory, start counting
    hits, misses and compile seconds, and return the directory."""
    global _listening
    import jax
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if ENV_MIN_SECS not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not _listening:
        _listening = True
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return jax.config.jax_compilation_cache_dir


def _on_event(event: str, **_: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _stats["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _stats["misses"] += 1


def _on_duration(event: str, secs: float, **_: Any) -> None:
    # wraps compile-or-fetch: on a hit this is the cache read time
    if event == "/jax/core/compile/backend_compile_duration":
        _stats["compile_s"] += secs


def cache_stats() -> Dict[str, Any]:
    """Hits, misses and backend compile seconds since
    :func:`place_compile_cache` was first called in this process."""
    import jax
    return {"dir": jax.config.jax_compilation_cache_dir, **_stats}
