"""JAX's persistent compilation cache, set up in one place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the launchers in
``repro.launch``) call ``enable_compile_cache()`` before their first
compile.  Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  nothing here overrides it;
* unset: ``<repo>/.jax_cache`` — a fixed path, because the path is part of
  what a later run must find again (never a temporary, PID- or time-based
  directory).

Every executable is cached (no minimum compile time or entry size), so a
second run of the same programs deserializes instead of compiling.
Library code and tests never call this: the cache is the entry point's
decision.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Must run before the process's first compile: JAX latches the cache
    decision when it first uses it.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
