"""Persistent XLA compilation cache — ONE definition.

Shared by tests/conftest.py, scripts/cpu_mesh_run.py AND the production
entry points (`trainer.train_model`/`test_model` and the dtpu-agent's
built-in worker enable it by default, cfg.TRAIN.COMPILE_CACHE): identical
programs compile once per machine, not once per process per run. That is
what makes supervised restarts warm — a dtpu-agent relaunch resumes
training without paying the full step compile again, and the saved time
shows up directly in the journal's goodput. Cache interactions are
journaled through the existing obs compile counters
(``/jax/compilation_cache/*`` events in ``counters`` records;
``backend_compile_duration`` keeps counting true compiles only).

Call before the first computation (jax may already be imported; only
backend-touching work must come after).
"""

from __future__ import annotations

import os


def enable_persistent_cache(cache_dir: str | None = None) -> str:
    """Turn on jax's persistent on-disk compile cache and return its path.

    Whoever launches the process places the cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no
    directory is set in code — ``cache_dir`` (``cfg.TRAIN.COMPILE_CACHE_DIR``)
    does not override it. Where it is not set, ``cache_dir`` if given, else
    the fixed ``<checkout>/.cache/jax_compile`` next to the checkout this
    package was imported from (the path is part of the cache key, so it
    never moves). Idempotent.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if not cache_dir:
        root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        cache_dir = os.path.join(root, ".cache", "jax_compile")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
