"""Where compiled programs are kept between runs.

Every entry point (``cli.main``, ``lm_cli.main``, the fleet daemon,
``bench.py``, ``chip_smoke.py``, the test suite and its worker processes)
calls ``enable()`` once, before its first compile.  The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this code
  sets no cache directory at all — whoever runs the program places the
  cache.
- unset: the cache goes to ``DEFAULT_DIR``, one fixed directory at the root
  of the checkout (gitignored).  The directory's path is part of JAX's
  cache key, so a path made from a temporary name, a pid or the time would
  never hit; this one is the same for every process of every run.

A parent hands the cache to a child process it spawns through the
environment: ``child_env()``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def cache_dir() -> str:
    """The cache directory in effect for this process and its children."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable(min_compile_secs: float | None = None) -> str:
    """Turn the persistent compile cache on (see the module docstring for
    where) and return its directory.  ``min_compile_secs`` lowers JAX's
    threshold for what is worth keeping (the test suite's many small
    programs); None leaves JAX's own."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if min_compile_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_secs)
    return cache_dir()


def child_env(min_compile_secs: float | None = None) -> dict[str, str]:
    """Environment entries that give a spawned process this process's
    cache (code-set ``jax.config`` values do not cross an exec)."""
    env = {ENV_VAR: cache_dir()}
    if min_compile_secs is not None:
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = str(
            min_compile_secs)
    return env
