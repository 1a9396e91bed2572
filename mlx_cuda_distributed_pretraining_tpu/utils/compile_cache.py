"""JAX's persistent compilation cache, placed from outside the program.

One rule for every entry point that compiles (trainer ``main``, server
``main``, ``chip_smoke.py``): the cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, and JAX reads that variable itself, so
nothing here sets a directory then. With the variable unset the cache lives
at one fixed path inside the checkout. The path is part of a cache key, so
a directory that moves (a temp name, a pid, a time) never hits.

Called from the ``main()`` entry points only, never from ``Trainer``:
tests build trainers directly and the suite runs with the cache off
(tests/conftest.py sets ``JAX_ENABLE_COMPILATION_CACHE=false``, which
child processes inherit), so no test shares a directory with another.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def cache_dir() -> str:
    """Where the cache lives: the environment's word, else the checkout's."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def cache_entries() -> int:
    """Number of files in the cache directory (0 when it does not exist)."""
    try:
        return len(os.listdir(cache_dir()))
    except OSError:
        return 0


def enable_compilation_cache() -> str:
    """Turn the persistent cache on before the first compile; returns one
    status line for the caller's log. Initializes no backend.

    Off on multi-process CPU: executables deserialized from the cache lose
    their gloo collective state and corrupt the heap on first dispatch
    (reproducible: a cold fleet populates and trains fine, the next fleet
    sharing the cache aborts in glibc after step 1).
    """
    from jax.experimental.compilation_cache import compilation_cache

    from ..parallel.xla_flags import guess_backend

    if not jax.config.jax_enable_compilation_cache:
        return "compilation cache: off (jax_enable_compilation_cache is false)"
    if jax.distributed.is_initialized() and guess_backend() == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return ("compilation cache: disabled on multi-process CPU (cached "
                "executables do not survive gloo collective "
                "re-initialization)")
    path = cache_dir()
    entries = cache_entries()
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache everything: a crash-restart under the supervisor recompiles
    # exactly the programs worth persisting, however fast or small.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The cache object binds its directory on first use; drop one bound
    # earlier in this process so the settings above take effect.
    compilation_cache.reset_cache()
    state = "warm (cache hits expected)" if entries else "cold (will populate)"
    return f"compilation cache: {path} — {entries} entries, {state}"
