"""The one place that decides where JAX's persistent compile cache lives.

A span-sort ladder takes ~25 s to compile cold on a TPU v5e (CHANGES.md
PR 21), so every module that jits imports this one before its first
``jax.jit``.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set  -> JAX reads it itself; nothing is set
  in code, so whoever launches the process places the cache.
* unset -> ``<checkout>/.jax_cache``, derived from this package's own path.
  The path is part of the cache key, so it is never a temp name, a pid or
  anything else that moves between runs; runner subprocesses import the
  same package and land on the same directory.

On an accelerator every compile is persisted, however short: JAX's default
floor of 1 s made a second identical run write "new" entries for kernels
that compiled in 0.9 s the first time and 1.1 s the second (chip run,
PR 21).  A process asked to run on the CPU keeps the floor — XLA:CPU
compiles in milliseconds and the tests would fill the directory.
"""
from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The compile-cache directory this process uses."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def cpu_requested() -> bool:
    """True when the CPU is the platform this process was ASKED to run on
    (``JAX_PLATFORMS=cpu`` or the same ``jax.config`` update) — as opposed
    to one JAX fell back to."""
    return (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu"


def entry_count() -> int:
    """Executables persisted so far (0 when the directory does not exist
    yet) — chip_smoke.py reports the growth as "compiled this run"."""
    try:
        return sum(1 for name in os.listdir(cache_dir())
                   if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


if not os.environ.get(_ENV):
    jax.config.update("jax_compilation_cache_dir", cache_dir())
if not cpu_requested():
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
