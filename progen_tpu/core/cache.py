"""Persistent XLA compilation cache.

A fresh process pays over a minute to compile ProGen-small's training
programs (78 s on a v5e chip, 10 s resumed from this cache; 521 s for the
36-layer ProGen-large step over four chips — chip_smoke.py, PR 21).  JAX
can persist compiled executables to disk; enabling it makes restarts and
resume-after-preemption start in seconds.

Off by default inside the library (libraries should not write to disk
unasked); the CLIs and worker processes call
:func:`enable_compilation_cache` at startup.  Where the cache lives is
decided from OUTSIDE the program: ``JAX_COMPILATION_CACHE_DIR``, which JAX
reads itself, places it; unset, it is one fixed directory inside the
checkout.  The path is part of nothing the program computes — never the
home directory, a temporary name, a pid or the time — so every process of
a run, and every later run from the same checkout, finds the same entries.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (git-ignored): progen_tpu/core/cache.py -> up 3
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn on JAX's on-disk compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own handling of it
    stands and no directory is set in code.  Otherwise the cache is
    :data:`DEFAULT_CACHE_DIR`.  A directory that cannot be made raises:
    a run that silently went uncached would report minutes of compile as
    if they were the program's.  Safe to call more than once and before
    any backend initialization.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything that took meaningful compile time; tiny programs
    # are cheaper to recompile than to hash+read
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
