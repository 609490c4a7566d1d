"""Process-wide kernel runtime settings: interpret mode and compile cache.

:func:`interpret_mode` is the one Pallas interpret-mode flag: True when
kernels should run through the Pallas interpreter (the CPU container)
and False the moment a real TPU/GPU backend is present -- so every
kernel, the fused bank megakernel included, lowers natively on the chip
without code changes.

Resolution order:

  1. ``REPRO_INTERPRET``  -- explicit override; "0"/"false"/"off" force
                             native lowering, anything else forces the
                             interpreter
  2. auto                 -- interpret on CPU, native on TPU/GPU

The decision is cached for the life of the process (kernels bake it
into their jit traces as a static argument); tests can re-evaluate the
environment via :func:`reset`.

:func:`enable_compilation_cache` turns on JAX's persistent compilation
cache for the entry points that run on the chip (``chip_smoke.py`` and
the benchmarks); tests never call it.
"""
from __future__ import annotations

import functools
import os
import pathlib

#: values that disable the interpreter when set in REPRO_INTERPRET
_FALSY = ("0", "false", "False", "no", "off")

#: jax backends with native Pallas lowering (no interpreter needed)
_NATIVE_BACKENDS = ("tpu", "gpu")

#: the checkout's own cache directory (git-ignored).  It must not move
#: between runs: the directory is part of what a cache hit needs.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


@functools.lru_cache(maxsize=1)
def interpret_mode() -> bool:
    """Should Pallas kernels run under ``interpret=True``?"""
    val = os.environ.get("REPRO_INTERPRET")
    if val is not None:
        return val not in _FALSY
    import jax
    return jax.default_backend() not in _NATIVE_BACKENDS


def reset() -> None:
    """Forget the cached decision (test hook: re-read the environment).

    Kernels that already traced with the old value keep their jit cache;
    callers re-reading :func:`interpret_mode` see the fresh decision.
    """
    interpret_mode.cache_clear()


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is used
    as it is; otherwise the cache lives in :data:`REPO_CACHE_DIR`.  The
    kernels here compile in about a second, under JAX's default
    minimum compile time for caching, so that minimum is lowered to 0.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
