"""Persistent compilation cache placement for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` first thing; nothing
calls it at import, so tests and library users keep JAX's defaults.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

#: the checkout root (``src/repro/compile_cache.py`` -> ``.``)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
#: size cap of the checkout's cache: an executable that compiles a full
#: table in as a constant (a ``DenseStore`` plan; on a v5e at full Criteo,
#: d=32, its step reports 870,556,160 B of generated code) can be close to
#: the table's size, while the tiered plans are tens of MB
MAX_BYTES = 1 << 30


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache``
    — a fixed path, so a later run from the same checkout finds what an
    earlier one compiled (the path is part of the cache key: a directory
    that moves never hits), capped at :data:`MAX_BYTES`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = _CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", MAX_BYTES)
    return str(path)
