"""JAX persistent compilation cache, placeable from outside.

Every entry point that drives the chip (``chip_smoke.py``, the
benchmark harness, a ``--platform default`` daemon) calls
:func:`enable` before its first compile. The directory is ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets it — JAX reads that variable itself, so no other
directory is set in code — and otherwise one fixed path inside the
checkout. The path is part of every cache key, so it is never derived
from a temp name, a pid or a time. Tests do not call this.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def cache_dir() -> str:
    """The directory the compile cache lives in (no side effects)."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Point JAX's persistent compile cache at :func:`cache_dir` and
    return it. Call before the first compile."""
    d = cache_dir()
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", d)
    return d
