"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable` once at start-up; importing this module changes nothing.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets no other directory.  Otherwise the cache lives in
``.jax_cache/`` at the root of the checkout (git-ignored): a fixed path,
because the path is part of what a later process must find again.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["ENV_VAR", "cache_dir", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the environment's, else the
    checkout's fixed ``.jax_cache/``."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT_DIR)


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
