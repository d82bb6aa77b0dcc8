"""JAX's persistent compilation cache, switched on by the entry points.

Call ``enable()`` from a program's ``main`` (``chip_smoke.py``,
``launch/serve.py``, ``transport/worker.py``), never at import: a library
import must not change the process's JAX configuration.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache lives in ``.jax_cache/`` at the root of
the checkout: a fixed path, because the path is part of what a later run
looks the cache up by.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = ".jax_cache"


def checkout_root() -> str:
    """The directory that holds ``src/`` (this file is src/repro/launch/)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    path = os.path.join(checkout_root(), CHECKOUT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
