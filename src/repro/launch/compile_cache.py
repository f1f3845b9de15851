"""Persistent XLA compilation cache for the launch entry points.

``enable()`` is the first call of every ``main()`` under ``repro.launch``
and of ``chip_smoke.py``; nothing calls it on import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed here. Otherwise the cache lives at ``CACHE_DIR``, a fixed path
inside the checkout (listed in ``.gitignore``): the directory is part of
what a later process must find again, so it is never derived from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
