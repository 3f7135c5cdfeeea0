"""Persistent XLA compilation cache for the serving entry points.

A cold 16-layer packed step takes tens of seconds to compile on a TPU, and
every fresh process pays it again unless compiled programs persist.  JAX
keys cache entries partly on the cache path, so the path must not move
between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names
(JAX reads that variable itself; nothing is set in code then) or the fixed
``<checkout>/.jax_cache`` directory (listed in ``.gitignore``).

Called from the entry points' ``main`` (``chip_smoke.py``,
``repro.launch.serve``, ``examples/serve_*.py``) — never at import time
and never from tests, whose compiles must not be written to disk.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
