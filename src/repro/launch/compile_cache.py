"""JAX's persistent compilation cache, switched on in one place.

``enable()`` is called by the entry points that compile real work
(``launch.serve``, ``launch.train`` and ``chip_smoke.py``).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and no
other directory is set; otherwise it lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored).  The path is fixed on purpose:
a later run only finds what an earlier one wrote at the same path, so
a temp- or pid-derived directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
