"""Where JAX's persistent compilation cache lives, for every entry point.

``launch/train.py``, ``examples/serve_llm.py`` and ``chip_smoke.py`` call
:func:`enable` before they compile anything. A cache entry's key includes
the directory, so the default is one fixed path inside the checkout,
never one built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` (git-ignored); this file is
#: ``<checkout>/src/repro/launch/compile_cache.py``.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when that is set, else at :data:`DEFAULT_DIR`; returns the directory."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
