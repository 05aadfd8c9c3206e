"""Process-level JAX setup shared by every entry point.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``python -m
repro.api``, ``python -m repro.serve``, the ``benchmarks/`` mains) call
:func:`setup_jax` first thing in their ``main``; library code never does,
so importing the package changes no global JAX state.
"""
from __future__ import annotations

import os
import pathlib

#: The checkout root (this file is ``<root>/src/repro/launch/runtime.py``).
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
#: Where the persistent compilation cache goes unless
#: ``JAX_COMPILATION_CACHE_DIR`` names another place.  A fixed path: the
#: directory is part of what a cached executable is found under.
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def setup_jax() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and no other directory is set here; otherwise the cache
    lives in ``<checkout>/.jax_cache``, which git ignores.  Also keeps the
    TPU runtime's logs out of ``/tmp`` unless ``TPU_LOG_DIR`` says
    otherwise.  Call before the first computation.
    """
    import jax

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
