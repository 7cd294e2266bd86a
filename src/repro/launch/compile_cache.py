"""Persistent JAX compilation cache, placed from outside the library.

Entry points — the serving CLI (:func:`repro.launch.serve.main`),
``chip_smoke.py`` and the bench harness's ``bench_main`` — call
:func:`enable` before their first compile; library modules never touch
the cache setting.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here.  Otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
