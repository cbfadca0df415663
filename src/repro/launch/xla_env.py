"""The persistent compilation cache of the entry points."""
from __future__ import annotations

import os


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here. Otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache`` — the path is part of the cache key, so a
    temporary or per-process directory would never hit. Called by the entry
    points (``chip_smoke.py``, :mod:`repro.launch.train`,
    :mod:`repro.launch.serve`), never on import.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import pathlib

    import jax

    repo = pathlib.Path(__file__).resolve().parents[3]
    jax.config.update("jax_compilation_cache_dir", str(repo / ".jax_cache"))
