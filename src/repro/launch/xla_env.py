"""XLA environment setup: per-worker flags for the process-backed actor
runtime, and the persistent compilation cache of the entry points.

Each :class:`repro.runtime.process.ProcessRuntime` worker is a fresh spawned
interpreter, so it gets its own XLA client — the one chance to set
compile-time flags per *stage* rather than per job. This module must stay
importable **before** jax (no jax import at module level): the worker calls
:func:`apply_worker_env` first thing in ``_worker_main``, then the spec
builder's first jax touch picks the flags up.

The GPU flag set follows the standard latency-hiding recipe (async
collectives + latency-hiding scheduler + priority async stream) so that a
stage's cross-node sends overlap its compute; on CPU hosts the flags are
omitted — the CPU client rejects GPU-only options.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

# flags that let a pipeline stage overlap collective communication with
# compute (see jax gpu_performance_tips); applied only when the worker is
# actually going to use the gpu client
GPU_ASYNC_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _wants_gpu(env: Dict[str, str]) -> bool:
    plats = env.get("JAX_PLATFORMS", env.get("JAX_PLATFORM_NAME", ""))
    return "cuda" in plats or "gpu" in plats or "rocm" in plats


def worker_env(node: int, base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment overrides for the worker owning ``node``.

    The parent's ``XLA_FLAGS`` are inherited verbatim (this is how
    ``--xla_force_host_platform_device_count=N`` reaches every worker so a
    stage sees the same device table the driver planned against); GPU
    workers additionally get the async-collective flags appended.
    """
    base = dict(os.environ if base is None else base)
    flags = base.get("XLA_FLAGS", "").split()
    if _wants_gpu(base):
        for f in GPU_ASYNC_FLAGS:
            if f not in flags:
                flags.append(f)
    env: Dict[str, str] = {}
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    # workers share one host: don't let each grab the whole accelerator pool
    env.setdefault("XLA_PYTHON_CLIENT_PREALLOCATE", "false")
    env["REPRO_WORKER_NODE"] = str(node)
    return env


def apply_worker_env(node: int) -> None:
    """Install the per-worker environment. Must run before jax is imported
    in the worker process — XLA reads these at client construction."""
    if "jax" in __import__("sys").modules:  # pragma: no cover - guard only
        # too late for XLA_FLAGS to matter; don't silently pretend otherwise
        os.environ["REPRO_WORKER_NODE"] = str(node)
        return
    os.environ.update(worker_env(node))


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
