"""Benchmark helpers: timing + multi-device subprocess execution.

Benchmarks print ``name,us_per_call,derived`` CSV lines. The main benchmark
process keeps the default single CPU device; anything needing N>1 devices
re-executes itself in a subprocess with the placeholder-device flag (same
policy as the tests)."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import time

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def timeit(fn, *args, iters: int = 10, warmup: int = 2):
    """Median wall time per call in microseconds (blocks on jax arrays)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def run_subprocess_bench(module: str, devices: int = 8,
                         timeout: float = 1200.0,
                         extra_env: dict = None):
    """Run ``python -m benchmarks.<module>`` with N placeholder devices and
    forward its CSV lines. ``extra_env`` adds/overrides environment entries
    (the smoke job sets ``BENCH_SMOKE=1`` this way).

    The children are CPU emulations by design, so they are pinned to the CPU
    backend: on an accelerator host the parent may already hold the chip,
    and a second process cannot open it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, "-m", f"benchmarks.{module}"],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=str(pathlib.Path(__file__).resolve().parent.parent))
    if proc.returncode != 0:
        raise RuntimeError(f"bench {module} failed:\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.count(",") >= 2 and not line.startswith("#"):
            print(line, flush=True)
