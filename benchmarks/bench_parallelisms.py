"""Figs 10/12/15/16: data / model / ZeRO / hybrid parallel train steps.

Runs a reduced GPT-style model on an 8-device host mesh under four plans:
  dp8   : (8 data x 1 model), plain optimizer        (Fig 10)
  tp8   : (1 data x 8 model), tensor parallel        (Fig 12, InsightFace)
  zero8 : (8 data x 1 model), ZeRO master shards     (Fig 15)
  hyb   : (2 data x 4 model), ZeRO + tensor parallel (Fig 16)
derived: tokens/s and per-device param+optimizer bytes (the Fig 15 memory
comparison).
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys


def main():
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks._util import emit, timeit
    from repro.configs.registry import ARCHITECTURES
    from repro.launch.mesh import make_mesh
    from repro.train.steps import make_train_step

    cfg = dataclasses.replace(
        ARCHITECTURES["qwen3-1.7b"].reduced(),
        num_layers=4, d_model=256, num_heads=8, num_kv_heads=8, d_ff=1024,
        vocab_size=2048)
    B, S = 8, 128
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(B, S + 1)), jnp.int32)}

    plans = [
        ("dp8", (8, 1), False), ("tp8", (1, 8), False),
        ("zero8", (8, 1), True), ("hybrid_2x4", (2, 4), True),
    ]
    for name, (d_, m_), zero in plans:
        mesh = make_mesh((d_, m_), ("data", "model"))
        ts = make_train_step(cfg, mesh, zero=zero)
        params = ts.init_params(jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda p, s: jax.device_put(
                p, jax.sharding.NamedSharding(mesh, s)),
            params, ts.model_param_specs,
            is_leaf=lambda x: isinstance(x, (jnp.ndarray, np.ndarray)))
        if zero:
            params = ts.shard_params_fn(params)
        opt = ts.init_opt(params)

        def step(p, o):
            return ts.step_fn(p, o, batch)

        # run once for state, then time with fresh copies (donation!)
        def timed():
            p2 = jax.tree.map(jnp.copy, params)
            o2 = jax.tree.map(jnp.copy, opt)
            return ts.step_fn(p2, o2, batch)

        us = timeit(timed, iters=5, warmup=2)
        # per-device param + optimizer state bytes
        def bytes_per_dev(tree):
            total = 0
            for leaf in jax.tree.leaves(tree):
                if hasattr(leaf, "sharding"):
                    shard = leaf.sharding.shard_shape(leaf.shape)
                    total += int(np.prod(shard)) * leaf.dtype.itemsize
            return total

        mem = bytes_per_dev(params) + bytes_per_dev(opt)
        toks = B * S
        emit(f"parallelism/{name}", us,
             f"tok_s={toks/(us/1e6):.0f};state_bytes_per_dev={mem}")


if __name__ == "__main__":
    main()
