"""Fig 13 (HugeCTR / Wide&Deep): model-parallel embedding lookup.

Vocab-split (S(0)) embedding with masked-gather + P(sum) combine vs
replicated-table lookup, on an 8-way model axis. derived: per-device table
bytes (the Fig 13 memory story: S(0) scales the vocab, B does not)."""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks._util import emit, timeit
    from repro.compat import shard_map
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("model",))
    V, D, N = 1 << 18, 64, 4096
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)

    def sharded(tbl, ix):
        Vl = tbl.shape[0]
        off = jax.lax.axis_index("model") * Vl
        local = ix - off
        ok = (local >= 0) & (local < Vl)
        e = tbl[jnp.clip(local, 0, Vl - 1)]
        e = jnp.where(ok[:, None], e, 0.0)
        return jax.lax.psum(e, "model")       # P(sum) -> B

    def replicated(tbl, ix):
        return tbl[ix]

    p1 = jax.jit(shard_map(sharded, mesh=mesh,
                           in_specs=(P("model"), P()), out_specs=P(),
                           check=False))
    p2 = jax.jit(shard_map(replicated, mesh=mesh,
                           in_specs=(P(), P()), out_specs=P(),
                           check=False))
    us1 = timeit(p1, table, ids, iters=5)
    us2 = timeit(p2, table, ids, iters=5)
    emit("embedding_mp/vocab_split_S0", us1,
         f"table_bytes_per_dev={V*D*4//8}")
    emit("embedding_mp/replicated_B", us2,
         f"table_bytes_per_dev={V*D*4}")


if __name__ == "__main__":
    main()
