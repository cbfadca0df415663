"""Fig 11/12 (InsightFace): hierarchical sharded-vocab softmax-xent vs the
naive all-gather-logits implementation, on an 8-way model axis.

derived: parsed collective wire bytes per device for each plan — the
hierarchical (local-reduce) version moves O(rows) stats instead of the
O(rows x vocab) logits."""
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
    from repro.kernels.softmax_xent.ref import combine_stats, local_stats_ref
    from repro.launch.mesh import make_mesh
    from repro.launch.dryrun import _HloTextParser, wire_bytes

    mesh = make_mesh((8,), ("model",))
    N, V = 2048, 8192
    Vl = V // 8
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(N, V)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)

    def hierarchical(lg, lb):
        off = jax.lax.axis_index("model") * Vl
        m, s, z = local_stats_ref(lg, lb, off)
        tok = combine_stats(m, s, z, axis_name="model")
        return jax.lax.pmean(tok.mean(), "model")

    def allgather(lg, lb):
        full = jax.lax.all_gather(lg, "model", axis=1, tiled=True)
        m, s, z = local_stats_ref(full, lb, 0)
        tok = jnp.log(s) + m - z
        return jax.lax.pmean(tok.mean(), "model")

    for name, fn in (("hierarchical", hierarchical), ("allgather", allgather)):
        prog = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(None, "model"), P()),
            out_specs=P(), check=False))
        lowered = prog.lower(logits, labels)
        parsed = sum(wire_bytes(c) * c["trip"]
                     for c in _HloTextParser(lowered.as_text()).collectives)
        us = timeit(prog, logits, labels, iters=5)
        emit(f"mp_softmax/{name}", us, f"wire_bytes_per_dev={parsed:.0f}")


if __name__ == "__main__":
    main()
