"""Table 2 validation: analytic boxing costs vs HLO-parsed wire bytes.

For every same-set SBP transition, build the boxing collective on an 8-way
axis, lower it, parse the emitted collective from the StableHLO, and compare
per-device wire bytes against the Table-2 prediction. derived column:
``predicted=<bytes>;parsed=<bytes>``.
"""
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
    from repro.compat import shard_map
    from repro.core.boxing import boxing_fn, transition_cost
    from repro.core.sbp import Sbp, ndsbp
    from repro.launch.mesh import make_mesh
    from repro.launch.dryrun import _HloTextParser, wire_bytes
    from benchmarks._util import emit, timeit

    mesh = make_mesh((8,), ("x",))
    shape = (256, 512)
    T = 256 * 512 * 4

    cases = [("S(0)", "S(1)"), ("S(0)", "B"), ("B", "S(0)"),
             ("P", "S(0)"), ("P", "B"), ("S(1)", "S(0)")]
    for src, dst in cases:
        pred = transition_cost(Sbp.parse(src), Sbp.parse(dst), T, 8)
        fn = boxing_fn(ndsbp(src), ndsbp(dst), ("x",), (8,), shape)
        src_clean = "B" if src.startswith("P") else src
        dst_clean = "B" if dst.startswith("P") else dst

        def pspec(sig):
            nd = ndsbp(sig)
            comp = nd[0]
            if comp.is_split:
                return P(*(["x"] if comp.axis == 0 else [None, "x"]))
            return P()

        prog = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(pspec(src_clean),),
            out_specs=pspec(dst_clean), check=False))
        x = jnp.asarray(np.random.default_rng(0).normal(size=shape),
                        jnp.float32)
        lowered = prog.lower(x)
        parser = _HloTextParser(lowered.as_text())
        parsed = sum(wire_bytes(c) * c["trip"] for c in parser.collectives)
        us = timeit(prog, x, iters=5)
        emit(f"table2/{src}->{dst}", us,
             f"predicted={pred.volume:.0f};parsed={parsed:.0f};"
             f"prim={pred.primitive}")


if __name__ == "__main__":
    main()
