"""1F1B pipeline-parallel *training* from register quotas (§4.3, §6.5).

Two `api.compile` calls on the same logical graph — `backend="actors"` and
`backend="monolithic"` — give two Sessions with the same `step()` surface.
The actor one cuts the graph into stages, differentiates each with a
per-stage ``jax.vjp``, and streams microbatches through fwd/bwd stage
actors; no schedule table anywhere — the forward out-register quota
``R[s] = S - s`` alone produces the 1F1B overlap, and `regs="serial"` runs
the same graph fully serialized. Bit-identical numbers either way.

Run (either form works from the repo root):

    python examples/train_1f1b.py
    python -m examples.train_1f1b
"""
try:
    from examples import _bootstrap  # noqa: F401  (python -m examples.train_1f1b)
except ImportError:
    import _bootstrap  # noqa: F401  (python examples/train_1f1b.py)

import numpy as np

from repro import api
from repro.core.graph import LogicalGraph
from repro.core.placement import Placement

STAGES, MICROBATCHES, BATCH, WIDTH = 4, 8, 64, 128
STEPS = 5


def build():
    placement = Placement(("d",), (1,), device_kind="cpu")
    g = LogicalGraph(placement)
    h = g.input("x", (BATCH, WIDTH))
    labels = g.input("labels", (BATCH,), dtype="int32")
    for i in range(STAGES):
        w = g.input(f"w{i}", (WIDTH, WIDTH))
        h = g.matmul(h, w, name=f"mm{i}")
        if i < STAGES - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    g.softmax_xent(h, labels, name="loss")
    return g


def main():
    g = build()
    rng = np.random.default_rng(0)
    params = {f"w{i}": (rng.normal(size=(WIDTH, WIDTH)) * 0.1).astype(np.float32)
              for i in range(STAGES)}
    data = {"x": rng.normal(size=(BATCH, WIDTH)).astype(np.float32),
            "labels": rng.integers(0, WIDTH, size=(BATCH,)).astype(np.int32)}

    mono = api.compile(g, mode="train", backend="monolithic",
                       params=dict(params), num_microbatches=MICROBATCHES)
    pipe = api.compile(g, mode="train", backend="actors", stages=STAGES,
                       params=dict(params), num_microbatches=MICROBATCHES,
                       regs="1f1b")
    print(pipe.describe())
    for st in pipe.executor.tstaged.stages:
        print(f"  stage {st.index}: fwd {list(st.input_names)} -> "
              f"{list(st.output_names)}; params {list(st.param_names)}")

    for step in range(STEPS):
        mres = mono.step(**data)
        pres = pipe.step(**data)
        bit = (mres.loss == pres.loss) and all(
            bool(np.all(np.asarray(mres.grads[n]) ==
                        np.asarray(pres.grads[n])))
            for n in params)
        print(f"step {step}: loss {float(pres.loss):10.4f}   "
              f"makespan {pres.metrics['makespan'] * 1e3:6.1f} ms   "
              f"peak in-flight {pres.metrics['peak_inflight']}   "
              f"bit-identical to monolithic: {bool(bit)}")
    print("(loss falls, the pipeline and the monolithic step agree bitwise; "
          "api.assert_sessions_match(pipe, mono, data, steps=N) is the "
          "one-liner form)")


if __name__ == "__main__":
    main()
