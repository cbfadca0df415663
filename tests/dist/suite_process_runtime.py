"""Multi-device suite: the process-backed runtime on real meshes.

Each ProcessRuntime worker is a fresh spawned interpreter; the parent's
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` is inherited
verbatim (repro.launch.xla_env.worker_env), so every worker re-lowers its
stages against the same 8-device table the driver planned with. The claim
under test: swapping the transport (threads -> processes) changes *nothing*
numerically, even when stages run on multi-device meshes —

* train: 4 stages on a 2-device data-parallel placement, 3 AdamW steps
  with global-norm clipping, bitwise (loss/grads/params/opt state) against
  the threaded session;
* serve: 2 stages on a (1, 2) model-parallel mesh (sequence-sharded KV
  cache), token streams identical to the threaded engine (which the serve
  suite already ties to the monolithic reference).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))

import numpy as np

STAGES, MICROBATCHES, BATCH, WIDTH = 4, 4, 16, 32
PROMPT_LEN = 8


def _graph(placement):
    from repro.core.graph import LogicalGraph

    g = LogicalGraph(placement)
    h = g.input("x", (BATCH, WIDTH), sbp="S(0)")
    labels = g.input("labels", (BATCH,), dtype="int32", sbp="S(0)")
    for i in range(STAGES):
        w = g.input(f"w{i}", (WIDTH, WIDTH))
        h = g.matmul(h, w, name=f"mm{i}")
        if i < STAGES - 1:
            h = g.unary(h, "relu", name=f"relu{i}")
    g.softmax_xent(h, labels, name="loss")
    return g


def train_processes_match_threads():
    from repro import api
    from repro.core.lowering import OptimizerSpec
    from repro.core.placement import Placement

    placement = Placement(("data",), (2,), device_kind="cpu")
    rng = np.random.default_rng(5)
    params = {f"w{i}": (rng.normal(size=(WIDTH, WIDTH)) * 0.5
                        ).astype(np.float32) for i in range(STAGES)}
    data = {"x": rng.normal(size=(BATCH, WIDTH)).astype(np.float32),
            "labels": rng.integers(0, WIDTH, (BATCH,)).astype(np.int32)}
    opt = OptimizerSpec.adamw(lr=1e-2, grad_clip=0.5)
    kw = dict(mode="train", stages=STAGES, num_microbatches=MICROBATCHES,
              optimizer=opt)
    st = api.compile(_graph(placement), runtime="threads",
                     params=dict(params), **kw)
    sp = api.compile(_graph(placement), runtime="processes",
                     params=dict(params), **kw)
    try:
        api.assert_sessions_match(sp, st, data, steps=3)
        assert int(sp.opt_state.step) == 3
        assert any(v > 0 for v in sp.executor.last_edge_bytes.values())
    finally:
        sp.close()
        st.close()
    print(f"train dp(2): {STAGES} stages x 3 AdamW steps bitwise across "
          f"process workers")


def serve_processes_match_threads():
    import jax

    from repro import api
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build_model
    from repro.train.steps import plan_from_mesh

    cfg = get_config("qwen2.5-3b").reduced()
    cfg = dataclasses.replace(cfg, vocab_size=1000)
    mesh = make_mesh((1, 2), ("data", "model"))
    params = build_model(cfg, plan_from_mesh(mesh)).init(
        jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    gens = [2, 4, 3]
    prompts = [rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).astype(
        np.int32) for _ in gens]
    kw = dict(mode="serve", stages=2, params=params, mesh=mesh,
              num_groups=2, group_size=1, max_prompt_len=PROMPT_LEN,
              max_new_tokens=max(gens))
    st = api.compile(cfg, runtime="threads", **kw)
    sp = api.compile(cfg, runtime="processes", **kw)
    try:
        ot = st.generate(list(zip(prompts, gens)))
        op = sp.generate(list(zip(prompts, gens)))
        for i, (a, b) in enumerate(zip(ot, op)):
            assert np.array_equal(a, b), (i, a, b)
    finally:
        sp.close()
        st.close()
    print(f"serve mp(1x2): {sum(gens)} tokens identical across process "
          f"workers")


if __name__ == "__main__":
    train_processes_match_threads()
    serve_processes_match_threads()
    print("ALL-OK")
